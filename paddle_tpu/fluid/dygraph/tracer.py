"""Eager tracer + tape autograd.

Reference: paddle/fluid/imperative/tracer.cc:81 Tracer::TraceOp (runs the op
through the shared kernel registry and records OpBase for backward),
engine.cc BasicEngine::Execute (reverse walk + GradientAccumulator),
layer.h:55 VarBase.

Here TraceOp runs the op's JAX lowering immediately on concrete jax.Arrays;
the tape stores (type, input/output VarBases, attrs) and backward replays
grad-maker specs through the same lowering rules — so eager and static mode
share one op implementation, like the reference."""

from __future__ import annotations

import numpy as np

from .. import core
from .. import unique_name
from ..ops import registry as _registry
from ..ops.registry import LowerCtx, _FakeOp


def _guard_backend():
    """Backend of the ``dygraph.guard(place)`` in force: eager values are
    put on that Place's device (``to_variable``), so eager lowerings pick
    their device-native code for it."""
    from .. import framework

    return core._jax_backend_for(framework._current_expected_place())


class VarBase(object):
    """Eager tensor: jax.Array + grad slot (reference: imperative/layer.h:55)."""

    def __init__(self, value=None, name=None, persistable=False,
                 stop_gradient=False, is_parameter=False):
        self.name = name or unique_name.generate("eager_tmp")
        self._value = value
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_parameter = is_parameter
        self._grad = None
        self.trainable = not stop_gradient

    # -- value access --
    @property
    def value(self):
        return self._value

    def set_value(self, v):
        import jax.numpy as jnp

        self._value = jnp.asarray(np.asarray(v)) if not hasattr(v, "dtype") else v

    def numpy(self):
        return np.asarray(self._value)

    @property
    def shape(self):
        return tuple(self._value.shape) if self._value is not None else ()

    @property
    def dtype(self):
        return core.np_to_dtype(np.asarray(self._value).dtype)

    def detach(self):
        out = VarBase(self._value, stop_gradient=True)
        return out

    # -- autograd --
    def backward(self, backward_strategy=None):
        from .base import _current_tracer

        tracer = _current_tracer()
        if tracer is None:
            raise RuntimeError("backward() outside dygraph guard")
        tracer.run_backward(self, backward_strategy)

    def gradient(self):
        if self._grad is None:
            return None
        return np.asarray(self._grad)

    def clear_gradient(self):
        self._grad = None

    @property
    def grad(self):
        return self._grad

    def __repr__(self):
        return "VarBase(name=%s, shape=%s)" % (self.name, list(self.shape))

    # math ops route through the tracer so the tape sees them
    def _binary(self, other, op_type, reverse=False):
        from .base import _current_tracer

        tracer = _current_tracer()
        x, y = self, other
        if np.isscalar(other):
            if op_type == "scale":
                pass
            y = VarBase(
                _as_jax(np.full((1,), other, self.numpy().dtype)),
                stop_gradient=True,
            )
        if reverse:
            x, y = y, x
        outs = tracer.trace_op(
            op_type, {"X": [x], "Y": [y]}, {"Out": 1}, {"axis": -1}
        )
        return outs["Out"][0]

    def __add__(self, o):
        return self._binary(o, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "elementwise_sub")

    def __rsub__(self, o):
        return self._binary(o, "elementwise_sub", reverse=True)

    def __mul__(self, o):
        return self._binary(o, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "elementwise_div")


def _as_jax(v):
    import jax.numpy as jnp

    return jnp.asarray(v)


class _EnvScope(object):
    """Scope view over the eager env dict so HOST op lowerings (which
    use ctx.scope.get/set) run under the dygraph tracer too."""

    __slots__ = ("_env",)

    def __init__(self, env):
        self._env = env

    def get(self, name, default=None):
        return self._env.get(name, default)

    def set(self, name, value):
        self._env[name] = value


class _TapeEntry(object):
    __slots__ = ("type", "inputs", "outputs", "attrs")

    def __init__(self, type, inputs, outputs, attrs):
        self.type = type
        self.inputs = inputs  # {slot: [VarBase]}
        self.outputs = outputs
        self.attrs = attrs


class Tracer(object):
    def __init__(self):
        self._tape = []
        self._no_grad = False
        import jax

        self._key = jax.random.key(np.random.randint(0, 2**31 - 1))
        self._key_counter = 0

    def _next_key(self):
        import jax

        k = jax.random.fold_in(self._key, self._key_counter)
        self._key_counter += 1
        return k

    def trace_op(self, type, inputs, outputs, attrs, stop_gradient=False):
        """Execute op eagerly; returns {slot: [VarBase]} for outputs.

        `outputs` maps slot -> int (number of outputs to create) or a list of
        existing VarBases to write into."""
        opdef = _registry.get_op_def(type)
        if opdef is None or opdef.lower is None:
            raise NotImplementedError("no lowering for dygraph op %r" % type)

        in_names = {}
        env = {}
        for slot, vars_ in inputs.items():
            vars_ = vars_ if isinstance(vars_, (list, tuple)) else [vars_]
            names = []
            for v in vars_:
                if v is None:
                    continue
                names.append(v.name)
                env[v.name] = v.value
            in_names[slot] = names

        out_vars = {}
        out_names = {}
        for slot, spec in outputs.items():
            if isinstance(spec, int):
                vs = [VarBase(stop_gradient=stop_gradient) for _ in range(spec)]
            else:
                vs = spec if isinstance(spec, (list, tuple)) else [spec]
            out_vars[slot] = list(vs)
            out_names[slot] = [v.name for v in vs]

        fake = _FakeOp(type, in_names, out_names, dict(attrs or {}))
        # host ops (print, detection/NMS, tree walks, ...) read and write
        # through ctx.scope; in eager mode the env IS the scope
        ctx = LowerCtx(env=env, base_key=self._next_key(),
                       scope=_EnvScope(env))
        with _registry.lowering_on(_guard_backend()):
            opdef.lower(ctx, fake)

        for slot, vs in out_vars.items():
            for v in vs:
                if v.name in env:
                    v._value = env[v.name]

        if not self._no_grad and not stop_gradient:
            self._tape.append(
                _TapeEntry(
                    type,
                    {k: list(v) if isinstance(v, (list, tuple)) else [v]
                     for k, v in inputs.items()},
                    out_vars,
                    dict(attrs or {}),
                )
            )
        return out_vars

    # -- backward (reference: BasicEngine::Execute, engine.cc) --
    def run_backward(self, loss, backward_strategy=None):
        with _registry.lowering_on(_guard_backend()):
            self._run_backward(loss, backward_strategy)

    def _run_backward(self, loss, backward_strategy):
        import jax.numpy as jnp

        sorted_sum = bool(
            backward_strategy is not None
            and getattr(backward_strategy, "sorted_sum_gradient", False)
        )
        grads = {}  # VarBase id -> jax array (reverse-encounter accumulation)
        grads[id(loss)] = jnp.ones_like(loss.value)
        holders = {id(loss): loss}
        # BackwardStrategy.sorted_sum_gradient: per-var contribution list
        # tagged with the producing entry's tape index, so the final sum
        # runs in FORWARD-op order (backward_strategy.h:24 semantics).
        # Only tracked when requested — the lists would otherwise pin one
        # extra buffer per gradient edge for the whole backward
        contribs = (
            {id(loss): [(len(self._tape), grads[id(loss)])]}
            if sorted_sum else None
        )

        for tape_idx, entry in zip(
            range(len(self._tape) - 1, -1, -1), reversed(self._tape)
        ):
            out_has_grad = any(
                id(v) in grads
                for vs in entry.outputs.values()
                for v in vs
            )
            if not out_has_grad:
                continue
            opdef = _registry.get_op_def(entry.type)
            if opdef is None or opdef.grad_maker is None:
                continue
            in_names = {
                slot: [v.name for v in vs] for slot, vs in entry.inputs.items()
            }
            out_names = {
                slot: [v.name for v in vs] for slot, vs in entry.outputs.items()
            }
            fake_fwd = _FakeOp(entry.type, in_names, out_names, entry.attrs)
            specs = opdef.grad_maker(fake_fwd)

            env = {}
            for vs in entry.inputs.values():
                for v in vs:
                    env[v.name] = v.value
            for vs in entry.outputs.values():
                for v in vs:
                    env[v.name] = v.value
                    if id(v) in grads:
                        env[v.name + "@GRAD"] = grads[id(v)]

            by_name = {}
            for vs in entry.inputs.values():
                for v in vs:
                    by_name[v.name + "@GRAD"] = v

            for spec in specs:
                gop = _FakeOp(
                    spec["type"], spec["inputs"], spec["outputs"], spec["attrs"]
                )
                gdef = _registry.get_op_def(spec["type"])
                ctx = LowerCtx(env=env)
                gdef.lower(ctx, gop)
                for slot, names in spec["outputs"].items():
                    for n in names:
                        if n == _registry.EMPTY_VAR or n not in env:
                            continue
                        target = by_name.get(n)
                        if target is None or target.stop_gradient:
                            continue
                        g = env[n]
                        if id(target) in grads:
                            grads[id(target)] = grads[id(target)] + g
                        else:
                            grads[id(target)] = g
                        if contribs is not None:
                            contribs.setdefault(id(target), []).append(
                                (tape_idx, g)
                            )
                        holders[id(target)] = target

        if sorted_sum:
            # deterministic forward-order accumulation for the final grads
            def _forward_order_sum(cs):
                cs = sorted(cs, key=lambda c: c[0])
                total = cs[0][1]
                for _i, g in cs[1:]:
                    total = total + g
                return total

            grads = {
                vid: _forward_order_sum(cs) for vid, cs in contribs.items()
            }

        # write accumulated grads onto VarBases (GradientAccumulator)
        for vid, g in grads.items():
            vb = holders.get(vid)
            if vb is not None and not vb.stop_gradient:
                if vb._grad is None:
                    vb._grad = g
                else:
                    vb._grad = vb._grad + g
        self._tape = []

    def reset(self):
        self._tape = []
