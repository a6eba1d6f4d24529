"""Op registry and lowering machinery.

The analogue of the reference's OpInfoMap / REGISTER_OPERATOR
(paddle/fluid/framework/op_registry.h:199, op_info.h) redesigned for XLA:
instead of (type -> kernel functor per place), each OpDef carries

- ``infer_shape(op, block)``  — compile-time shape/dtype propagation
  (reference: framework/shape_inference.h compile-time path),
- ``lower(ctx, op)``          — the JAX lowering rule, executed while tracing
  a whole block into one XLA computation,
- ``grad_maker(op, ...)``     — desc-level grad-op construction
  (reference protocol: framework/grad_op_desc_maker.h:39); defaults to a
  generic maker whose lowering is ``jax.vjp`` of the forward rule.

Grad naming contract matches the reference: grad of var ``x`` is ``x@GRAD``.
"""

from __future__ import annotations

import contextlib
import threading

import numpy as np

GRAD_SUFFIX = "@GRAD"
EMPTY_VAR = "@EMPTY@"

# attr keys used to carry the forward op signature on generic grad ops
FWD_INPUTS_ATTR = "__fwd_inputs__"
FWD_OUTPUTS_ATTR = "__fwd_outputs__"


class SkipInferShape(Exception):
    """Raised by infer_shape rules that can't infer (e.g. unknown dims)."""


class OpDef(object):
    __slots__ = (
        "type",
        "infer_shape",
        "lower",
        "grad_maker",
        "host",
        "stateful_inputs",
    )

    def __init__(
        self, type, infer_shape=None, lower=None, grad_maker=None, host=False,
        stateful_inputs=(),
    ):
        self.type = type
        self.infer_shape = infer_shape
        self.lower = lower
        self.grad_maker = grad_maker
        self.host = host  # True: runs on host python, splits the XLA segment
        # input slots that alias an output (in-place update, e.g. optimizer
        # Param/ParamOut) — informs buffer donation
        self.stateful_inputs = tuple(stateful_inputs)


_REGISTRY = {}


def register_op(
    type,
    infer_shape=None,
    lower=None,
    grad=None,
    host=False,
    stateful_inputs=(),
):
    """Register an op. ``grad`` may be:
    - "generic": use the generic vjp-backed grad maker,
    - None: op has no gradient (grad ops never generated),
    - callable(op) -> list[op-spec dict]: custom desc-level grad maker.
    """
    grad_maker = generic_grad_maker if grad == "generic" else grad
    d = OpDef(
        type,
        infer_shape=infer_shape,
        lower=lower,
        grad_maker=grad_maker,
        host=host,
        stateful_inputs=stateful_inputs,
    )
    _REGISTRY[type] = d
    return d


def op(type, **kwargs):
    """Decorator form: @op("relu", grad="generic") def lower(ctx, op)."""

    def deco(fn):
        register_op(type, lower=fn, **kwargs)
        return fn

    return deco


def get_op_def(type):
    d = _REGISTRY.get(type)
    if d is None and type.endswith("_grad"):
        base = _REGISTRY.get(type[: -len("_grad")])
        if base is not None and base.lower is not None:
            # synthesize a generic vjp grad def (cached)
            d = OpDef(type, lower=_generic_grad_lower)
            _REGISTRY[type] = d
    return d


def has_op(type):
    return get_op_def(type) is not None


def all_op_types():
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Lowering context
# ---------------------------------------------------------------------------
# backend name ("cpu"/"tpu") of the device the current trace targets, or
# None outside any trace. The executor/tracer scopes each trace with
# ``lowering_on(backend)``, resolved from its Place; lowering rules branch
# on it to pick device-native code (NHWC convs and the Pallas kernels on
# the TPU, the jnp reference elsewhere) — never on jax.default_backend(),
# which on a chip host says "tpu" for a CPUPlace program too. ``mesh`` is
# the jax Mesh a GSPMD trace is partitioned over (None for one device, and
# for the legacy shard_map path, whose lowerings already see local shards):
# what GSPMD cannot split itself — a Pallas kernel — wraps itself per shard
# over it. Per thread: a serving process traces from its engine and
# predictor threads at once.
class _Lowering(threading.local):
    target = (None, None)  # (backend, mesh)


_lowering = _Lowering()


@contextlib.contextmanager
def lowering_on(backend, mesh=None):
    prev, _lowering.target = _lowering.target, (backend, mesh)
    try:
        yield
    finally:
        _lowering.target = prev


def lowering_backend():
    return _lowering.target[0]


def lowering_mesh():
    return _lowering.target[1]


class LowerCtx(object):
    """Environment threaded through the lowering of one block segment.

    ``env`` maps var name -> traced jax value. ``base_key`` is a jax PRNG key
    (traced input) for random ops; each random op takes ``next_key()``.
    ``mesh_axes`` names the SPMD mesh axes this block is being traced under
    (e.g. {"data": 8}) — collective ops lower to lax collectives over these
    axes; empty means single-device and collectives become identities.
    """

    def __init__(self, env=None, base_key=None, mesh_axes=None, block=None,
                 scope=None, dist_specs=None):
        self.env = env if env is not None else {}
        self.base_key = base_key
        self._key_counter = 0
        self.mesh_axes = dict(mesh_axes or {})
        self.block = block
        self.scope = scope  # host-side scope, only for host ops
        self._cur_op = None  # op currently being lowered (set by run_op)
        # var name -> dist_attr tuple for TP-sharded vars (Megatron-style
        # matmul rules consult this; empty when not tracing under a mesh)
        self.dist_specs = dict(dist_specs or {})

    # -- env access --
    def get(self, name):
        if name == EMPTY_VAR:
            return None
        try:
            return self.env[name]
        except KeyError:
            raise KeyError(
                "var %r is not materialized in the lowering environment"
                % name
            )

    def get_opt(self, name):
        if name == EMPTY_VAR:
            return None
        return self.env.get(name)

    def set(self, name, value):
        if name != EMPTY_VAR:
            self.env[name] = value

    # -- op-relative access --
    def in1(self, op, slot, idx=0, optional=False):
        names = op.inputs.get(slot) or []
        if not names or names[idx] == EMPTY_VAR:
            if optional:
                return None
            raise KeyError("op %s missing input slot %r" % (op.type, slot))
        return self.get(names[idx]) if not optional else self.get_opt(names[idx])

    def ins(self, op, slot):
        return [self.get(n) for n in op.inputs.get(slot, []) if n != EMPTY_VAR]

    def out(self, op, slot, value, idx=0):
        names = op.outputs.get(slot) or []
        if names and names[idx] != EMPTY_VAR:
            self.set(names[idx], value)

    def outs(self, op, slot, values):
        names = op.outputs.get(slot) or []
        for n, v in zip(names, values):
            if n != EMPTY_VAR:
                self.set(n, v)

    def next_key(self):
        """PRNG key for the op being lowered. Derivation rules (matching the
        reference's seeding semantics, e.g. uniform_random_op.cc `seed`
        attr):
        - op has a nonzero ``seed`` attr -> key(seed): fully deterministic,
          independent of everything else;
        - otherwise fold the (program-seed, step) base key by a hash of the
          op's first output name: the same var gets the same init in every
          process regardless of which subset of ops the program contains
          (required for trainer/pserver init agreement in dist training);
        - no current op (direct lowering-rule calls) -> positional counter.
        """
        import jax

        if self.base_key is None:
            raise RuntimeError(
                "random op lowered without a PRNG key — executor must pass one"
            )
        op = self._cur_op
        seed_attr = 0
        salt = None
        if op is not None:
            try:
                seed_attr = int(op.attr("seed", 0) or 0)
            except Exception:
                seed_attr = 0
            for slot in sorted(op.outputs or {}):
                for n in op.outputs[slot]:
                    if n != EMPTY_VAR:
                        salt = n
                        break
                if salt is not None:
                    break
        if seed_attr:
            k = jax.random.key(seed_attr)
        elif salt is not None:
            import zlib

            k = jax.random.fold_in(
                self.base_key, zlib.crc32(salt.encode()) & 0x7FFFFFFF
            )
        else:
            k = jax.random.fold_in(self.base_key, self._key_counter)
        self._key_counter += 1
        axis = self.data_axis
        if axis is not None:
            # distinct randomness per shard (the reference's per-device
            # cuRAND streams); axis_index is free inside shard_map
            k = jax.random.fold_in(k, jax.lax.axis_index(axis))
        return k

    @property
    def data_axis(self):
        """Name of the data-parallel mesh axis if tracing under one."""
        for name in ("data", "dp"):
            if name in self.mesh_axes:
                return name
        return None

    def dist_spec(self, name):
        return self.dist_specs.get(name)

    def axis_size(self, axis_name):
        return self.mesh_axes.get(axis_name, 1)


class OpError(RuntimeError):
    """Lowering/runtime failure annotated with the op's Python creation
    site (reference: framework/op_call_stack.cc InsertCallStackInfo)."""


def run_op(ctx, op):
    """Lower a single op into the context environment."""
    d = get_op_def(op.type)
    if d is None or d.lower is None:
        raise NotImplementedError(
            "no lowering rule registered for op %r" % op.type
        )
    prev = ctx._cur_op
    ctx._cur_op = op
    try:
        d.lower(ctx, op)
    except OpError:
        raise
    except Exception as e:
        stack = op.attr("op_callstack") if hasattr(op, "attr") else None
        site = (
            "\n  defined at:\n    " + "\n    ".join(stack)
            if stack
            else ""
        )
        raise OpError(
            "error lowering op %r: %s: %s%s"
            % (op.type, type(e).__name__, e, site)
        ) from e
    finally:
        ctx._cur_op = prev


# ---------------------------------------------------------------------------
# Generic grad: desc maker + vjp lowering
# ---------------------------------------------------------------------------
def generic_grad_maker(op):
    """Grad-op spec with the reference naming convention: inputs are the
    forward inputs, forward outputs, and output grads (slot ``S@GRAD``);
    outputs are input grads. The forward signature is recorded in attrs so
    the vjp lowering can re-trace the forward rule."""
    g_inputs = {}
    for slot, names in op.inputs.items():
        g_inputs[slot] = list(names)
    for slot, names in op.outputs.items():
        g_inputs[slot] = list(names)
        g_inputs[slot + GRAD_SUFFIX] = [n + GRAD_SUFFIX for n in names]
    g_outputs = {
        slot + GRAD_SUFFIX: [n + GRAD_SUFFIX for n in names]
        for slot, names in op.inputs.items()
    }
    attrs = dict(op.attrs)
    attrs[FWD_INPUTS_ATTR] = {k: list(v) for k, v in op.inputs.items()}
    attrs[FWD_OUTPUTS_ATTR] = {k: list(v) for k, v in op.outputs.items()}
    return [
        dict(
            type=op.type + "_grad",
            inputs=g_inputs,
            outputs=g_outputs,
            attrs=attrs,
        )
    ]


class _FakeOp(object):
    """Lightweight op stand-in for re-tracing a forward rule inside vjp."""

    __slots__ = ("type", "inputs", "outputs", "attrs")

    def __init__(self, type, inputs, outputs, attrs):
        self.type = type
        self.inputs = inputs
        self.outputs = outputs
        self.attrs = attrs

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def has_attr(self, name):
        return name in self.attrs

    def input(self, slot):
        return list(self.inputs.get(slot, []))

    def output(self, slot):
        return list(self.outputs.get(slot, []))


def _is_float(v):
    import jax.numpy as jnp

    return v is not None and jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)


def _generic_grad_lower(ctx, op):
    """Lower ``foo_grad`` via jax.vjp of foo's forward rule.

    The recomputed forward is CSE'd by XLA against the original forward in
    the same block program, so this costs nothing at run time while keeping
    the per-op grad-kernel surface near zero (the reference needed a
    hand-written grad kernel per op: e.g. operators/conv_op.cc grad +
    conv_cudnn_op.cu — here one rule covers all).
    """
    import jax
    import jax.numpy as jnp

    fwd_type = op.type[: -len("_grad")]
    fwd_def = get_op_def(fwd_type)
    fwd_inputs = op.attr(FWD_INPUTS_ATTR)
    fwd_outputs = op.attr(FWD_OUTPUTS_ATTR)
    if fwd_inputs is None or fwd_outputs is None:
        raise NotImplementedError(
            "generic grad for %s requires maker-recorded signature" % op.type
        )

    # which (slot, idx) entries we need grads for
    wrt = []  # [(slot, idx, name)]
    for gslot, gnames in op.outputs.items():
        if not gslot.endswith(GRAD_SUFFIX):
            continue
        slot = gslot[: -len(GRAD_SUFFIX)]
        for idx, gname in enumerate(gnames):
            if gname == EMPTY_VAR:
                continue
            src_names = fwd_inputs.get(slot, [])
            if idx < len(src_names):
                val = ctx.get_opt(src_names[idx])
                if _is_float(val):
                    wrt.append((slot, idx, gname))

    if not wrt:
        return

    primal_vals = tuple(
        ctx.get(fwd_inputs[slot][idx]) for slot, idx, _ in wrt
    )
    # deterministic flat order of forward outputs
    out_order = [
        (slot, idx, name)
        for slot in sorted(fwd_outputs)
        for idx, name in enumerate(fwd_outputs[slot])
        if name != EMPTY_VAR
    ]

    attrs = {
        k: v
        for k, v in op.attrs.items()
        if k not in (FWD_INPUTS_ATTR, FWD_OUTPUTS_ATTR)
    }

    def fwd_fn(*vals):
        env = dict()
        # base: all forward inputs from the outer env (+ their `@SEQ_LEN`
        # ragged-length companions, which sequence-op rules mask with)
        for slot, names in fwd_inputs.items():
            for n in names:
                if n != EMPTY_VAR:
                    v = ctx.get_opt(n)
                    if v is not None:
                        env[n] = v
                    lv = ctx.get_opt(n + "@SEQ_LEN")
                    if lv is not None:
                        env[n + "@SEQ_LEN"] = lv
        for (slot, idx, _), v in zip(wrt, vals):
            env[fwd_inputs[slot][idx]] = v
        # block threads through so ops with sub-blocks (recurrent,
        # dynamic_decode) can resolve them during the vjp replay; base_key
        # threads through so random forwards (nce sampling, dropout) replay
        # the same draws under the vjp
        sub = LowerCtx(
            env=env, base_key=ctx.base_key, mesh_axes=ctx.mesh_axes,
            block=ctx.block
        )
        fake = _FakeOp(fwd_type, fwd_inputs, fwd_outputs, attrs)
        fwd_def.lower(sub, fake)
        return tuple(
            env.get(name) for _, _, name in out_order
        )

    outs, vjp_fn = jax.vjp(fwd_fn, *primal_vals)

    cots = []
    for (slot, idx, name), o in zip(out_order, outs):
        og = ctx.get_opt(name + GRAD_SUFFIX)
        # the grad op lists OG inputs under slot "S@GRAD"
        og_names = op.inputs.get(slot + GRAD_SUFFIX, [])
        if og is None and idx < len(og_names):
            og = ctx.get_opt(og_names[idx])
        if o is not None and not _is_float(o):
            # an integer output (a count, an index) carries no gradient:
            # its cotangent has the one type jax takes for it
            og = np.zeros(o.shape, jax.dtypes.float0)
        elif og is None:
            og = jnp.zeros_like(o) if o is not None else None
        cots.append(og)

    grads = vjp_fn(tuple(cots))
    for (slot, idx, gname), g in zip(wrt, grads):
        ctx.set(gname, g)


# ---------------------------------------------------------------------------
# generic infer_shape: abstract interpretation of the lowering rule
# ---------------------------------------------------------------------------
# dynamic dims (-1) are probed with this size; output dims equal to it are
# mapped back to -1 (batch-dim propagation heuristic)
_PROBE_DIM = 977


def generic_infer_shape(op, block):
    """Compile-time shape/dtype propagation with NO per-op rule: run the
    op's own lowering under jax.eval_shape on ShapeDtypeStructs built from
    the block's var metadata. The reference needed a hand-written
    InferShape per op (framework/shape_inference.h); here the lowering IS
    the shape function — abstract evaluation costs no FLOPs and cannot
    disagree with runtime behavior."""
    import jax

    d = get_op_def(op.type)
    if d is None or d.lower is None or d.host:
        raise SkipInferShape()
    if op.has_attr("sub_block"):
        raise SkipInferShape()  # control flow resolves shapes at lowering

    in_structs = {}
    for name in op.input_arg_names:
        if name == EMPTY_VAR:
            continue
        v = block._find_var_recursive(name)
        if v is None or v.shape is None:
            raise SkipInferShape()
        shape = tuple(
            _PROBE_DIM if int(s) < 0 else int(s) for s in v.shape
        )
        try:
            dt = np.dtype(v.dtype) if not isinstance(v.dtype, int) else None
        except TypeError:
            dt = None
        if dt is None:
            from .. import core as _core

            dt = _core.dtype_to_np(v.dtype)
        in_structs[name] = jax.ShapeDtypeStruct(shape, dt)

    out_names = [n for n in op.output_arg_names if n != EMPTY_VAR]

    def trace(env_in):
        env = dict(env_in)
        ctx = LowerCtx(
            env=env, base_key=jax.random.key(0), block=block
        )
        ctx._cur_op = op
        d.lower(ctx, op)
        return {n: env[n] for n in out_names if n in env}

    try:
        outs = jax.eval_shape(trace, in_structs)
    except Exception:
        raise SkipInferShape()

    for n, st in outs.items():
        v = block._find_var_recursive(n)
        if v is None:
            continue
        from .. import core as _core

        v.shape = tuple(
            -1 if int(s) == _PROBE_DIM else int(s) for s in st.shape
        )
        v.dtype = _core.np_to_dtype(st.dtype)


# ---------------------------------------------------------------------------
# infer_shape helpers
# ---------------------------------------------------------------------------
def set_out(op, block, slot, shape, dtype=None, idx=0):
    names = op.outputs.get(slot) or []
    if not names or names[idx] == EMPTY_VAR:
        return
    v = block._find_var_recursive(names[idx])
    if v is not None:
        v.shape = tuple(int(s) for s in shape)
        if dtype is not None:
            v.dtype = dtype


def in_var(op, block, slot, idx=0):
    names = op.inputs.get(slot) or []
    if not names:
        return None
    return block._find_var_recursive(names[idx])


def same_shape_infer(in_slot, out_slot="Out"):
    def infer(op, block):
        v = in_var(op, block, in_slot)
        if v is None:
            raise SkipInferShape()
        set_out(op, block, out_slot, v.shape, v.dtype)

    return infer


def numeric_grad(f, xs, eps=1e-3):
    """Finite-difference gradient oracle for tests (reference test harness:
    python/paddle/fluid/tests/unittests/op_test.py:46 get_numeric_gradient)."""
    xs = [np.asarray(x, np.float64) for x in xs]
    base = float(np.sum(f(*xs)))
    grads = []
    for i, x in enumerate(xs):
        g = np.zeros_like(x)
        it = np.nditer(x, flags=["multi_index"])
        while not it.finished:
            idx = it.multi_index
            old = x[idx]
            x[idx] = old + eps
            up = float(np.sum(f(*xs)))
            x[idx] = old - eps
            down = float(np.sum(f(*xs)))
            x[idx] = old
            g[idx] = (up - down) / (2 * eps)
            it.iternext()
        grads.append(g)
    _ = base
    return grads
