"""What the family files share: one compiled train step with its state,
driven through ``fluid.Executor.run`` as a trainer drives it."""

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def _delta_norms(now, start):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        now[k].astype(jnp.float32) - start[k]))) for k in now}


def _name(var):
    return getattr(var, "name", var)


class TrainStep(object):
    """The object set-up builds and the window drives: program, executor,
    scope. ``leaf_to_var`` maps the reference's leaf names to the
    program's parameter names."""

    def __init__(self, main, startup, loss, place, leaf_to_var, mesh=None):
        import paddle_tpu.fluid as fluid

        self.main, self.loss = main, loss
        self.leaf_to_var = leaf_to_var
        self.exe = fluid.Executor(place)
        self.scope = fluid.core.Scope()
        self.exe.run(startup, scope=self.scope)
        self.target = main
        if mesh:
            from paddle_tpu.fluid import compiler

            self.target = compiler.CompiledProgram(main).with_mesh(
                loss_name=loss.name, mesh_axes=mesh["mesh_axes"],
                fsdp=bool(mesh.get("fsdp")))
        adam = [op for op in main.global_block().ops if op.type == "adam"]
        self.beta1 = float(adam[0].attr("beta1"))
        self._moment1 = {_name(op.inputs["Param"][0]):
                         _name(op.inputs["Moment1"][0]) for op in adam}
        missing = set(leaf_to_var.values()) ^ set(self._moment1)
        if missing:
            raise RuntimeError("reference leaves and the program's trained "
                               "parameters differ: %s" % sorted(missing)[:6])
        # what startup made of the optimizer's state, to start a seed
        # anew: the moments are zeros, the two powers a number each
        self._moments = [_name(op.inputs[slot][0]) for op in adam
                         for slot in ("Moment1", "Moment2")]
        self._powers = {
            _name(op.inputs[slot][0]):
            np.asarray(self.scope.get(_name(op.inputs[slot][0])))
            for op in adam for slot in ("Beta1Pow", "Beta2Pow")}
        self._stepped = False

    def set_params(self, params):
        """Seeded weights in (copies: the step may donate its state); if
        steps were taken since startup, the optimizer's state back to what
        startup made."""
        for leaf, var in self.leaf_to_var.items():
            want = tuple(self.scope.get(var).shape)
            if tuple(params[leaf].shape) != want:
                raise RuntimeError("%s is %s, %s wants %s" % (
                    leaf, params[leaf].shape, var, want))
            self.scope.set(var, jnp.copy(params[leaf]))
        if self._stepped:
            for name in self._moments:
                self.scope.set(name, jnp.zeros_like(self.scope.get(name)))
            for name, val in self._powers.items():
                self.scope.set(name, jnp.asarray(val))
            self._stepped = False

    def run(self, feed):
        self._stepped = True
        (lv,) = self.exe.run(self.target, feed=feed, fetch_list=[self.loss],
                             scope=self.scope)
        return float(np.asarray(lv).reshape(-1)[0])

    def first_grad_norms(self):
        """Per leaf, the norm of the gradient Adam was handed in the one
        step since ``set_params``: moment1 = (1 - beta1) * gradient."""
        m1 = {leaf: self.scope.get(self._moment1[var])
              for leaf, var in self.leaf_to_var.items()}
        scale = 1.0 / (1.0 - self.beta1)
        return {k: float(v) * scale
                for k, v in jax.device_get(_norms(m1)).items()}

    def delta_norms(self, start):
        now = {leaf: self.scope.get(var)
               for leaf, var in self.leaf_to_var.items()}
        start = {k: start[k] for k in now}
        return {k: float(v)
                for k, v in jax.device_get(_delta_norms(now, start)).items()}

    def close(self):
        self.scope = self.exe = self.target = self.main = None


def block_vars(prefix):
    """leaf -> var for one transformer block whose layers are named
    ``<prefix>_att_q`` ... as ``models/bert.py`` names them."""
    out = {}
    for leaf, var in (("q", "q"), ("k", "k"), ("v", "v"), ("o", "out")):
        out["attn/%s/w" % leaf] = "%s_att_%s.w_0" % (prefix, var)
        out["attn/%s/b" % leaf] = "%s_att_%s.b_0" % (prefix, var)
    for ln in ("ln1", "ln2"):
        out["%s/g" % ln] = "%s_%s.w_0" % (prefix, ln)
        out["%s/b" % ln] = "%s_%s.b_0" % (prefix, ln)
    for fc in ("fc0", "fc1"):
        out["%s/w" % fc] = "%s_ffn_%s.w_0" % (prefix, fc)
        out["%s/b" % fc] = "%s_ffn_%s.b_0" % (prefix, fc)
    return out
