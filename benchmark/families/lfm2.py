"""``lfm2`` configurations onto the repo's entry point for training:
``models/lfm2.py::build_lfm2_train`` under ``fluid.optimizer.Adam`` and
``mixed_precision.decorate``, one ``fluid.Executor.run`` a step with the
loss and the held experts' counts fetched together
(``lfm2.run_train_step``). No serving path: ``models/lfm2.py`` builds no
inference program."""

import json

import numpy as np

# at the top, not where it is used: on a commit without the model a run of
# this family's cells fails here, at once, before any weight is made
from paddle_tpu.models import lfm2

from benchmark.families import common
from benchmark.references import lfm2 as reference

TOY = dict(vocab_size=211, hidden_size=32, intermediate_size=48,
           num_attention_heads=4, num_key_value_heads=2,
           moe_intermediate_size=16, num_experts=2, num_experts_per_tok=2,
           max_position_embeddings=64,
           published={"num_hidden_layers": 24, "num_experts": 8,
                      "vocab_size": 211})


def toy(config):
    return dict(config, **TOY)


def _layer_vars(config):
    """(leaf, var, trained) for every parameter of the configuration."""
    yield "embed", "lfm2_embed", True
    yield "norm", "lfm2_norm", True
    z = reference.sizes(config)
    for i, kind in enumerate(z["kinds"]):
        leaf, var = "l%d/" % i, "lfm2_%d_" % i
        yield leaf + "op_norm", var + "op_norm", True
        yield leaf + "ffn_norm", var + "ffn_norm", True
        if kind == "full_attention":
            for w in "qkvo":
                yield ("%sattn/w%s" % (leaf, w),
                       "%satt_%s.w_0" % (var, w), True)
            for g in ("q_norm", "k_norm"):
                yield leaf + "attn/" + g, var + "att_" + g, True
        else:
            yield leaf + "conv/w_in", var + "conv_in.w_0", True
            yield leaf + "conv/taps", var + "conv_conv", True
            yield leaf + "conv/w_out", var + "conv_out.w_0", True
        if i < z["dense"]:
            for w in ("w1", "w3", "w2"):
                yield ("%smlp/%s" % (leaf, w),
                       "%smlp_%s.w_0" % (var, w), True)
        else:
            yield leaf + "moe/wg", var + "moe_router.w_0", True
            yield leaf + "moe/bias", var + "moe_router_bias", False
            for w in ("w1", "w3", "w2"):
                yield ("%smoe/%s" % (leaf, w),
                       "%smoe_experts_%s" % (var, w), True)


def leaf_to_var(config):
    """The reference's TRAINED leaves -> the program's parameters."""
    return {leaf: var for leaf, var, trained in _layer_vars(config)
            if trained}


def buffers(config):
    """The leaves no optimizer touches (the router's bias) -> vars."""
    return {leaf: var for leaf, var, trained in _layer_vars(config)
            if not trained}


def model_config(config, rehearse):
    # the rehearsal has no Mosaic: same kernels, Pallas interpreter
    return lfm2.LFM2Config.from_config(config, flash_interpret=rehearse)


class TrainStep(common.TrainStep):
    """``common.TrainStep`` with the router's biases set beside the
    trained leaves, and the step run through ``lfm2.run_train_step``: the
    counts come back with the loss, and the ``train_step`` span carries
    what they say."""

    def __init__(self, main, startup, loss, counts, place, config):
        super().__init__(main, startup, loss, place, leaf_to_var(config))
        self.counts = counts
        self.buffers = buffers(config)
        self._held = []

    def set_params(self, params):
        """The seeded weights in, then the CALLER's copy moved to the
        host, in place: the harness keeps it until ``delta_norms``, and on
        the device its 2 GB beside the step's 6.1 GB of state and 7.3 GB
        of temporaries would pass the chip's 16 GB in the check steps."""
        import jax.numpy as jnp

        super().set_params(params)
        for leaf, var in self.buffers.items():
            self.scope.set(var, jnp.copy(params[leaf]))
        for leaf in list(params):
            params[leaf] = np.asarray(params[leaf])

    def run(self, feed):
        first = not self._stepped
        self._stepped = True
        loss, counts = lfm2.run_train_step(
            self.exe, self.target, feed, self.loss, self.counts, self.scope)
        if first:
            # the first step since the seeded weights went in: the plain
            # reference says beside its own counts how many choices differ
            reference.PROGRAM_COUNTS[reference.batch_key(
                feed["ids"])] = counts
        self._held.append(int(counts.sum()))
        return loss

    def close(self):
        """An earlier line of the run: how the assignments the held
        experts received moved over the steps (nothing balances the
        router: the 8 held of 32 may gain or lose tokens as it trains)."""
        held = self._held
        print(json.dumps({
            "note": "moe_train_held_assignments", "steps": len(held),
            "first": held[:1], "last": held[-1:],
            "min": min(held, default=None), "max": max(held, default=None),
            "every_20th": held[::20]}), flush=True)
        super().close()


def build_train(config, traffic, place, rehearse):
    import paddle_tpu.fluid as fluid

    cfg = model_config(config, rehearse)
    with fluid.unique_name.guard():
        main, startup, _feeds, loss, counts = lfm2.build_lfm2_train(
            cfg, traffic["seq_len"],
            learning_rate=config["train"]["learning_rate"], use_amp=True)
    return TrainStep(main, startup, loss, counts, place, config)


def feed(batch):
    n, s = batch["ids"].shape
    return {
        "ids": batch["ids"].reshape(n, s, 1).astype("int64"),
        "pos_ids": np.tile(np.arange(s)[None, :, None], (n, 1, 1))
        .astype("int64"),
    }
