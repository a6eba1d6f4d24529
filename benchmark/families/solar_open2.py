"""``solar_open2`` configurations onto the repo's entry points: serving
through export -> ``AnalysisPredictor`` -> ``DecodeEngine(model=
models/solar_open2)`` -> ``InferenceServer`` -> ``Gateway``, the stack of
``families/gpt.py::ServeStack``. No training path: ``models/solar_open2.py``
builds inference programs only."""

import tempfile
import time

# at the top, not where it is used: on a commit without the model a run of
# this family's cells fails here, at once, before any weight is made
from paddle_tpu.models import solar_open2

from benchmark.families import gpt as gpt_family

TOY = dict(vocab_size=211, hidden_size=32, num_hidden_layers=8,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                               "num_heads": 2, "num_kv_heads": None},
           moe_intermediate_size=16, n_routed_experts=2,
           num_experts_per_tok=2, max_position_embeddings=64,
           published={"num_hidden_layers": 8, "n_routed_experts": 8,
                      "vocab_size": 211})
TOY_SERVE = dict(slots=4, max_len=64, block_size=4, prefill_buckets=[8, 16],
                 prefill_chunk=16)


def toy(config):
    out = dict(config, **TOY)
    out["serve"] = dict(out["serve"], **TOY_SERVE)
    return out


def leaf_to_var(config):
    """The reference's leaves -> the program's parameters."""
    out = {"embed": "so2_embed", "norm": "so2_norm", "head": "so2_head.w_0"}
    gqa = set(config["gqa_layers"])
    for i in range(config["num_hidden_layers"]):
        leaf, var = "l%d/" % i, "so2_%d_" % i
        out.update({leaf + "ln1": var + "ln1", leaf + "ln2": var + "ln2"})
        if i in gqa:
            for w in ("q", "k", "v", "gate", "o"):
                out["%sattn/w%s" % (leaf, w)] = "%satt_%s.w_0" % (var, w)
        else:
            for w in "qkv":
                out["%skda/w%s" % (leaf, w)] = "%skda_%s.w_0" % (var, w)
                out["%skda/conv_%s" % (leaf, w)] = "%skda_conv_%s" % (var, w)
            for w in ("f_down", "f_up", "g_down", "g_up"):
                out["%skda/%s" % (leaf, w)] = "%skda_%s.w_0" % (var, w)
            out.update({
                leaf + "kda/wb": var + "kda_b.w_0",
                leaf + "kda/wo": var + "kda_o.w_0",
                leaf + "kda/a_log": var + "kda_a_log",
                leaf + "kda/dt_bias": var + "kda_dt_bias",
                leaf + "kda/o_norm": var + "kda_o_norm"})
        out.update({
            leaf + "moe/wg": var + "moe_router.w_0",
            leaf + "moe/bias": var + "moe_router_bias",
            leaf + "moe/w1": var + "moe_experts_w1",
            leaf + "moe/w3": var + "moe_experts_w3",
            leaf + "moe/w2": var + "moe_experts_w2"})
        for w in ("w1", "w3", "w2"):
            out["%sshared/%s" % (leaf, w)] = "%smoe_shared_%s.w_0" % (var, w)
    return out


def model_config(config, rehearse):
    # the rehearsal has no Mosaic: same kernels, Pallas interpreter
    return solar_open2.SolarOpen2Config.from_config(
        config, dtype=config["torch_dtype"], flash_interpret=rehearse)


class ServeStack(gpt_family.ServeStack):
    """GPT's stack with this family's model module, as
    ``families/deepseek.py`` builds its own: the seeded weights go
    straight into the served scope (no startup program runs: a second set
    of 7.8 GB would not fit beside them). A prompt is prefilled in
    windows of at most ``serve.prefill_chunk`` tokens (the largest
    bucket), one window a tick: a prompt longer than that keeps its state
    row between two windows while the other slots step."""

    def __init__(self, config, place, params, rehearse, times):
        import paddle_tpu.fluid as fluid
        from paddle_tpu import inference, serving
        from paddle_tpu.fluid import flags
        from paddle_tpu.serving.decode import DecodeEngine

        t = time.perf_counter()
        cfg = model_config(config, rehearse)
        spec = config["serve"]
        with fluid.unique_name.guard():
            infer, _startup, feed_names, logits = solar_open2.build_infer(
                cfg, min(spec["prefill_buckets"]))
        exe = fluid.Executor(place)
        self.scope = fluid.core.Scope()
        self._vars = leaf_to_var(config)
        declared = {v.name: tuple(v.shape) for v in infer.list_vars()
                    if getattr(v, "is_parameter", False)}
        for leaf, var in self._vars.items():
            if declared.get(var) != tuple(params[leaf].shape):
                raise RuntimeError("%s is %s, %s wants %s" % (
                    leaf, params[leaf].shape, var, declared.get(var)))
        if set(declared) != set(self._vars.values()):
            raise RuntimeError("reference leaves and the program's "
                               "parameters differ: %s" % sorted(
                                   set(declared) ^ set(self._vars.values())))
        self.set_params(params)
        times["build_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self._flags = flags
        flags.set_flags({"FLAGS_serving_strict_compiles": True})
        self._dir = tempfile.mkdtemp(prefix="bench_serve_")
        with fluid.scope_guard(self.scope):
            fluid.io.save_inference_model(
                self._dir, feed_names, [logits], exe, main_program=infer)
        predictor = inference.create_paddle_predictor(
            inference.AnalysisConfig(self._dir))
        times["export_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.engine = DecodeEngine(
            cfg, place=place, scope=self.scope, slots=spec["slots"],
            max_len=spec["max_len"], block_size=spec["block_size"],
            prefill_buckets=list(spec["prefill_buckets"]),
            prefill_chunk=spec["prefill_chunk"], param_program=infer,
            queue_depth=4 * spec["slots"],
            model=solar_open2)
        self.server = serving.InferenceServer(
            predictor, max_batch_size=1, num_workers=1,
            decode_engine=self.engine).start()
        self.gateway = serving.Gateway(
            self.server, port=0, max_inflight=4 * spec["slots"]).start()
        times["engine_start_s"] = time.perf_counter() - t
        self.host, self.port = "127.0.0.1", self.gateway.port


def build_serve(config, place, params, rehearse, times):
    return ServeStack(config, place, params, rehearse, times)
