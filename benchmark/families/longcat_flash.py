"""``LongCat-Flash`` configurations onto the repo's entry points: serving
through export -> ``AnalysisPredictor`` -> ``DecodeEngine(model=
models/longcat_flash)`` -> ``InferenceServer`` -> ``Gateway``, the stack
of ``families/gpt.py::ServeStack``. No training path:
``models/longcat_flash.py`` builds inference programs only."""

import tempfile
import time

# at the top, not where it is used: on a commit without the model a run of
# this family's cells fails here, at once, before any weight is made
from paddle_tpu.models import longcat_flash

from benchmark.families import gpt as gpt_family

TOY = dict(vocab_size=211, hidden_size=64, ffn_hidden_size=96,
           expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
           kv_lora_rank=32, q_lora_rank=16, qk_rope_head_dim=8,
           v_head_dim=16, qk_nope_head_dim=16, n_routed_experts=2,
           zero_expert_num=4, moe_topk=3, rope_theta=1e4,
           max_position_embeddings=64,
           published={"num_layers": 2, "n_routed_experts": 8,
                      "vocab_size": 211})
TOY_SERVE = dict(slots=4, max_len=64, block_size=4, prefill_buckets=[8, 16],
                 prefill_chunk=16)


def toy(config):
    out = dict(config, **TOY)
    out["serve"] = dict(out["serve"], **TOY_SERVE)
    return out


def leaf_to_var(config):
    """The reference's leaves -> the program's parameters."""
    out = {"embed": "lc_embed", "norm": "lc_norm", "head": "lc_head.w_0"}
    for i in range(config["num_layers"]):
        leaf, var = "l%d/" % i, "lc_%d_" % i
        for j in (0, 1):
            att = "%satt%d" % (var, j)
            out.update({
                "%sln_att%d" % (leaf, j): "%sln_att%d" % (var, j),
                "%sln_ffn%d" % (leaf, j): "%sln_ffn%d" % (var, j),
                "%satt%d/q_norm" % (leaf, j): att + "_q_norm",
                "%satt%d/kv_norm" % (leaf, j): att + "_kv_norm"})
            for ours, theirs in (("wqa", "qa"), ("wqb", "qb"),
                                 ("wkva", "kva"), ("wkvb", "kvb"),
                                 ("wo", "o")):
                out["%satt%d/%s" % (leaf, j, ours)] = "%s_%s.w_0" % (
                    att, theirs)
            for w in ("w1", "w3", "w2"):
                out["%sffn%d/%s" % (leaf, j, w)] = "%sffn%d_%s.w_0" % (
                    var, j, w)
        out.update({
            leaf + "moe/wg": var + "moe_router.w_0",
            leaf + "moe/bias": var + "moe_router_bias",
            leaf + "moe/w1": var + "moe_experts_w1",
            leaf + "moe/w3": var + "moe_experts_w3",
            leaf + "moe/w2": var + "moe_experts_w2"})
    return out


def model_config(config, rehearse):
    # the rehearsal has no Mosaic: same kernel, Pallas interpreter
    return longcat_flash.LongcatFlashConfig.from_config(
        config, dtype=config["torch_dtype"], flash_interpret=rehearse)


class ServeStack(gpt_family.ServeStack):
    """GPT's stack with this family's model module, as
    ``families/deepseek.py`` builds its own: the seeded weights go
    straight into the served scope (no startup program runs: a second set
    of 10.3 GB would not fit beside them). A prompt is prefilled in
    windows of at most ``serve.prefill_chunk`` tokens (the largest
    bucket), one window a tick."""

    def __init__(self, config, place, params, rehearse, times):
        import paddle_tpu.fluid as fluid
        from paddle_tpu import inference, serving
        from paddle_tpu.fluid import flags
        from paddle_tpu.serving.decode import DecodeEngine

        t = time.perf_counter()
        cfg = model_config(config, rehearse)
        spec = config["serve"]
        with fluid.unique_name.guard():
            infer, _startup, feed_names, logits = longcat_flash.build_infer(
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
            queue_depth=4 * spec["slots"], model=longcat_flash)
        self.server = serving.InferenceServer(
            predictor, max_batch_size=1, num_workers=1,
            decode_engine=self.engine).start()
        self.gateway = serving.Gateway(
            self.server, port=0, max_inflight=4 * spec["slots"]).start()
        times["engine_start_s"] = time.perf_counter() - t
        self.host, self.port = "127.0.0.1", self.gateway.port


def build_serve(config, place, params, rehearse, times):
    return ServeStack(config, place, params, rehearse, times)
