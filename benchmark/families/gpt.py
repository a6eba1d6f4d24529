"""GPT configurations onto the repo's entry points: training through
``models/gpt.py::build_gpt_lm_train``; serving through export ->
``AnalysisPredictor`` -> ``DecodeEngine`` -> ``InferenceServer`` ->
``Gateway``."""

import shutil
import tempfile
import time

import numpy as np

from benchmark.families import common

TOY = dict(vocab_size=211, n_embd=64, n_layer=2, n_head=4, n_inner=128,
           n_positions=64)
TOY_SERVE = dict(slots=4, max_len=64, block_size=4, prefill_buckets=[8, 16])


def toy(config):
    out = dict(config, **TOY)
    if "serve" in out:
        out["serve"] = dict(out["serve"], **TOY_SERVE)
    return out


def leaf_to_var(config):
    out = {"wte": "tok_embedding", "wpe": "pos_embedding",
           "head/w": "lm_head.w_0", "head/b": "lm_head.b_0"}
    for i in range(config["n_layer"]):
        for leaf, var in common.block_vars("gpt_%d" % i).items():
            out["h%d/%s" % (i, leaf)] = var
    return out


def model_config(config, rehearse):
    from paddle_tpu.models import gpt

    cfg = gpt.GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        intermediate_size=config["n_inner"],
        max_position_embeddings=config["n_positions"],
        hidden_dropout=config["dropout"],
        attention_dropout=config["dropout"],
        use_flash_attention=config["use_flash_attention"])
    # the rehearsal has no Mosaic: same kernels, Pallas interpreter
    cfg.flash_interpret = rehearse
    return cfg


def build_train(config, traffic, place, rehearse):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import gpt

    cfg = model_config(config, rehearse)
    with fluid.unique_name.guard():
        main, startup, _feeds, loss = gpt.build_gpt_lm_train(
            cfg, traffic["seq_len"],
            learning_rate=config["train"]["learning_rate"], use_amp=True)
    return common.TrainStep(main, startup, loss, place, leaf_to_var(config),
                            mesh=config.get("mesh"))


def feed(batch):
    n, s = batch["ids"].shape
    return {
        "ids": batch["ids"].reshape(n, s, 1).astype("int64"),
        "pos_ids": np.tile(np.arange(s)[None, :, None], (n, 1, 1))
        .astype("int64"),
        "input_mask": np.ones((n, s, 1), "float32"),
    }


class ServeStack(object):
    """The stack a user deploys, started and warm. ``url`` takes
    ``POST /v1/generate``."""

    def __init__(self, config, place, params, rehearse, times):
        import paddle_tpu.fluid as fluid
        from paddle_tpu import inference, serving
        from paddle_tpu.fluid import flags
        from paddle_tpu.models import gpt
        from paddle_tpu.serving.decode import DecodeEngine

        t = time.perf_counter()
        cfg = model_config(config, rehearse)
        spec = config["serve"]
        export_len = min(spec["prefill_buckets"])
        with fluid.unique_name.guard():
            infer, startup, feed_names, logits = gpt.build_gpt_infer(
                cfg, export_len)
        exe = fluid.Executor(place)
        self.scope = fluid.core.Scope()
        exe.run(startup, scope=self.scope)
        self._vars = leaf_to_var(config)
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
            param_program=infer, queue_depth=4 * spec["slots"])
        self.server = serving.InferenceServer(
            predictor, max_batch_size=1, num_workers=1,
            decode_engine=self.engine).start()
        self.gateway = serving.Gateway(
            self.server, port=0, max_inflight=4 * spec["slots"]).start()
        times["engine_start_s"] = time.perf_counter() - t
        self.host, self.port = "127.0.0.1", self.gateway.port

    def set_params(self, params):
        for leaf, var in self._vars.items():
            self.scope.set(var, params[leaf])

    def wait_idle(self, timeout=120.0):
        """Until the engine holds no stream (calibration, between seeds:
        a stream whose client left runs on to its length)."""
        end = time.time() + timeout
        while time.time() < end:
            stats = self.engine.stats()
            if not (stats["active"] or stats["prefilling"]
                    or stats["queued"]):
                return
            time.sleep(0.5)

    def close(self):
        self.gateway.stop()
        self.server.stop()
        self._flags.set_flags({"FLAGS_serving_strict_compiles": False})
        shutil.rmtree(self._dir, ignore_errors=True)
        self.engine = self.server = self.gateway = self.scope = None


def build_serve(config, place, params, rehearse, times):
    return ServeStack(config, place, params, rehearse, times)
