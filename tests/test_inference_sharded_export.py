"""Mesh-sharded AOT export (VERDICT r4 task 6): a dist-attr-sharded (TP)
program exports as a shard-manifest bundle — per-chip program in wire
format + dist_attr manifest + full-value params — and reloads in a FRESH
PROCESS as a predictor compiled under CompiledProgram.with_spmd, with
output parity against the dense single-device run.

Reference semantics: analysis_predictor.cc:636 serves whatever program it
is given; the TP extension keeps that property by re-establishing the
shardings at load time instead of baking a mesh into the artifact.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import inference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, os, sys
import numpy as np
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, %(root)r)
from paddle_tpu import inference

pred = inference.AnalysisPredictor.from_executable(%(bundle)r)
data = np.load(%(io)r)
inputs = [data[n] for n in json.loads(%(feeds)r)]
outs = pred.run(inputs)
for ref_i, out in enumerate(outs):
    np.testing.assert_allclose(
        out, data["__out_%%d" %% ref_i], rtol=2e-4, atol=2e-5)
print("SHARDED_RELOAD_OK", len(outs))
"""


def _reload_in_fresh_process(bundle_dir, io_path, feed_names):
    src = _CHILD % {
        "root": ROOT,
        "bundle": str(bundle_dir),
        "io": str(io_path),
        "feeds": json.dumps(list(feed_names)),
    }
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, env=env, timeout=420, cwd=ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARDED_RELOAD_OK" in out.stdout


def test_mlp_tp_bundle_roundtrip(tmp_path):
    """The dryrun's dp x tp MLP: export sharded, reload fresh, parity."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        h = fluid.layers.fc(input=x, size=32, act="relu")
        out = fluid.layers.fc(input=h, size=8)

    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
        model_dir = str(tmp_path / "model")
        fluid.io.save_inference_model(model_dir, ["x"], [out], exe,
                                      main_program=main)

    pred = inference.AnalysisPredictor(inference.AnalysisConfig(model_dir))
    rs = np.random.RandomState(0)
    xb = rs.rand(4, 16).astype("float32")
    dense = pred.run([xb])

    # Megatron column/row-parallel annotations on the LOADED program
    blk = pred.program.global_block()
    blk.vars["fc_0.w_0"].dist_attr = (None, "model")
    blk.vars["fc_0.b_0"].dist_attr = ("model",)
    blk.vars["fc_1.w_0"].dist_attr = ("model", None)

    bundle = str(tmp_path / "bundle")
    meta_path = pred.save_optimized_model(
        bundle, mesh_axes={"data": 2, "model": 2})
    meta = json.load(open(meta_path))
    assert meta["kind"] == "sharded_program"
    assert meta["dist_attrs"]["fc_0.w_0"] == [None, "model"]

    # reload IN-PROCESS first (8 virtual devices via conftest env)
    pred2 = inference.AnalysisPredictor.from_executable(bundle)
    outs2 = pred2.run([xb])
    np.testing.assert_allclose(outs2[0], dense[0], rtol=2e-4, atol=2e-5)

    # and in a FRESH process
    io_path = tmp_path / "io.npz"
    np.savez(io_path, x=xb,
             **{"__out_%d" % i: o for i, o in enumerate(dense)})
    _reload_in_fresh_process(bundle, io_path, ["x"])


@pytest.mark.slow
def test_bert_tp_bundle_roundtrip(tmp_path):
    """Tiny BERT with Megatron-annotated FFN weights (col-parallel fc0,
    row-parallel fc1 per encoder layer): the dp x tp bundle reloads in a
    fresh process with logits parity vs the dense run."""
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0,
                               is_test=True)
    S, B = 16, 4
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.layers.data(name="src_ids", shape=[S, 1], dtype="int64")
        pos = fluid.layers.data(name="pos_ids", shape=[S, 1], dtype="int64")
        sent = fluid.layers.data(name="sent_ids", shape=[S, 1], dtype="int64")
        mask = fluid.layers.data(name="input_mask", shape=[S, 1],
                                 dtype="float32")
        _seq, pooled = bert.bert_encoder(src, pos, sent, mask, cfg)
        logits = fluid.layers.fc(input=pooled, size=2)

    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
        model_dir = str(tmp_path / "model")
        feeds = ["src_ids", "pos_ids", "sent_ids", "input_mask"]
        fluid.io.save_inference_model(model_dir, feeds, [logits], exe,
                                      main_program=main)

    pred = inference.AnalysisPredictor(inference.AnalysisConfig(model_dir))
    rs = np.random.RandomState(1)
    inputs = [
        rs.randint(0, cfg.vocab_size, (B, S, 1)).astype("int64"),
        np.tile(np.arange(S)[None, :, None], (B, 1, 1)).astype("int64"),
        np.zeros((B, S, 1), "int64"),
        np.ones((B, S, 1), "float32"),
    ]
    dense = pred.run(inputs)

    # annotate each encoder layer's FFN weights Megatron col/row
    blk = pred.program.global_block()
    annotated = 0
    for l in range(cfg.num_layers):
        w0, b0 = "layer_%d_ffn_fc0.w_0" % l, "layer_%d_ffn_fc0.b_0" % l
        w1 = "layer_%d_ffn_fc1.w_0" % l
        assert blk.vars[w0].shape[-1] == cfg.intermediate_size, w0
        assert blk.vars[w1].shape[0] == cfg.intermediate_size, w1
        blk.vars[w0].dist_attr = (None, "model")
        blk.vars[b0].dist_attr = ("model",)
        blk.vars[w1].dist_attr = ("model", None)
        annotated += 1
    assert annotated == cfg.num_layers

    bundle = str(tmp_path / "bundle")
    pred.save_optimized_model(bundle, mesh_axes={"data": 2, "model": 2})

    io_path = tmp_path / "io.npz"
    np.savez(io_path, src_ids=inputs[0], pos_ids=inputs[1],
             sent_ids=inputs[2], input_mask=inputs[3],
             **{"__out_%d" % i: o for i, o in enumerate(dense)})
    _reload_in_fresh_process(bundle, io_path, feeds)
