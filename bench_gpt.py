"""Tertiary benchmark: GPT-2-small causal-LM training throughput
(tokens/sec) on one chip. Exercises the CAUSAL flash-attention path (the
in-kernel `causal` flag, no dense [T, T] bias) that neither headline
metric covers. Same architecture as bench.py / bench_bert.py: the parent
never imports jax; each attempt is a child process on ``TPUPlace(0)`` with
a hard wall-clock timeout, demoting batch on OOM/timeout. A run that finds
no TPU exits non-zero and prints no result. Prints ONE JSON line.
``vs_baseline`` compares the seq-1024 config against the DERIVED V100-era
constant below (BASELINE.md provenance); other configs report null.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

METRIC = "gpt2_small_lm_throughput"
UNIT = "tokens/sec/chip"
DEFAULT_SEQ_LEN = int(os.environ.get("BENCH_GPT_SEQ", "1024"))

# V100-era GPT-2-small fp32 training baseline (tokens/sec, single V100).
# DERIVED, not independently reported (BASELINE.md provenance note, same
# method as the seq-384 BERT constant): FLOPs-scaled from the documented
# BERT-base seq-128 constant (40 seq/s = 5120 tok/s). Per-token per-layer
# FLOPs ∝ 24·H² + 4·S·H; H=768 over 12 layers gives 174.6M (BERT, S=128)
# vs 207.6M (GPT-2, S=1024), and GPT-2's untied lm_head adds 2·H·V ≈
# 77.2M/tok → ratio ≈ 1.63× → 5120 / 1.63 ≈ 3100 tok/s. Valid for the
# seq-1024 full config only.
V100_GPT2_SMALL_TOK_PER_SEC = 3100.0


def _hb(msg):
    print("HB %s" % msg, file=sys.stderr, flush=True)


def child_main(cfg):
    import bench

    place = bench.chip_start()
    import jax
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import gpt

    dev = fluid.core.get_jax_device(place)
    batch = cfg["batch"]
    seq_len = int(cfg.get("seq_len", DEFAULT_SEQ_LEN))
    gcfg = gpt.GPTConfig(
        # long-context rungs (seq 4096) need a position table larger
        # than GPT-2's stock 1024; growing it is the only change
        max_position_embeddings=max(1024, seq_len),
    )
    # throughput config: dropout off (same convention as bench_bert)
    gcfg.hidden_dropout = 0.0
    gcfg.attention_dropout = 0.0
    gcfg.use_flash_attention = bool(
        cfg.get("flash", os.environ.get("BENCH_FLASH", "0") == "1")
    )
    _hb("build start")
    main, startup, _feeds, loss = gpt.build_gpt_lm_train(
        gcfg, seq_len, learning_rate=3e-4,
        use_amp=os.environ.get("BENCH_AMP", "1") == "1",
    )
    exe = fluid.Executor(place)
    _hb("startup start")
    exe.run(startup)
    _hb("startup ok")
    rs = np.random.RandomState(0)
    feed = {
        "ids": jax.device_put(
            rs.randint(0, gcfg.vocab_size, (batch, seq_len, 1)).astype("int64"),
            dev,
        ),
        "pos_ids": jax.device_put(
            np.tile(np.arange(seq_len)[None, :, None], (batch, 1, 1))
            .astype("int64"), dev,
        ),
        "input_mask": jax.device_put(
            np.ones((batch, seq_len, 1), "float32"), dev
        ),
    }
    _hb("warmup start")
    for i in range(cfg["warmup"]):
        exe.run(main, feed=feed, fetch_list=[loss])
        _hb("warmup %d done" % i)
    exe.run(main, feed=feed, fetch_list=[])
    exe.run(main, feed=feed, fetch_list=[loss])
    _hb("timed start")
    t0 = time.perf_counter()
    steps = cfg["steps"]
    out = None
    for i in range(steps):
        out = exe.run(
            main, feed=feed, fetch_list=[loss] if i == steps - 1 else []
        )
    lval = float(np.asarray(out[0]).ravel()[0])
    dt = time.perf_counter() - t0
    assert np.isfinite(lval), lval
    tps = batch * seq_len * steps / dt
    _hb("timed ok %.2fs loss=%.4f tps=%.1f" % (dt, lval, tps))
    print("RESULT " + json.dumps({"tps": tps, "device": "tpu", "loss": lval}),
          flush=True)


def main():
    import bench

    deadline = time.time() + int(os.environ.get("BENCH_BUDGET_S", "1400"))
    seq = DEFAULT_SEQ_LEN
    flash = os.environ.get("BENCH_FLASH", "0") == "1"
    # batch scales down with seq len so the attempt fits the same slot
    big, small = (16, 4) if seq <= 1024 else (4, 1)
    attempts = [
        (dict(batch=big, steps=10, warmup=2, seq_len=seq, flash=flash), 420),
        (dict(batch=small, steps=10, warmup=2, seq_len=seq, flash=flash),
         360),
    ]
    for cfg, slot in attempts:
        label = "gpt-tpu-b%d-s%d%s" % (
            cfg["batch"], cfg["seq_len"], "-flash" if cfg["flash"] else "",
        )
        res, _kind, err = bench._run_attempt(
            label, cfg, slot, deadline,
            script=os.path.abspath(__file__),
        )
        if err:
            print("bench_gpt[%s]: %s" % (label, err), file=sys.stderr,
                  flush=True)
        if res:
            out = {
                "metric": METRIC,
                "value": round(res["tps"], 1),
                "unit": UNIT,
                # the derived V100 constant (BASELINE.md) covers exactly
                # the seq-1024 GPT-2-small config; anything else is null
                "vs_baseline": (
                    round(res["tps"] / V100_GPT2_SMALL_TOK_PER_SEC, 3)
                    if cfg["seq_len"] == 1024 else None
                ),
                "batch": cfg["batch"],
                "seq_len": cfg["seq_len"],
                "device": res["device"],
            }
            if cfg["flash"]:
                out["flash_attention"] = True
            bench.bank_write(
                "gpt_seq%d%s" % (
                    cfg["seq_len"], "_flash" if cfg["flash"] else ""
                ),
                bench._bank_entry(out),
            )
            print(json.dumps(out), flush=True)
            return 0
    print("bench_gpt: all attempts failed", file=sys.stderr, flush=True)
    return 1


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        import bench

        bench._child_entry(json.loads(sys.argv[2]), child_main)
    else:
        sys.exit(main())
