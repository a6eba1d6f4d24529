"""Secondary benchmark: BERT-base fine-tune throughput (sequences/sec) on
one chip (BASELINE.md metric 2). Same architecture as bench.py: the parent
never imports jax; each attempt is a child process on ``TPUPlace(0)`` with
a hard wall-clock timeout, demoting batch on OOM/timeout. A run that finds
no TPU exits non-zero and prints no result. Prints ONE JSON line.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

METRIC = "bert_base_finetune_throughput"
UNIT = "sequences/sec/chip"
DEFAULT_SEQ_LEN = int(os.environ.get("BENCH_BERT_SEQ", "128"))


def _hb(msg):
    print("HB %s" % msg, file=sys.stderr, flush=True)


def child_main(cfg):
    import bench

    place = bench.chip_start()
    import jax
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    dev = fluid.core.get_jax_device(place)
    batch = cfg["batch"]
    seq_len = int(cfg.get("seq_len", DEFAULT_SEQ_LEN))
    bcfg = bert.BertConfig()
    bcfg.hidden_dropout = 0.0
    bcfg.attention_dropout = 0.0
    # fused Pallas flash attention (opt-in probe: BENCH_FLASH=1 or cfg)
    bcfg.use_flash_attention = bool(
        cfg.get("flash", os.environ.get("BENCH_FLASH", "0") == "1")
    )
    _hb("build start")
    main, startup, feeds, loss, acc = bert.build_bert_classifier(
        bcfg, seq_len, learning_rate=2e-5,
        # bf16 matmuls on the MXU (BENCH_AMP=0 opts out, bench.py parity)
        use_amp=os.environ.get("BENCH_AMP", "1") == "1",
    )
    exe = fluid.Executor(place)
    _hb("startup start")
    exe.run(startup)
    _hb("startup ok")
    rs = np.random.RandomState(0)
    feed = {
        "src_ids": jax.device_put(
            rs.randint(0, bcfg.vocab_size, (batch, seq_len, 1)).astype("int64"), dev
        ),
        "pos_ids": jax.device_put(
            np.tile(np.arange(seq_len)[None, :, None], (batch, 1, 1)).astype("int64"),
            dev,
        ),
        "sent_ids": jax.device_put(
            np.zeros((batch, seq_len, 1), "int64"), dev
        ),
        "input_mask": jax.device_put(
            np.ones((batch, seq_len, 1), "float32"), dev
        ),
        "label": jax.device_put(rs.randint(0, 2, (batch, 1)).astype("int64"), dev),
    }
    _hb("warmup start")
    for i in range(cfg["warmup"]):
        exe.run(main, feed=feed, fetch_list=[loss])
        _hb("warmup %d done" % i)
    # compile + fully drain the fetch-free variant BEFORE the clock starts
    # (async dispatch would otherwise leak this step into the timed window)
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
    sps = batch * steps / dt
    _hb("timed ok %.2fs loss=%.4f sps=%.1f" % (dt, lval, sps))
    result = {"sps": sps, "device": "tpu", "loss": lval}
    # dense path only: cost analysis cannot see inside the flash Pallas
    # custom call, so a flash census would undercount (PERF.md round-5)
    if not bcfg.use_flash_attention:
        try:
            from paddle_tpu.observability import xla_stats as _xla_stats

            _xla_stats.attach_headline_census(result)
        except Exception as e:  # census must never sink a measurement
            _hb("census unavailable: %s" % e)
    print("RESULT " + json.dumps(result), flush=True)


def main():
    import bench

    deadline = time.time() + int(os.environ.get("BENCH_BUDGET_S", "1400"))
    seq = DEFAULT_SEQ_LEN
    flash = os.environ.get("BENCH_FLASH", "0") == "1"
    # batch scales down with seq len so the attempt fits the same slot
    big, small = (64, 16) if seq <= 128 else (24, 8)
    attempts = [
        (dict(batch=big, steps=10, warmup=2, seq_len=seq, flash=flash), 420),
        (dict(batch=small, steps=10, warmup=2, seq_len=seq, flash=flash),
         360),
    ]
    for cfg, slot in attempts:
        label = "bert-tpu-b%d-s%d%s" % (
            cfg["batch"], cfg["seq_len"], "-flash" if cfg["flash"] else "",
        )
        res, _kind, err = bench._run_attempt(
            label, cfg, slot, deadline,
            script=os.path.abspath(__file__),
        )
        if err:
            print("bench_bert[%s]: %s" % (label, err), file=sys.stderr,
                  flush=True)
        if res:
            # single source of truth for lines and baselines: bench.py
            # (BASELINE.md documents the per-seq-len provenance); the
            # child's fresh census rides along for dense rungs
            out = bench._bert_line(res, cfg["batch"], cfg["seq_len"],
                                   cfg["flash"])
            bench.bank_write(
                "bert_seq%d%s" % (cfg["seq_len"], "_flash" if cfg["flash"] else ""),
                bench._bank_entry(out),
            )
            print(json.dumps(out), flush=True)
            return 0
    print("bench_bert: all attempts failed", file=sys.stderr, flush=True)
    return 1


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        import bench

        bench._child_entry(json.loads(sys.argv[2]), child_main)
    else:
        sys.exit(main())
