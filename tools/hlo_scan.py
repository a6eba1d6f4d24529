"""Structural scan of the compiled training step's optimized HLO + cost
analysis (the PERF.md methodology, reproducible).

Builds the ResNet-50, BERT-base, or GPT-2-small training step exactly as
bench.py / bench_bert.py / bench_gpt.py do, compiles the executor's main
XLA segment ahead-of-time on the current backend, and prints ONE JSON
line:

  {"model", "batch", "backend", "flops", "bytes_accessed",
   "hlo_ops": {"transpose": N, "convert": N, "copy": N, "fusion": N,
               "dot": N, "convolution": N, "all-reduce": N}}

Usage (CPU structural scan — fusion hygiene and op census only):
  JAX_PLATFORMS=cpu python tools/hlo_scan.py --model resnet --batch 32
On a chip the same command (without JAX_PLATFORMS) gives the real
per-step FLOP / HBM-byte counts used for the MFU math in PERF.md. The
place follows jax's default backend (``core.default_place``); the line's
"backend" field says which one compiled it.
NOTE: transpose/copy elimination is a TPU-backend layout-assignment
property — the CPU backend legitimately keeps them, so only the TPU run
can reproduce PERF.md's "0 transposes" claim.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(model, batch, amp, remat, flash=False, seq=128):
    import numpy as np

    if model == "resnet":
        from paddle_tpu.models import resnet

        main, startup, feeds, loss, acc = resnet.build_resnet_train(
            depth=50, class_num=1000, image_size=224, use_amp=amp,
            recompute=remat,
        )
        rs = np.random.RandomState(0)
        feed = {
            "img": rs.rand(batch, 3, 224, 224).astype("float32"),
            "label": rs.randint(0, 1000, (batch, 1)).astype("int64"),
        }
    elif model == "bert":
        if remat:
            raise SystemExit(
                "--remat is only wired for resnet; a bert line would be a "
                "mislabeled non-remat census"
            )
        from paddle_tpu.models import bert

        cfg = bert.BertConfig()
        cfg.hidden_dropout = 0.0
        cfg.attention_dropout = 0.0
        cfg.use_flash_attention = flash
        S = seq
        main, startup, feeds, loss, acc = bert.build_bert_classifier(
            cfg, S, learning_rate=2e-5, use_amp=amp
        )
        rs = np.random.RandomState(0)
        feed = {
            "src_ids": rs.randint(0, cfg.vocab_size, (batch, S, 1)).astype("int64"),
            "pos_ids": np.tile(
                np.arange(S)[None, :, None], (batch, 1, 1)
            ).astype("int64"),
            "sent_ids": np.zeros((batch, S, 1), "int64"),
            "input_mask": np.ones((batch, S, 1), "float32"),
            "label": rs.randint(0, 2, (batch, 1)).astype("int64"),
        }
    elif model == "gpt":
        if remat:
            raise SystemExit(
                "--remat is only wired for resnet; a gpt line would be a "
                "mislabeled non-remat census"
            )
        from paddle_tpu.models import gpt

        cfg = gpt.GPTConfig(
            hidden_dropout=0.0, attention_dropout=0.0,
            use_flash_attention=flash,
            max_position_embeddings=max(1024, seq),
        )
        S = seq
        main, startup, feeds, loss = gpt.build_gpt_lm_train(
            cfg, S, use_amp=amp
        )
        rs = np.random.RandomState(0)
        feed = {
            "ids": rs.randint(0, cfg.vocab_size, (batch, S, 1)).astype("int64"),
            "pos_ids": np.tile(
                np.arange(S)[None, :, None], (batch, 1, 1)
            ).astype("int64"),
            "input_mask": np.ones((batch, S, 1), "float32"),
        }
    else:
        raise SystemExit("unknown model %r" % model)
    return main, startup, feed, loss


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet", choices=["resnet", "bert", "gpt"])
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--amp", type=int, default=1)
    ap.add_argument("--remat", type=int, default=0)
    ap.add_argument("--flash", type=int, default=0)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--out", default="", help="also write the JSON line here")
    args = ap.parse_args()

    def hb(msg):
        print("HB %s" % msg, file=sys.stderr, flush=True)

    from paddle_tpu import compile_cache

    # the bench children's persistent XLA cache: when the ladder already
    # compiled this exact program, the census compile is a cache hit
    compile_cache.enable()
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import executor as _ex

    hb("build start (program construction)")
    prog, startup, feed, loss = build(
        args.model, args.batch, bool(args.amp), bool(args.remat),
        flash=bool(args.flash), seq=args.seq,
    )
    hb("build ok; device discovery next")
    # on a chip the lowering backend (and with it the NHWC conv path)
    # must match what bench.py compiles, or the census describes a
    # program the bench never runs
    place = fluid.core.default_place()
    hb("device ok (%s); startup run next" % type(place).__name__)
    scope = fluid.core.Scope()
    exe = fluid.Executor(place)
    exe.run(startup, scope=scope)
    hb("startup ok; lowering main segment")

    cb = _ex._CompiledBlock(prog, 0, list(feed), [loss.name], place)
    xla = [p for k, _s, p in cb._plans if k == "xla"]
    # the training step is the LARGEST segment (feed/fetch host ops aside)
    plan = max(xla, key=lambda p: len(p["feeds"]) + len(p["mutable"])
               + len(p["const"]))

    import numpy as np

    feed_vals = tuple(feed[n] for n in plan["feeds"])
    mutable_vals = tuple(np.asarray(scope.get(n)) for n in plan["mutable"])
    const_map = {
        n: np.asarray(scope.get(n))
        for n in plan["const"]
        if scope.get(n) is not None
    }
    rng = jax.random.key(0)
    lowered = jax.jit(plan["raw_fn"]).lower(
        feed_vals, mutable_vals, (), const_map, rng
    )
    hb("lowered; compiling")
    compiled = lowered.compile()
    hb("compiled; cost analysis")

    # shared census library (observability/xla_stats.py): the always-on
    # device-plane telemetry and this one-off scan run the SAME cost
    # parsing + op-census regex, so they can never disagree. Output stays
    # byte-compatible with the pre-refactor scan.
    from paddle_tpu.observability import xla_stats

    census = xla_stats.executable_census(compiled)
    line = json.dumps({
        "model": args.model,
        "flash": bool(args.flash),
        "batch": args.batch,
        "seq": args.seq if args.model in ("bert", "gpt") else None,
        "backend": jax.default_backend(),
        "flops": census["flops"],
        "bytes_accessed": census["bytes_accessed"],
        "hlo_ops": xla_stats.interesting_ops(census["hlo_ops"]),
        "total_hlo_ops": census["total_hlo_ops"],
    })
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
