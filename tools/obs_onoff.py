#!/usr/bin/env python3
"""What the always-on tracer costs in a benchmark cell, on the chip: one
process and one set-up (the benchmark's own: ``benchmark/run.py``'s
``start``, the cell's family and traffic kind), then windows that take
turns with ``FLAGS_obs_trace`` off and on (off on on off off on), each
giving the cell's end-to-end numbers. Same executables, same chip, same
minutes: what is left between the two medians is the tracer.

  chiprun -- python3 tools/obs_onoff.py --workload gpt2s-train-s1024 --seed 7

A train window is ``--steps`` steps timed from the first to the last
(whole steps, so the rate is not cut to a step's worth); a serve window
is ``--seconds`` of the closed loop after its own ramp, token ids from
``--seed`` + the window's number. The last line is one JSON object.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ORDER = (0, 1, 1, 0, 0, 1)


def train_windows(ctx, steps):
    import paddle_tpu.fluid as fluid
    from benchmark.traffic_kinds import train_steps as ts

    family, traffic, config = ctx.cell.family, ctx.traffic, ctx.config
    step = family.build_train(config, traffic, ctx.place, ctx.rehearse)
    ts.first_steps(step, family, ctx.cell.reference, config, traffic,
                   ctx.seed)
    ctx.open_window()
    tokens = traffic["batch"] * traffic["seq_len"]
    i = ts.CHECK_STEPS
    for on in ORDER:
        fluid.set_flags({"FLAGS_obs_trace": bool(on)})
        t0 = time.perf_counter()
        for _ in range(steps):
            step.run(family.feed(ts.batch_for(traffic, config, ctx.seed, i)))
            i += 1
        yield on, {"train_tok_per_s":
                   steps * tokens / (time.perf_counter() - t0)}
    step.close()


def serve_windows(ctx, seconds):
    import paddle_tpu.fluid as fluid
    from benchmark.harness.stats import percentile
    from benchmark.traffic_kinds import serve_closed as sc

    params = ctx.cell.reference.init_params(ctx.seed, ctx.config)
    stack = ctx.cell.family.build_serve(ctx.config, ctx.place, params,
                                        ctx.rehearse, {})
    del params
    for k, on in enumerate(ORDER):
        fluid.set_flags({"FLAGS_obs_trace": bool(on)})
        got = sc.drive(ctx, stack, ctx.seed + k, seconds)
        t0, t1 = got["window"]
        tpots = [sc.tpot_ms(r) for r in got["finished"] if len(r.times) >= 2]
        yield on, {
            "serve_tok_per_s":
                sc.tokens_in_window(got["records"], t0, t1) / (t1 - t0),
            "tpot_p90_ms": percentile(tpots, 90) if tpots else None,
            "requests_failed": len(got["failed"])}
        stack.wait_idle()
    stack.close()


def main(argv=None):
    from benchmark import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0
    cell, ctx = run.start(args)
    import paddle_tpu.fluid as fluid

    windows = (serve_windows(ctx, args.seconds)
               if cell.traffic["kind"] == "serve_closed"
               else train_windows(ctx, args.steps))
    seen, setup_s = {0: [], 1: []}, None
    try:
        for on, values in windows:
            run.log("onoff_window", obs_trace=on, **values)
            seen[on].append(values)
            setup_s = setup_s or ctx.setup_s  # the first window's opening
    finally:
        fluid.set_flags({"FLAGS_obs_trace": True})
    out = {"workload": cell.name, "seed": args.seed, "setup_s": setup_s}
    for name in seen[0][0]:
        off, on = ([w[name] for w in seen[k] if w[name] is not None]
                   for k in (0, 1))
        if not off or not on or name == "requests_failed":
            continue
        out[name] = {
            "off": off, "on": on,
            "on_over_off_pct": 100.0 * (
                statistics.median(on) / statistics.median(off) - 1.0)}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
