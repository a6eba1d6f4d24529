#!/usr/bin/env python3
"""One benchmark cell with every ``cpu=True`` span reading its thread's
CPU clock (``trace.set_cpu_every``; the program reads it in one tick in
``trace.CPU_EVERY``), and every such read counted and timed where it is
made: the run behind a split of the serve tick by phase (PERF.md section
5), and what that split costs.

  chiprun -- python3 tools/cpu_clocks.py --every 1 -- \\
      --workload gpt2s-serve-chat --seed 7 --seconds 40 --trace 1

What follows ``--`` goes to ``benchmark/run.py`` as it is, so the run
prints the cell's own lines (``tick_wait_by_phase`` among a traced run's
notes); then one more, ``CLOCKS`` and a JSON object: for each clock and
kind of thread (the engine's loop, a gateway handler, any other) the
calls, microseconds a call, and calls and microseconds a decode step.
``--every 0`` leaves the program's own rate: what the committed tree
pays. The wrapper around a read costs about a third of a microsecond,
inside the span that made it.
"""

import argparse
import collections
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def kind_of_thread():
    name = threading.current_thread().name
    if name == "decode-engine":
        return "loop"
    return "handler" if name.startswith("Thread-") else "other"


def count_calls(clock, stats):
    """Wrap ``time.<clock>``: calls and seconds by kind of thread."""
    real = getattr(time, clock)
    now = time.perf_counter

    def counted():
        t = now()
        value = real()
        spent = now() - t
        row = stats[(clock, kind_of_thread())]
        row[0] += 1
        row[1] += spent
        return value

    setattr(time, clock, counted)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--every", type=int, default=1,
                    help="read the clock in one outermost span in this "
                         "many; 0: the program's own rate")
    ap.add_argument("cell", nargs=argparse.REMAINDER,
                    help="-- then benchmark/run.py's arguments")
    args = ap.parse_args(argv)
    stats = collections.defaultdict(lambda: [0, 0.0])
    for clock in ("thread_time", "process_time"):
        count_calls(clock, stats)

    from benchmark import run
    from paddle_tpu.fluid import profiler
    from paddle_tpu.observability import trace

    if args.every:
        trace.set_cpu_every(args.every)
    run.main([a for a in args.cell if a != "--"])
    steps = profiler.get_counter("decode_steps")
    out = {"cpu_every": args.every or trace.CPU_EVERY,
           "decode_steps": steps, "cpu_count": os.cpu_count()}
    for (clock, who), (calls, seconds) in sorted(stats.items()):
        out["%s.%s" % (clock, who)] = {
            "calls": calls, "us_a_call": 1e6 * seconds / max(calls, 1),
            "calls_a_step": calls / max(steps, 1),
            "us_a_step": 1e6 * seconds / max(steps, 1)}
    print("CLOCKS " + json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
