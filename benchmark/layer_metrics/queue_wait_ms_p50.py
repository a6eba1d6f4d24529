"""Median ``queue_wait_ms`` (submit to the engine's dequeue) over the
``decode_request`` records of the requests dequeued inside the window."""

from benchmark.harness import program_spans as ps
from benchmark.harness.stats import median


def read(ev):
    t0, t1 = ev.window
    xs = [r["args"]["queue_wait_ms"]
          for r in ps.instants(ps.in_window(ev), "decode_request")
          if r["args"].get("queue_wait_ms") is not None
          and t0 <= r["args"]["dequeue"] <= t1]
    return median(xs) if xs else None
