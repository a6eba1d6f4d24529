"""Median over the window's ticks of ``engine_tick`` less the
``executor_fetch`` spans inside it: the part of a tick in which the
engine's loop thread was not waiting for the device. To set beside
``host_gap_ms.serve``, which reads the same from the device's side."""

from benchmark.harness import program_spans as ps
from benchmark.harness.stats import median


def read(ev):
    ps.note_tick_self(ev)
    spans = ps.in_window(ev)
    fetches = ps.named(spans, "executor_fetch")
    xs = [ps.ms(t) - sum(ps.ms(f) for f in ps.inside(t, fetches))
          for t in ps.named(spans, "engine_tick")]
    return median(xs) if xs else None
