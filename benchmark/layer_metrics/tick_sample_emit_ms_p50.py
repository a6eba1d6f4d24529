"""Median milliseconds of ``tick_sample_emit``: after the fused step,
one ``pick`` and one ``_emit`` a stream (each emit wakes an SSE handler
thread), speculative accounting and block trimming."""

from benchmark.harness import program_spans as ps
from benchmark.harness.stats import median


def read(ev):
    xs = [ps.ms(s) for s in ps.named(ps.in_window(ev), "tick_sample_emit")]
    return median(xs) if xs else None
