"""Median milliseconds of ``executor_fetch``: the host waiting for the
device to finish the step and copying the fetches back."""

from benchmark.harness import program_spans as ps
from benchmark.harness.stats import median


def read(ev):
    xs = [ps.ms(s) for s in ps.named(ps.in_window(ev), "executor_fetch")]
    return median(xs) if xs else None
