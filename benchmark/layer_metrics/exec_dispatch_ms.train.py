"""Milliseconds of one ``Executor.run`` inside ``executor_dispatch``
(signature, executable lookup, the call of the AOT executable until it
returns), summed over the run's segments; the median over the window's
runs."""

from benchmark.harness import program_spans as ps
from benchmark.harness.stats import median


def read(ev):
    xs = ps.per_parent_ms(ps.in_window(ev), "executor_run",
                          "executor_dispatch")
    return median(xs) if xs else None
