"""Milliseconds of one ``Executor.run`` spent gathering the arguments of
its compiled segments (``executor_marshal``: scope lookups and
``_to_device`` for every feed, state variable and constant), summed over
the run's segments; the median over the window's runs."""

from benchmark.harness import program_spans as ps
from benchmark.harness.stats import median


def read(ev):
    ps.note_executor_phases(ev)
    xs = ps.per_parent_ms(ps.in_window(ev), "executor_run",
                          "executor_marshal")
    return median(xs) if xs else None
