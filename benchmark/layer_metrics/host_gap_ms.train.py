"""Median device-idle gap between the end of one train step's program and
the start of the next on the first chip: what the executor, the feed and
the loss fetch cost between dispatches."""

from benchmark.harness.stats import median


def read(ev):
    steps = ev.steps()
    gaps = [1e3 * (b.start - a.end) for a, b in zip(steps, steps[1:])]
    return median(gaps) if gaps else None
