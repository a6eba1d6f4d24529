"""Per decode tick, the period between the starts of consecutive T = 1
step programs on the device minus the time an operation ran in it (the
median over the profiled ticks): what scheduling, sampling and the
request path cost the chip."""

from benchmark.harness import xplane
from benchmark.harness.stats import median


def read(ev):
    planes = ev.planes()
    steps = ev.steps()
    if len(steps) < 2:
        return None
    out = []
    merged = xplane.union_intervals(planes[0].ops)
    starts = [a for a, _b in merged]
    for a, b in zip(steps, steps[1:]):
        busy = xplane.busy_between(merged, starts, a.start, b.start)
        out.append(1e3 * ((b.start - a.start) - busy))
    return median(out)
