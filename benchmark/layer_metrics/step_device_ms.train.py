"""Device-busy milliseconds per train step: the union of the op intervals
inside the whole step programs of the profiled seconds, averaged over the
chips, per step."""

from benchmark.harness import xplane


def read(ev):
    per_chip = []
    for plane in ev.planes():
        steps = ev.steps(plane)
        if steps:
            busy = xplane.busy_seconds(xplane.ops_inside(plane, steps))
            per_chip.append(1e3 * busy / len(steps))
    return sum(per_chip) / len(per_chip) if per_chip else None
