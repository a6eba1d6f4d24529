"""Share of the collectives' time during which no other operation ran on
that chip: the part of FSDP's traffic the step waits for."""

from benchmark.harness import xplane


def read(ev):
    total = exposed = 0.0
    for plane in ev.planes():
        steps = ev.steps(plane)
        if steps:
            coll, other = xplane.collectives(plane, steps)
            total += xplane.measure(coll)
            exposed += xplane.measure(xplane.subtract(coll, other))
    return 100.0 * exposed / total if total else None
