"""Milliseconds per train step in which a collective operation
(all-gather, reduce-scatter, all-reduce) was under way on a chip, averaged
over the chips: what FSDP's sharded state costs in traffic."""

from benchmark.harness import xplane


def read(ev):
    per_chip = []
    for plane in ev.planes():
        steps = ev.steps(plane)
        if steps:
            coll, _other = xplane.collectives(plane, steps)
            per_chip.append(1e3 * xplane.measure(coll) / len(steps))
    if not per_chip or not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip)
