"""Median over the window's T = 1 steps of the ``executor_fetch`` span
inside ``decode_paged_step``: the wait for the step and the copy of what
it fetches to the host. Since the step programs end in an argmax that is
the [slots, 1] token ids, 256 B at 64 slots; the [slots, vocab] float32
logits the name recalls stay on the device (``decode_step_logits.w1``),
so all but microseconds of this is waiting for the device."""

from benchmark.harness import program_spans as ps
from benchmark.harness.stats import median


def read(ev):
    spans = ps.in_window(ev)
    fetches = ps.named(spans, "executor_fetch")
    xs = [sum(ps.ms(f) for f in ps.inside(step, fetches))
          for step in ps.named(spans, "decode_paged_step")]
    xs = [x for x in xs if x > 0]
    return median(xs) if xs else None
