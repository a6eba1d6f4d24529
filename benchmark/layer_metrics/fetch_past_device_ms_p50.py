"""Median, over the T = 1 steps of the profiled seconds, of how long
after the step's program had ended on the first chip the loop thread's
``executor_fetch`` returned (from the fetch's own start where the
program had ended before it): the copy back and the loop thread's wait
for its turn at the interpreter lock, which ``logits_fetch_ms_p50``
holds beside the wait for the device. The note gives the same fetches'
median wall time and the CPU share of those of them that read their CPU
clock. None without tied clocks, whole step modules or a loop thread
whose ticks read it."""

from benchmark.harness import cpu_spans
from benchmark.harness.stats import median


def read(ev):
    rows = cpu_spans.fetch_past_device_ms(ev)
    if not rows:
        return None
    ev.ctx.note("fetch_past_device",
                **cpu_spans.share_of([f for _ms, f in rows]).facts())
    return median([ms for ms, _f in rows])
