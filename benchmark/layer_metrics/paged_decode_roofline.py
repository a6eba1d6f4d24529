"""Share of the roofline the paged decode kernel reaches, by the LIVE
keys and values of each stream (prompt plus tokens so far, as the client
knows them at the middle of the profiled seconds). The kernel
(``flash_decode_paged``) walks a slot's block table and neither fetches
nor computes a logical block past the slot's last live one, so what it
moves is the live rows rounded up to whole blocks, not a ``max_len``
rectangle; what separates it from 100 % is how fast it moves them."""

from benchmark.harness import peaks
from benchmark.kernels import paged_decode


def read(ev):
    seconds = ev.kernel_seconds_per_step(paged_decode.EVENT_PATTERN)
    tracer = ev.facts.get("tracer")
    if seconds is None or tracer is None or tracer.window is None:
        return None
    at = 0.5 * (tracer.window[0] + tracer.window[1])
    live = [len(r.prompt) + sum(1 for x in r.times if x <= at)
            for r in ev.requests
            if r.sent is not None and r.sent <= at
            and (r.ended is None or r.ended >= at)]
    if not live:
        return None
    flops, moved = paged_decode.needs(ev.config, live)
    return peaks.roofline_pct(flops, moved, seconds, ev.peaks)
