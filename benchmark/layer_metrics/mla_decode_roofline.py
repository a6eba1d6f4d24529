"""Share of the roofline the latent paged attention kernel reaches, by
the LIVE rows of each stream (prompt plus tokens so far, as the client
knows them at the middle of the profiled seconds, as
``paged_decode_roofline`` takes them)."""

from benchmark.harness import peaks
from benchmark.kernels import latent_decode


def read(ev):
    seconds = latent_decode.step_seconds(ev, latent_decode.MLA_PATTERN)
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
    flops, moved = latent_decode.mla_needs(ev.config, live)
    return peaks.roofline_pct(flops, moved, seconds, ev.peaks)
