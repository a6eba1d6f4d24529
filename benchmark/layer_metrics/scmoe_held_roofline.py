"""Share of the roofline the held experts' grouped products reach in a
T = 1 step: the held experts HIT stream their weights once
(``experts_hit`` and ``assignments`` of the ``decode_paged_step`` spans
inside the profiled seconds, medians; identity assignments are in
neither), against the time ``moe_ms_per_step`` reads."""

from benchmark.harness import peaks
from benchmark.kernels import hybrid_decode, latent_decode, shortcut_decode


def read(ev):
    seconds = latent_decode.step_seconds(ev, latent_decode.MOE_PATTERN)
    hit = hybrid_decode.step_span_median(ev, "experts_hit")
    assigned = hybrid_decode.step_span_median(ev, "assignments")
    if seconds is None or hit is None or assigned is None:
        return None
    flops, moved = shortcut_decode.held_needs(ev.config, hit, assigned)
    return peaks.roofline_pct(flops, moved, seconds, ev.peaks)
