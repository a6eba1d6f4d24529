"""Share of the roofline the held experts' grouped products reach in a
T = 1 step: the held experts HIT stream their weights once
(``experts_hit`` and ``assignments`` of the ``decode_paged_step`` spans
inside the profiled seconds, medians), against ``moe_held_ms_per_step``."""

from benchmark.harness import peaks
from benchmark.kernels import hybrid_decode


def read(ev):
    seconds = hybrid_decode.step_seconds(ev, hybrid_decode.MOE_PATTERN)
    hit = hybrid_decode.step_span_median(ev, "experts_hit")
    assigned = hybrid_decode.step_span_median(ev, "assignments")
    if seconds is None or hit is None or assigned is None:
        return None
    flops, moved = hybrid_decode.moe_held_needs(ev.config, hit, assigned)
    return peaks.roofline_pct(flops, moved, seconds, ev.peaks)
