"""Share of the roofline the delta-rule kernel reaches in a T = 1 step:
every LIVE slot's state read once and written once a delta-rule layer
(``state_slots_live`` of the ``decode_paged_step`` spans inside the
profiled seconds, median), against ``kda_decode_ms_per_step``."""

from benchmark.harness import peaks
from benchmark.kernels import hybrid_decode


def read(ev):
    seconds = hybrid_decode.step_seconds(ev, hybrid_decode.KDA_PATTERN)
    live = hybrid_decode.step_span_median(ev, "state_slots_live")
    if seconds is None or not live:
        return None
    flops, moved = hybrid_decode.kda_needs(ev.config, live)
    return peaks.roofline_pct(flops, moved, seconds, ev.peaks)
