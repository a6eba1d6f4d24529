"""Share of the roofline the grouped-query paged attention kernel
reaches, by the LIVE rows of each stream (prompt plus tokens so far, as
the client knows them at the middle of the profiled seconds, as
``mla_decode_roofline`` takes them)."""

from benchmark.harness import peaks
from benchmark.kernels import hybrid_decode


def read(ev):
    seconds = hybrid_decode.step_seconds(ev, hybrid_decode.GQA_PATTERN)
    live = hybrid_decode.live_lengths(ev)
    if seconds is None or not live:
        return None
    flops, moved = hybrid_decode.gqa_needs(ev.config, live)
    return peaks.roofline_pct(flops, moved, seconds, ev.peaks)
