"""Share of the roofline the latent paged attention kernel reaches over
its two calls a double layer, by the live rows the program says the
kernel reads (``latent_rows_live`` of the ``decode_paged_step`` spans
inside the profiled seconds, median), against the time
``mla_decode_ms_per_step`` reads."""

from benchmark.harness import peaks
from benchmark.kernels import hybrid_decode, latent_decode, shortcut_decode


def read(ev):
    seconds = latent_decode.step_seconds(ev, latent_decode.MLA_PATTERN)
    rows = hybrid_decode.step_span_median(ev, "latent_rows_live")
    if seconds is None or not rows:
        return None
    flops, moved = shortcut_decode.mla2_needs(ev.config, rows)
    return peaks.roofline_pct(flops, moved, seconds, ev.peaks)
