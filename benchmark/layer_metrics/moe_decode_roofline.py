"""Share of the roofline the routed experts' grouped products reach in a
T = 1 step:
the experts HIT stream their weights once (``experts_hit`` and
``assignments`` of the ``decode_paged_step`` spans inside the profiled
seconds, medians), against ``moe_ms_per_step``."""

from benchmark.harness import peaks
from benchmark.harness import program_spans as ps
from benchmark.kernels import latent_decode


def read(ev):
    seconds = latent_decode.step_seconds(ev, latent_decode.MOE_PATTERN)
    tracer = ev.facts.get("tracer")
    if seconds is None or tracer is None or tracer.window is None:
        return None
    t0, t1 = tracer.window
    steps = [s for s in ps.named(ps.in_window(ev), "decode_paged_step")
             if s["start"] >= t0 and s["end"] <= t1]
    hit = ps.median_arg(steps, "decode_paged_step",
                        lambda a: a.get("experts_hit"))
    assigned = ps.median_arg(steps, "decode_paged_step",
                             lambda a: a.get("assignments"))
    if hit is None or assigned is None:
        return None
    flops, moved = latent_decode.moe_needs(ev.config, hit, assigned)
    return peaks.roofline_pct(flops, moved, seconds, ev.peaks)
