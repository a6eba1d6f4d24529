"""Share of the roofline the held experts' grouped products reach in a
train step: what the assignments the step counted need
(``kernels/moe_train.py::needs``; the ``assignments`` of the
``train_step`` spans inside the profiled seconds, median) against
``moe_train_ms_per_step``."""

from benchmark.harness import peaks
from benchmark.kernels import moe_train


def read(ev):
    seconds = ev.kernel_seconds_per_step(moe_train.MOE_PATTERN)
    assigned = moe_train.step_span_median(ev, "assignments")
    if seconds is None or assigned is None:
        return None
    flops, moved = moe_train.needs(ev.config, assigned)
    return peaks.roofline_pct(flops, moved, seconds, ev.peaks)
