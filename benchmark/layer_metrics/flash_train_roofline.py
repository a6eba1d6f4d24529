"""Share of the roofline the training flash kernels reach: the least time
the chip could take for the operations and bytes causal attention needs
(``kernels/flash_train.py``) over the kernels' summed time."""

from benchmark.harness import peaks
from benchmark.kernels import flash_train


def read(ev):
    seconds = ev.kernel_seconds_per_step(flash_train.EVENT_PATTERN)
    if seconds is None:
        return None
    flops, moved = flash_train.needs(ev.config, ev.traffic, ev.chips)
    return peaks.roofline_pct(flops, moved, seconds, ev.peaks)
