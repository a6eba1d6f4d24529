"""Milliseconds per train step in the grouped products of the routed
experts held here: the forward products, their transposes to dX and their
transposes to dW (``kernels/moe_train.py::MOE_PATTERN``), summed over the
expert layers, on the first chip."""

from benchmark.kernels import moe_train


def read(ev):
    seconds = ev.kernel_seconds_per_step(moe_train.MOE_PATTERN)
    return None if seconds is None else 1e3 * seconds
