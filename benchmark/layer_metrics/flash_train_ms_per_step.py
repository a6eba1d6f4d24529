"""Milliseconds per train step in the flash attention kernels (forward,
dq, dkv), summed over layers, on the first chip."""

from benchmark.kernels import flash_train


def read(ev):
    seconds = ev.kernel_seconds_per_step(flash_train.EVENT_PATTERN)
    return None if seconds is None else 1e3 * seconds
