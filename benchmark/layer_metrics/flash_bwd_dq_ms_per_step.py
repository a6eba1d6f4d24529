"""Milliseconds per train step in the flash attention dQ backward kernel
(``flash_bwd_dq``), summed over layers, on the first chip."""

from benchmark.kernels import flash_names


def read(ev):
    seconds = ev.kernel_seconds_per_step(flash_names.event_pattern("flash_bwd_dq"))
    return None if seconds is None else 1e3 * seconds
