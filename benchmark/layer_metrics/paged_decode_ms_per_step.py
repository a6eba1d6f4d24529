"""Milliseconds per T = 1 decode step in the paged attention kernel
(``flash_decode_paged_attention``), summed over layers."""

from benchmark.kernels import paged_decode


def read(ev):
    seconds = ev.kernel_seconds_per_step(paged_decode.EVENT_PATTERN)
    return None if seconds is None else 1e3 * seconds
