"""Milliseconds per T = 1 step in the grouped-query paged attention
kernel (``flash_decode_paged_gqa``), summed over the softmax layers."""

from benchmark.kernels import hybrid_decode


def read(ev):
    seconds = hybrid_decode.step_seconds(ev, hybrid_decode.GQA_PATTERN)
    return None if seconds is None else 1e3 * seconds
