"""Milliseconds per T = 1 step in the delta-rule kernel (``kda_decode``),
summed over the delta-rule layers."""

from benchmark.kernels import hybrid_decode


def read(ev):
    seconds = hybrid_decode.step_seconds(ev, hybrid_decode.KDA_PATTERN)
    return None if seconds is None else 1e3 * seconds
