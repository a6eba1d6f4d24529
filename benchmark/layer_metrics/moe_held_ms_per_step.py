"""Milliseconds per T = 1 step in the grouped products of the routed
experts HELD here (the ``ragged-dot`` kernels of each ``moe_ffn``, a
share of the model's experts), summed over layers."""

from benchmark.kernels import hybrid_decode


def read(ev):
    seconds = hybrid_decode.step_seconds(ev, hybrid_decode.MOE_PATTERN)
    return None if seconds is None else 1e3 * seconds
