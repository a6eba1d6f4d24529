"""Milliseconds per T = 1 step in the routed experts' grouped products
(the three ``ragged-dot`` kernels of each ``moe_ffn``), summed over
layers."""

from benchmark.kernels import latent_decode


def read(ev):
    seconds = latent_decode.step_seconds(ev, latent_decode.MOE_PATTERN)
    return None if seconds is None else 1e3 * seconds
