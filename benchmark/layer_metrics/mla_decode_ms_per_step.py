"""Milliseconds per T = 1 step in the latent paged attention kernel
(``mla_decode_paged``), summed over layers."""

from benchmark.kernels import latent_decode


def read(ev):
    seconds = latent_decode.step_seconds(ev, latent_decode.MLA_PATTERN)
    return None if seconds is None else 1e3 * seconds
