"""Milliseconds of device time of one whole T = 1 step program of a
latent-cache model (the program that runs ``mla_decode_paged``): what
``logits_fetch_ms_p50`` waits for before it copies, and the whole of which
``moe_ms_per_step`` and ``mla_decode_ms_per_step`` are parts."""

from benchmark.kernels import latent_decode


def read(ev):
    seconds = latent_decode.step_program_seconds(ev)
    return None if seconds is None else 1e3 * seconds
