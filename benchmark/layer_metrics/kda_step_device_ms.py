"""Milliseconds of device time of one whole T = 1 step program of a model
with a recurrent state (the program that runs ``kda_decode``): the whole
of which ``kda_decode_ms_per_step``, ``gqa_decode_ms_per_step`` and
``moe_held_ms_per_step`` are parts."""

from benchmark.kernels import hybrid_decode


def read(ev):
    seconds = hybrid_decode.step_program_seconds(ev)
    return None if seconds is None else 1e3 * seconds
