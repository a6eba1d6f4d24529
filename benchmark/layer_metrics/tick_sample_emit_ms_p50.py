"""Median milliseconds of ``tick_sample_emit``: after the fused step,
for every stream its token taken from the step's fetched ids (or picked
on the host from its logits row where it samples) and one ``_emit``,
speculative accounting and block trimming. An emit only lays the token
in the tick's outbox: the handlers' threads are woken later, in
``tick_publish``, once the next device call is dispatched
(``tick_publish_ms_p50``)."""

from benchmark.harness import program_spans as ps
from benchmark.harness.stats import median


def read(ev):
    xs = [ps.ms(s) for s in ps.named(ps.in_window(ev), "tick_sample_emit")]
    return median(xs) if xs else None
