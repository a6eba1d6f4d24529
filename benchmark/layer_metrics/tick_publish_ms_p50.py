"""Median wall milliseconds of the ``tick_publish`` spans that hand over
at least one token: the outbox emptied into the streams' queues, which
wakes the handlers' threads. The note gives how much of it is CPU and how
much waiting, by the CPU share of those of them that read their CPU
clock (the loop thread's wait for the lock it has just given the woken
handlers a reason to take), on however few steps of the clock that
rests (``cpu_sum``). None where none of them read it. Before it, the
line ``tick_wait_by_phase``: the same split for every span name of the
loop thread."""

from benchmark.harness import cpu_spans
from benchmark.harness import program_spans as ps
from benchmark.harness.stats import median


def read(ev):
    cpu_spans.note_wait_by_phase(ev)
    spans = [s for s in ps.named(cpu_spans.in_window(ev), "tick_publish")
             if (s["args"].get("tokens") or 0) >= 1]
    share = cpu_spans.share_of(spans)
    if not share.stamped:
        return None
    facts = share.facts()
    ev.ctx.note("tick_publish_split",
                tokens_p50=median([s["args"]["tokens"] for s in spans]),
                **facts)
    return facts["wall"]
