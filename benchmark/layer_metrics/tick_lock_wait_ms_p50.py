"""The part of a tick's host time (``engine_tick`` less the
``executor_fetch`` spans inside it, what ``tick_host_ms_p50`` reads) in
which the loop thread held no CPU though it waited for no device, so it
waited for the interpreter lock, or for a core. Not a median of waits: a
thread's CPU clock may move in steps longer than a tick, so no one tick
has a wait to take a median of. It is the waiting SHARE of the host time
(1 - the summed CPU time, ``engine_tick``'s ``cpu_ms`` less the fetches',
over the summed host time of the ticks that read their CPU clock; a sum
is weighted by the long ticks, those that admit) applied to the median
host time of all the window's ticks, ``tick_host_ms_p50``'s figure. The
note ``tick_host_split`` gives the share as it came out and the sums it
rests on. None from a program whose spans carry no CPU clock, and where
the stamped ticks' CPU holds under ``cpu_spans.MIN_CLOCK_STEPS`` steps of
the clock (a device-bound cell: few ticks, little host time in each)."""

from benchmark.harness import cpu_spans


def read(ev):
    host = cpu_spans.tick_host(ev)
    if host is None:
        return None
    facts = host.facts()
    ev.ctx.note("tick_host_split", **facts)
    return facts["wait"]
