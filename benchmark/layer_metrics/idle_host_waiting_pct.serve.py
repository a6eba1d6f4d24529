"""Share of the first chip's idle seconds (profiled seconds) that pass
while the engine's loop thread is in a span outside ``executor_fetch``
and holds no CPU: each gap is shared out by overlap among the loop
thread's innermost spans, as ``idle_unattributed_pct.serve`` does, and
each share split by the waiting share of the spans of that name (1 -
their own CPU time over their own wall time, summed over the window: a
thread's CPU clock may move in steps longer than any one span; a share
as it comes out, not cut to [0, 1]). The note
``idle_by_what_the_host_did`` gives the idle seconds the host spent
working, lock-waiting and inside a fetch (and the few no span covers),
with the spans that hold most of each. None where the stamped spans'
CPU holds under ``cpu_spans.MIN_CLOCK_STEPS`` steps of the clock (a
device-bound cell)."""

from benchmark.harness import cpu_spans


def read(ev):
    idle = cpu_spans.idle_by_what_the_host_did(ev)
    if not idle:
        return None
    total = sum(idle[k] for k in
                ("working", "lock_waiting", "in_fetch", "no_span"))
    if total <= 0:
        return None
    ev.ctx.note("idle_by_what_the_host_did", idle_s=total, **idle)
    return 100.0 * idle["lock_waiting"] / total
