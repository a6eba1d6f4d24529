"""Median over the window's ticks of ``tick_sample_emit``'s duration
less its ``cpu_ms`` (the loop thread's CPU time inside it): time the
loop thread held no CPU in a phase that makes no device call, so it was
waiting for the interpreter lock or for a core. The phase wakes no
thread any more (its emits fill an outbox that ``tick_publish`` empties
later), and where the CPU clock moves in 10 ms steps a difference taken
span by span says nothing: this has stood within 0.07 ms of
``tick_sample_emit_ms_p50`` on every line of the ledger and has never
read starvation. ``tick_lock_wait_ms_p50`` reads the wait by sums."""

from benchmark.harness import program_spans as ps
from benchmark.harness.stats import median


def read(ev):
    xs = [ps.ms(s) - s["args"]["cpu_ms"]
          for s in ps.named(ps.in_window(ev), "tick_sample_emit")
          if s["args"].get("cpu_ms") is not None]
    return median(xs) if xs else None
