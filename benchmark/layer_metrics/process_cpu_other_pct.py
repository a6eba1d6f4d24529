"""Share of the process's CPU time, over the window's ticks that read the
CPU clocks (``engine_tick``'s ``process_cpu_ms``: ``time.process_time``
over the tick, all threads), that neither the engine's loop thread
(``engine_tick``'s ``cpu_ms``) nor the gateway's handlers
(``gateway_handler_cpu_us`` over the window, a tick's share of it) used:
the runtime's own threads (the device runtime's, the server's accept loop
and workers, the profiler's in a traced run) and nothing of the benchmark,
whose clients run in a process of their own (``harness/loadgen.py``); up
to PR 40 the 64 in-process clients were counted here and made up most of
it. The note gives the three in ms a tick."""

from benchmark.harness import cpu_spans


def read(ev):
    sums = cpu_spans.tick_cpu_sums(ev)
    handlers_us = ev.counters.get("gateway_handler_cpu_us")
    if sums is None or handlers_us is None:
        return None
    ticks, stamped, process_ms, loop_ms = sums
    if process_ms <= 0:
        return None
    process_ms, loop_ms = process_ms / stamped, loop_ms / stamped
    handlers_ms = handlers_us / 1e3 / ticks
    other_ms = process_ms - loop_ms - handlers_ms
    ev.ctx.note("process_cpu_a_tick", ticks=ticks, stamped_ticks=stamped,
                process_ms=process_ms, loop_ms=loop_ms,
                handlers_ms=handlers_ms, other_ms=other_ms)
    return 100.0 * other_ms / process_ms
