"""The profiler, for a few seconds in the middle of a traced run's window.

A whole window would not come back (a decode tick is hundreds of
thousands of kernel programs); spans and counters cover all of it.
"""

import contextlib
import shutil
import tempfile
import time


class MidWindow(object):
    """``poll(elapsed)`` from the window's loop starts the profiler at
    40 % of the window and stops it ``trace_s`` later. ``anchor`` is the
    host clock (``perf_counter``) read inside the ``bench_anchor``
    annotation: it ties the program's spans to the profiler's clock."""

    def __init__(self, ctx, trace_s):
        self.on = bool(ctx.trace)
        self.start_at = 0.4 * ctx.seconds
        self.trace_s = min(float(trace_s), 0.5 * ctx.seconds)
        self.trace_dir = None
        self.window = None   # (perf_counter at start, at stop)
        self.anchor = None
        self._state = "idle"

    def poll(self, elapsed):
        if not self.on:
            return
        if self._state == "idle" and elapsed >= self.start_at:
            import jax

            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.trace_dir)
            with jax.profiler.TraceAnnotation("bench_anchor"):
                self.anchor = time.perf_counter()
            self._t0 = time.perf_counter()
            self._state = "tracing"
        elif (self._state == "tracing"
              and elapsed >= self.start_at + self.trace_s):
            self._stop()

    def _stop(self):
        import jax

        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.window = (self._t0, t1)
        self._state = "done"

    def finish(self):
        if self._state == "tracing":
            self._stop()

    def annotate(self, name):
        if self._state != "tracing":
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def discard(self):
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
