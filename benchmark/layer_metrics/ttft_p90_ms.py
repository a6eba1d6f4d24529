"""Client clock, POST sent to first SSE token, 90th percentile over the
requests whose first token arrived inside the window. With as many
clients as slots nothing queues, so this is prefill time plus the request
path."""

from benchmark.harness.stats import percentile


def read(ev):
    t0, t1 = ev.window
    xs = [1e3 * (r.times[0] - r.sent) for r in ev.requests
          if r.times and t0 <= r.times[0] <= t1]
    return percentile(xs, 90) if len(xs) >= 10 else None
