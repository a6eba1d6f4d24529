"""90th percentile, over the requests that ended inside the window, of
a request's median lag between the engine's emit of a token and the SSE
writer's flush of it (``gateway_request``'s ``sse_lag_ms_p50``)."""

from benchmark.harness import program_spans as ps
from benchmark.harness.stats import percentile


def read(ev):
    xs = [s["args"]["sse_lag_ms_p50"]
          for s in ps.named(ps.in_window(ev), "gateway_request")
          if s["args"].get("sse_lag_ms_p50") is not None]
    return percentile(xs, 90) if xs else None
