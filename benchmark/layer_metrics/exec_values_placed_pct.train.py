"""Of the values ``executor_marshal`` gathered in the window, the share
that went through ``jax.device_put`` (``placed`` over ``values``): about
0 where the state is already resident, 100 where every value is placed
again on every step."""

from benchmark.harness import program_spans as ps


def read(ev):
    spans = ps.named(ps.in_window(ev), "executor_marshal")
    values = sum(s["args"].get("values", 0) for s in spans)
    if not values:
        return None
    return 100.0 * sum(s["args"].get("placed", 0) for s in spans) / values
