"""Share of the routed experts HELD here that received at least one
assignment in a T = 1 step, over the window: the ``moe_experts_hit``
counter's rise over steps x double layers x experts held. What a step
must stream."""


def read(ev):
    steps = ev.counters.get("decode_steps", 0)
    hit = ev.counters.get("moe_experts_hit")
    if not steps or hit is None:
        return None
    cfg = ev.config
    return 100.0 * hit / (steps * cfg["num_layers"] * cfg["n_routed_experts"])
