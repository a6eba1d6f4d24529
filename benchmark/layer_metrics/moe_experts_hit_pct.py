"""Share of the routed experts that received at least one assignment in
a T = 1 step, over the window: the ``moe_experts_hit`` counter's rise
over steps x expert layers x experts. What a step must stream."""


def read(ev):
    steps = ev.counters.get("decode_steps", 0)
    hit = ev.counters.get("moe_experts_hit")
    if not steps or hit is None:
        return None
    cfg = ev.config
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return 100.0 * hit / (steps * layers * cfg["n_routed_experts"])
