"""Share of a T = 1 step's router assignments that went to identity
experts, over the window: the ``moe_zero_assignments`` counter's rise
over steps x slots x ``moe_topk`` x double layers (every slot of the
fused step routes, an idle one its token 0). Work the step did not have
to do: 256 of 768 outputs under uniform ids, and the dial that uneven
routing would turn."""


def read(ev):
    steps = ev.counters.get("decode_steps", 0)
    zero = ev.counters.get("moe_zero_assignments")
    if not steps or zero is None:
        return None
    cfg = ev.config
    routed = (steps * cfg["serve"]["slots"] * cfg["moe_topk"]
              * cfg["num_layers"])
    return 100.0 * zero / routed
