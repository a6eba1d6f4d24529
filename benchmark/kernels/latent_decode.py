"""What one T = 1 step of a latent-cache expert model NEEDS, and which
device events belong to it.

The step program is the one that runs the ``mla_decode_paged`` kernel
(one call a layer); prefill windows, which may take more device time in
all, run it nowhere. Of the routed-expert op the device trace names the
three grouped products a layer (XLA's own ``ragged-dot`` kernels, where
the experts' weights stream): a fusion's event is its bare name
(``fusion.473``) and carries no ``jax.named_scope``, so the op's router,
sort and unsort are not told apart from the rest of the step.
"""

from benchmark.harness import xplane
from benchmark.kernels import flash_names

MLA_PATTERN = flash_names.event_pattern("mla_decode_paged")
MOE_PATTERN = r"^%?ragged-dot"
POOL_BYTES = 2  # bfloat16


def _steps(ev):
    """(first chip's plane, its whole T = 1 step programs)."""
    planes = ev.planes()
    if not planes:
        return None, []
    plane = planes[0]
    return plane, xplane.modules_running(plane, MLA_PATTERN)


def step_seconds(ev, pattern):
    """Summed time of the events named by ``pattern`` inside the whole
    T = 1 step programs on the first chip, per step; None without any."""
    plane, steps = _steps(ev)
    ops = xplane.matching(xplane.ops_inside(plane, steps), pattern) \
        if steps else []
    if not ops:
        return None
    return sum(e.dur for e in ops) / len(steps)


def step_program_seconds(ev):
    """Device time of one whole T = 1 step program, the mean over the
    profile's steps; None without any."""
    _plane, steps = _steps(ev)
    return sum(m.dur for m in steps) / len(steps) if steps else None


def mla_needs(config, live_lengths):
    """(FLOPs, bytes) of one step over all layers: a live row's latent
    and rope key (576 values) are read once for all heads; per head a
    score over those 576 and a weighted sum over the 512 of the latent,
    2 FLOPs a multiply-add."""
    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    heads, layers = config["num_attention_heads"], config["num_hidden_layers"]
    rows = float(sum(live_lengths))
    flops = layers * rows * 2 * heads * (row + config["kv_lora_rank"])
    return flops, layers * rows * row * POOL_BYTES


def moe_needs(config, experts_hit, assignments):
    """(FLOPs, bytes) of the routed experts of one step, all expert
    layers together: every expert with an assignment streams its three
    matrices once; an assignment is three products at the expert's width.
    The router and the shared experts run outside the grouped products
    and are in neither the bytes nor the time."""
    h, i = config["hidden_size"], config["moe_intermediate_size"]
    expert = 3 * h * i
    return assignments * 2 * expert, POOL_BYTES * experts_hit * expert
