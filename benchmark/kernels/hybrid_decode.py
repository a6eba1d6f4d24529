"""What one T = 1 step of a model that keeps a recurrent state beside a
grouped-query KV cache NEEDS, and which device events belong to it.

The step program is the one that runs the ``kda_decode`` kernel (one call
a delta-rule layer); prefill windows, which scan chunks through XLA, run
it nowhere. Three parts of the step have names in the device trace: the
delta-rule kernel, the grouped paged attention kernel
(``flash_decode_paged_gqa``, one call a softmax layer) and the routed
experts' grouped products (XLA's own ``ragged-dot`` kernels, where the
held experts' weights stream; as in ``latent_decode.py`` the op's router,
sort and unsort carry no name and are not told apart from the rest).
"""

from benchmark.harness import program_spans as ps
from benchmark.harness import xplane
from benchmark.kernels import flash_names

KDA_PATTERN = flash_names.event_pattern("kda_decode")
GQA_PATTERN = flash_names.event_pattern("flash_decode_paged_gqa")
MOE_PATTERN = r"^%?ragged-dot"
POOL_BYTES = 2    # bfloat16 K/V rows and expert weights
STATE_BYTES = 4   # float32 recurrent state


def _steps(ev):
    """(first chip's plane, its whole T = 1 step programs)."""
    planes = ev.planes()
    if not planes:
        return None, []
    plane = planes[0]
    return plane, xplane.modules_running(plane, KDA_PATTERN)


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


def profiled(ev):
    """(start, end) of the profiled seconds on the host's clock, or None."""
    tracer = ev.facts.get("tracer")
    return None if tracer is None else tracer.window


def step_span_median(ev, arg):
    """Median of ``arg`` over the ``decode_paged_step`` spans inside the
    profiled seconds; None where the program notes no such thing."""
    window = profiled(ev)
    if window is None:
        return None
    steps = [s for s in ps.named(ps.in_window(ev), "decode_paged_step")
             if s["start"] >= window[0] and s["end"] <= window[1]]
    return ps.median_arg(steps, "decode_paged_step", lambda a: a.get(arg))


def live_lengths(ev):
    """Prompt plus tokens so far of every stream in flight at the middle
    of the profiled seconds, as the client knows them
    (``mla_decode_roofline`` takes them so)."""
    window = profiled(ev)
    if window is None:
        return []
    at = 0.5 * (window[0] + window[1])
    return [len(r.prompt) + sum(1 for x in r.times if x <= at)
            for r in ev.requests
            if r.sent is not None and r.sent <= at
            and (r.ended is None or r.ended >= at)]


def layer_counts(config):
    """(softmax layers, delta-rule layers) of the configuration's cut."""
    layers = config["num_hidden_layers"]
    gqa = sum(1 for i in config["gqa_layers"] if i < layers)
    return gqa, layers - gqa


def kda_needs(config, live_slots):
    """(FLOPs, bytes) of one step over all delta-rule layers: a live
    slot's state (heads x key x value, float32) is read once and written
    once; per state element a decay, a multiply-add into S~^T k, a
    multiply-add of k u^T and a multiply-add into S^T q: 7 FLOPs."""
    kda = config["linear_attn_config"]
    elements = (layer_counts(config)[1] * float(live_slots)
                * kda["num_heads"] * kda["head_dim"] ** 2)
    return 7 * elements, 2 * STATE_BYTES * elements


def gqa_needs(config, lengths):
    """(FLOPs, bytes) of one step over all softmax layers: a live row's K
    and V (key heads side by side) are read once for all the query heads;
    per query head a score and a weighted sum over ``head_dim``, 2 FLOPs
    a multiply-add."""
    layers = layer_counts(config)[0]
    rows = float(sum(lengths))
    d = config["head_dim"]
    flops = layers * rows * config["num_attention_heads"] * 2 * 2 * d
    moved = layers * rows * 2 * config["num_key_value_heads"] * d * POOL_BYTES
    return flops, moved


def moe_held_needs(config, experts_hit, assignments):
    """(FLOPs, bytes) of the routed experts HELD here in one step, all
    layers together: every held expert with an assignment streams its
    three matrices once; an assignment is three products at the expert's
    width. The router (over all the experts of the model) and the shared
    expert run outside the grouped products and are in neither the bytes
    nor the time."""
    expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    return assignments * 2 * expert, POOL_BYTES * experts_hit * expert
