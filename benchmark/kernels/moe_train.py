"""What one TRAIN step of a routed-expert model NEEDS in the grouped
products of the experts held here, forward and both transposes, and the
model FLOPs of the whole step; which device events the grouped products
are, and what the step's ``train_step`` spans say of its counts.

Counts the algorithm, not today's lowering: the same numbers whatever
implements the products (XLA's ``ragged-dot``, which on the chip is a
``tpu_custom_call`` of its own under the names ``ragged-dot-none.N`` and
``ragged-dot-metadata.N``, forward, dX and dW alike; or a kernel of the
program's, under its own name through ``kernels/flash_names.py``).
"""

from benchmark.harness import program_spans as ps
from benchmark.references.lfm2 import sizes

MOE_PATTERN = r"^%?ragged-dot"
BYTES = 2         # bfloat16 rows, expert stacks and their gradients
PASSES = 3        # forward, the transpose to dX, the transpose to dW
PRODUCTS = 3      # W1, W3, W2


def expert_layers(config):
    """How many of the configuration's layers route (``sizes`` reads
    its cut in depth)."""
    z = sizes(config)
    return len(z["kinds"]) - z["dense"]


def step_spans(ev):
    """The ``train_step`` spans inside the profiled seconds (the whole
    window where nothing was profiled); [] on a program that opens none."""
    spans = ps.named(ps.in_window(ev), "train_step")
    tracer = ev.facts.get("tracer")
    window = None if tracer is None else tracer.window
    if window is not None:
        spans = [s for s in spans
                 if s["start"] >= window[0] and s["end"] <= window[1]]
    return spans


def step_span_median(ev, arg):
    """Median of ``arg`` over those spans; None where none notes it."""
    return ps.median_arg(step_spans(ev), "train_step",
                         lambda a: (a or {}).get(arg))


def needs(config, assignments):
    """(FLOPs, bytes) of the held experts' grouped products in one step,
    all expert layers together, for ``assignments`` rows routed to them
    (the step's counter): each of the three passes runs three products of
    2 x H x I FLOPs a row; each pass reads the held experts' three stacks
    once and moves a row in and a row out (H wide) an assignment; the
    stacks' gradients are written once."""
    z = sizes(config)
    h, i = z["h"], z["mi"]
    stacks = expert_layers(config) * z["held"] * PRODUCTS * h * i
    flops = PASSES * PRODUCTS * 2.0 * assignments * h * i
    moved = BYTES * (PASSES * stacks + stacks
                     + PASSES * 2.0 * assignments * h)
    return flops, moved


def even_share(config, traffic):
    """Assignments an expert gets when every token's picks spread evenly
    over the PUBLISHED experts: T * k / E."""
    z = sizes(config)
    tokens = traffic["batch"] * traffic["seq_len"]
    return tokens * z["topk"] / float(z["experts"])


def model_flops(config, traffic):
    """FLOPs one train step's model needs: 6 a token for every matmul
    weight the token meets (operator, router, the dense MLP or its even
    share of the held experts, the tied head), plus causal attention
    counted once (half the rectangle), forward and twice that backward.
    Recomputation counts nothing."""
    z = sizes(config)
    h, heads, d = z["h"], z["heads"], z["d"]
    held_share = z["held"] / float(z["experts"])
    weights, attention_layers = h * config["vocab_size"], 0
    for i, kind in enumerate(z["kinds"]):
        if kind == "full_attention":
            weights += 2 * h * heads * d + 2 * h * z["kvh"] * d
            attention_layers += 1
        else:
            weights += 4 * h * h
        if i < z["dense"]:
            weights += 3 * h * z["ffn"]
        else:
            weights += h * z["experts"] + (
                z["topk"] * held_share * 3 * h * z["mi"])
    b, s = traffic["batch"], traffic["seq_len"]
    attention = attention_layers * 3 * (2 * 2 * b * s * s * heads * d / 2.0)
    return 6.0 * weights * b * s + attention
