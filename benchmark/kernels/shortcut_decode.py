"""What one T = 1 step of a shortcut-connected expert model with two
latent attentions a double layer NEEDS, and which device events belong
to it.

The step program is the one that runs the ``mla_decode_paged`` kernel,
here ``2 * num_layers`` calls a step (``latent_decode.mla_needs`` counts
one a layer and would halve this model's rows). Two parts of the step
have names in the device trace: that kernel, and the held experts'
grouped products (XLA's own ``ragged-dot`` kernels, three a double
layer); as in ``latent_decode.py`` the expert op's router, sort and
unsort, and the identity experts' ``gate * x``, carry no name and are not
told apart from the rest.

The times are ``latent_decode.py``'s (the same step program, the same
two event names: ``decode_step_device_ms``, ``mla_decode_ms_per_step``
and ``moe_ms_per_step`` read this model unchanged); what is this
model's own is what a step NEEDS.
"""

POOL_BYTES = 2    # bfloat16 latent rows and expert weights


def mla2_needs(config, rows_a_double_layer):
    """(FLOPs, bytes) of one step over all ``2 * num_layers`` attentions.
    ``rows_a_double_layer``: the live latent rows the two kernel calls of
    ONE double layer read (``latent_rows_live`` of the step's span: a
    token's row in each of the two pools). A row's latent and rope key
    (576 values) are read once for all heads; per head a score over those
    576 and a weighted sum over the 512 of the latent, 2 FLOPs a
    multiply-add."""
    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    rows = float(rows_a_double_layer) * config["num_layers"]
    flops = rows * 2 * config["num_attention_heads"] * (
        row + config["kv_lora_rank"])
    return flops, rows * row * POOL_BYTES


def held_needs(config, experts_hit, assignments):
    """(FLOPs, bytes) of the routed experts HELD here in one step, all
    double layers together: every held expert with an assignment streams
    its three matrices once; an assignment is three products at the
    expert's width. Identity assignments are in neither: they multiply
    nothing and stream nothing. The router runs outside the grouped
    products and is in neither the bytes nor the time."""
    expert = 3 * config["hidden_size"] * config["expert_ffn_hidden_size"]
    return assignments * 2 * expert, POOL_BYTES * experts_hit * expert
