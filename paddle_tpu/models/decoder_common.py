"""The pieces the pre-norm expert decoders share, the served ones
(``models/deepseek.py``, ``models/solar_open2.py``,
``models/longcat_flash.py``) and the trained one (``models/lfm2.py``):
parameters in ``cfg.dtype``, bias-free linears, RMSNorm, the gated SiLU
MLP, the routed-expert layer over the experts HELD here with its shared
experts, the untied float32 head, a fresh pair of programs, and what a
step's expert counts say. A model passes its own name prefix, so
parameter names are each model's own.

A config gives ``dtype``, ``rms_norm_eps``, ``hidden_size``,
``vocab_size``, ``moe_intermediate_size``, ``n_shared_experts``,
``num_experts_per_tok``, ``routed_scaling_factor`` and, for the expert
layer, ``n_routed_experts`` (every expert of the model that has
weights), ``experts_held`` and ``expert_offset`` (the experts whose
weights lie here: global numbers ``expert_offset .. expert_offset +
experts_held - 1``). A config may also give ``router_scoring``
(``softmax`` for ``sigmoid``), ``norm_topk_prob`` (false: gates not
renormalised), ``zero_experts`` (identity experts, router outputs
after the ``n_routed_experts``), ``router_norm_eps`` (what the
renormalisation adds to the chosen scores' sum, 1e-20 unless given) and
``router_bias_trainable`` (false: the bias that chooses is a buffer no
optimizer touches).
"""

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.layer_helper import LayerHelper


def param(name, shape, cfg, dtype=None, value=None, trainable=True):
    init = None if value is None else fluid.initializer.Constant(value)
    attr = None if trainable else fluid.ParamAttr(name=name, trainable=False)
    return fluid.layers.create_parameter(
        shape=shape, dtype=dtype or cfg.dtype, name=name, attr=attr,
        default_initializer=init)


def linear(x, size, name):
    """x W, no bias; W is ``<name>.w_0`` in x's dtype."""
    return fluid.layers.fc(input=x, size=size, num_flatten_dims=2,
                           bias_attr=False, name=name)


def norm(x, cfg, name):
    return fluid.layers.rms_norm(
        x, param(name, [x.shape[-1]], cfg, value=1.0),
        epsilon=cfg.rms_norm_eps)


def gated_mlp(x, width, hidden, name):
    h = fluid.layers.swiglu(linear(x, width, name + "_w1"),
                            linear(x, width, name + "_w3"))
    return linear(h, hidden, name + "_w2")


def routed_experts(x, cfg, name):
    """The routed part alone: the router over all ``n_routed_experts``
    (and the config's identity experts after them), the grouped products
    over the ``experts_held`` here. -> (routed, counts int32
    [experts_held], identity assignments int32 [1] or None). A caller may
    add the result where it was computed (``expert_layer``) or carry it
    past other layers as a shortcut (``models/longcat_flash.py``)."""
    e, held, h, i = (cfg.n_routed_experts, cfg.experts_held,
                     cfg.hidden_size, cfg.moe_intermediate_size)
    zeros = getattr(cfg, "zero_experts", 0)
    out = fluid.layers.moe_ffn(
        x, param(name + "_router.w_0", [h, e + zeros], cfg),
        param(name + "_router_bias", [e + zeros], cfg, dtype="float32",
              value=0.0,
              trainable=getattr(cfg, "router_bias_trainable", True)),
        param(name + "_experts_w1", [held, h, i], cfg),
        param(name + "_experts_w3", [held, h, i], cfg),
        param(name + "_experts_w2", [held, i, h], cfg),
        num_experts=e, experts_per_token=cfg.num_experts_per_tok,
        expert_offset=cfg.expert_offset, scaling=cfg.routed_scaling_factor,
        scoring=getattr(cfg, "router_scoring", "sigmoid"),
        norm_topk=getattr(cfg, "norm_topk_prob", True), zero_experts=zeros,
        norm_eps=getattr(cfg, "router_norm_eps", 1e-20))
    return out if zeros else out + (None,)


def expert_layer(x, cfg, name):
    """Routed experts + the shared experts as one MLP. -> (y, counts
    int32 [experts_held])."""
    routed, counts, _zero = routed_experts(x, cfg, name)
    shared = gated_mlp(x, cfg.n_shared_experts * cfg.moe_intermediate_size,
                       cfg.hidden_size, name + "_shared")
    return fluid.layers.elementwise_add(routed, shared), counts


def float32_logits(x, w, name):
    """x W with the float32 accumulator kept for the result, whatever
    dtype the operands come in."""
    helper = LayerHelper(name)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="mul", inputs={"X": [x], "Y": [w]}, outputs={"Out": [out]},
        attrs={"x_num_col_dims": len(x.shape) - 1, "y_num_col_dims": 1,
               "out_dtype": fluid.core.np_to_dtype("float32")})
    return out


def lm_head(h, cfg, prefix):
    """Final RMSNorm (``<prefix>_norm``) and the untied head
    (``<prefix>_head.w_0``): float32 logits."""
    x = norm(h, cfg, prefix + "_norm")
    w = param(prefix + "_head.w_0", [cfg.hidden_size, cfg.vocab_size], cfg)
    return float32_logits(x, w, prefix + "_head")


def programs(donate=False):
    main, startup = fluid.Program(), fluid.Program()
    main._donate_mutable = donate
    return main, startup


def last_row_logits(h, last_onehot, cfg, prefix):
    """A prefill window's next-token logits: the last real token's hidden
    row is picked (``last_onehot`` [N, T, 1]) BEFORE the head, so the head
    runs on one row. -> [N, vocab] float32."""
    last = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(
        fluid.layers.cast(h, "float32"), last_onehot), dim=1, keep_dim=True)
    return fluid.layers.reshape(
        lm_head(fluid.layers.cast(last, cfg.dtype), cfg, prefix),
        shape=[-1, cfg.vocab_size])


def _expert_counts_say(counts):
    """{assignments, experts_hit, expert_load_max} of a step's counts."""
    return dict(assignments=int(counts.sum()),
                experts_hit=int((counts > 0).sum()),
                expert_load_max=int(counts.max()))


def expert_step_stats(counts, zero_counts=None):
    """What one T = 1 step's expert counts (``[expert layers, experts
    held]`` int32, as fetched) say, for the ``decode_paged_step`` span
    and ``/metrics``; ``zero_counts`` ([expert layers, 1]): the
    assignments that went to identity experts, which are in no expert's
    count."""
    from paddle_tpu.fluid import profiler

    out = _expert_counts_say(counts)
    profiler.bump_counter("moe_assignments", out["assignments"])
    profiler.bump_counter("moe_experts_hit", out["experts_hit"])
    profiler.bump_histogram("moe_expert_load_max", out["expert_load_max"])
    if zero_counts is not None:
        out["zero_assignments"] = int(zero_counts.sum())
        profiler.bump_counter("moe_zero_assignments", out["zero_assignments"])
    return out


def expert_train_stats(counts, span=None):
    """What one TRAIN step's expert counts (``[expert layers, experts
    held]`` int32, fetched with the loss) say: the assignments the held
    experts received, how many of them received any, and the fullest
    one's. Bumps ``moe_train_assignments``, ``moe_train_experts_hit`` and
    the histogram ``moe_train_expert_load_max``, and notes the three on
    ``span`` (the step's ``train_step`` span) where one is given."""
    from paddle_tpu.fluid import profiler

    # literal names: tools/flags_lint.py finds a metric by its literal
    out = _expert_counts_say(counts)
    profiler.bump_counter("moe_train_assignments", out["assignments"])
    profiler.bump_counter("moe_train_experts_hit", out["experts_hit"])
    profiler.bump_histogram("moe_train_expert_load_max",
                            out["expert_load_max"])
    if span is not None:
        span.note(**out)
    return out
