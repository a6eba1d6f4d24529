"""Layer functions of the decoder ops (``fluid/ops/decoder_ops.py``):
latent attention, routed experts, the gated short convolution, the gated
delta rule (KDA) and grouped-query window attention. ``rms_norm``,
``rotary_embedding``, ``swiglu``, ``moe_ffn`` and ``gated_short_conv``
have gradients; the others are inference only."""

from ..layer_helper import LayerHelper

__all__ = [
    "rms_norm",
    "rotary_embedding",
    "swiglu",
    "moe_ffn",
    "gated_short_conv",
    "mla_window_attention",
    "mla_decode_paged_attention",
    "kda_window",
    "kda_step",
    "gqa_window_attention",
]


def _one_out(op_type, inputs, attrs, dtype, name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op(type=op_type, inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def rms_norm(x, scale, epsilon=1e-6, name=None):
    """``scale * x / sqrt(mean(x^2) + epsilon)`` over the last axis
    (float32 statistics); ``scale`` is a [C] parameter the caller made."""
    return _one_out("rms_norm", {"X": [x], "Scale": [scale]},
                    {"epsilon": float(epsilon)}, x.dtype, name)


def rotary_embedding(x, pos, head_dim, rope_dim, theta=10000.0,
                     interleaved=False, name=None):
    """Rotary positions at the fed ``pos`` [N, T(, 1)] on the last
    ``rope_dim`` values of every ``head_dim`` chunk of ``x`` [N, T, C]."""
    return _one_out("rotary_embedding", {"X": [x], "Pos": [pos]},
                    {"head_dim": int(head_dim), "rope_dim": int(rope_dim),
                     "theta": float(theta),
                     "interleaved": bool(interleaved)}, x.dtype, name)


def swiglu(gate, up, name=None):
    """silu(gate) * up."""
    return _one_out("swiglu", {"Gate": [gate], "Up": [up]}, {}, gate.dtype,
                    name)


def moe_ffn(x, router_w, router_bias, w1, w3, w2, num_experts,
            experts_per_token, expert_offset=0, scaling=1.0,
            scoring="sigmoid", norm_topk=True, zero_experts=0,
            norm_eps=1e-20, name=None):
    """The routed part of a sparse expert layer over the experts held
    (``w1``/``w3`` [E_held, H, I], ``w2`` [E_held, I, H], global numbers
    from ``expert_offset``). -> (out like ``x``, counts int32 [E_held]),
    and with ``zero_experts`` identity experts after the ``num_experts``
    (``router_w`` that much wider) a third: the assignments that went to
    them, int32 [1]. ``scoring``, ``norm_topk``, ``norm_eps``: the
    op's."""
    helper = LayerHelper("moe_ffn", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    counts = helper.create_variable_for_type_inference(dtype="int32")
    outputs = {"Out": [out], "Counts": [counts]}
    attrs = {"num_experts": int(num_experts),
             "experts_per_token": int(experts_per_token),
             "expert_offset": int(expert_offset),
             "scaling": float(scaling)}
    # an attribute at its default is left out: a program that asks for
    # none of them is, op for op and byte for byte, what it was
    for key, value, default in (("scoring", str(scoring), "sigmoid"),
                                ("norm_topk", bool(norm_topk), True),
                                ("zero_experts", int(zero_experts), 0),
                                ("norm_eps", float(norm_eps), 1e-20)):
        if value != default:
            attrs[key] = value
    if zero_experts:
        outputs["ZeroCount"] = [
            helper.create_variable_for_type_inference(dtype="int32")]
    helper.append_op(
        type="moe_ffn",
        inputs={"X": [x], "RouterW": [router_w],
                "RouterBias": [router_bias], "W1": [w1], "W3": [w3],
                "W2": [w2]},
        outputs=outputs, attrs=attrs)
    if zero_experts:
        return out, counts, outputs["ZeroCount"][0]
    return out, counts


def gated_short_conv(x, conv_w, name=None):
    """``C * conv(B * x)`` of ``x`` [N, T, 3*C] = ``B ‖ C ‖ x``: a
    depthwise causal convolution over time with the taps ``conv_w``
    [K, C] (tap K-1 on the current row), zeros before the sequence.
    -> [N, T, C]."""
    return _one_out("gated_short_conv", {"X": [x], "ConvW": [conv_w]}, {},
                    x.dtype, name)


def _mla_attrs(num_heads, nope_dim, rope_dim, v_dim):
    return {"num_heads": int(num_heads), "nope_dim": int(nope_dim),
            "rope_dim": int(rope_dim), "v_dim": int(v_dim)}


def mla_window_attention(q, rows, wkvb, qpos, num_heads, nope_dim,
                         rope_dim, v_dim, name=None):
    """Up-projected latent attention of ``q`` [N, T, heads*(nope+rope)]
    over the latent ``rows`` [N, S, W]; key j visible to query i iff
    j <= qpos[i]. -> [N, T, heads*v_dim]."""
    return _one_out(
        "mla_window_attention",
        {"Q": [q], "Rows": [rows], "Wkvb": [wkvb], "QPos": [qpos]},
        _mla_attrs(num_heads, nope_dim, rope_dim, v_dim), q.dtype, name)


def mla_decode_paged_attention(q, pool, tables, lengths, wkvb, num_heads,
                               nope_dim, rope_dim, v_dim, interpret=False,
                               name=None):
    """Absorbed latent attention of one query a slot (``q`` [slots, 1,
    heads*(nope+rope)]) against the paged latent ``pool`` through
    ``tables`` up to ``lengths`` live keys. -> [slots, 1, heads*v_dim]."""
    return _one_out(
        "mla_decode_paged_attention",
        {"Q": [q], "Pool": [pool], "Tables": [tables],
         "Lengths": [lengths], "Wkvb": [wkvb]},
        dict(_mla_attrs(num_heads, nope_dim, rope_dim, v_dim),
             interpret=bool(interpret)), q.dtype, name)


def _kda(op_type, qkv, f, b, conv_w, a_log, dt_bias, num_heads, head_dim,
         state, feeds, attrs, name):
    """``state``: None or the layer's (S var, conv-tail var), both
    rewritten in place (the outputs alias the inputs)."""
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(dtype=qkv.dtype)
    inputs = {"QKV": [qkv], "F": [f], "B": [b], "ConvW": [conv_w],
              "ALog": [a_log], "DtBias": [dt_bias]}
    outputs = {"Out": [out]}
    if state is not None:
        inputs.update(State=[state[0]], Conv=[state[1]],
                      **{k: [v] for k, v in feeds.items()})
        outputs.update(StateOut=[state[0]], ConvOut=[state[1]])
    helper.append_op(type=op_type, inputs=inputs, outputs=outputs,
                     attrs=dict(attrs, num_heads=int(num_heads),
                                head_dim=int(head_dim)))
    return out


def kda_window(qkv, f, b, conv_w, a_log, dt_bias, num_heads, head_dim,
               state=None, row=None, start=None, length=None, name=None):
    """The gated delta-rule mixing of a window (chunked): ``qkv``
    [N, T, 3*H*D] before the short convolution, ``f`` [N, T, H*D] and
    ``b`` [N, T, H] the decay and write pre-activations. With ``state``
    (S var, conv-tail var; N = 1) the window continues the slot's fed
    ``row`` unless ``start`` is 0, stops changing the state at ``length``
    real tokens, and rewrites the row. -> [N, T, H*D]."""
    return _kda("kda_window", qkv, f, b, conv_w, a_log, dt_bias, num_heads,
                head_dim, state,
                {"Row": row, "Start": start, "Length": length}, {}, name)


def kda_step(qkv, f, b, conv_w, a_log, dt_bias, num_heads, head_dim, state,
             rows, interpret=False, name=None):
    """The gated delta-rule mixing of one token a slot: slot i reads and
    rewrites row ``rows[i]`` of ``state`` (S var, conv-tail var).
    -> [slots, 1, H*D]."""
    return _kda("kda_step", qkv, f, b, conv_w, a_log, dt_bias, num_heads,
                head_dim, state, {"Row": rows},
                {"interpret": bool(interpret)}, name)


def gqa_window_attention(q, k, v, qpos, num_kv_heads, head_dim, name=None):
    """Grouped-query causal softmax attention of ``q`` [N, T, heads*D]
    over ``k``/``v`` [N, S, kv_heads*D]; key j visible to query i iff
    j <= qpos[i]. -> [N, T, heads*D]."""
    return _one_out(
        "gqa_window_attention",
        {"Q": [q], "K": [k], "V": [v], "QPos": [qpos]},
        {"num_kv_heads": int(num_kv_heads), "head_dim": int(head_dim)},
        q.dtype, name)
