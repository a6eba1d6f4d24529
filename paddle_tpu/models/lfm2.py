"""Decoder-only model of the ``lfm2_moe`` family (``LiquidAI/LFM2-8B-A1B``):
a pre-norm block whose operator is, by ``layer_types``, a gated short
convolution or grouped-query softmax attention with QK-norm and rotary
positions, and whose feed-forward is a gated SiLU MLP in the first
``num_dense_layers`` blocks and a routed-expert layer without a shared
expert in the others. TRAINING: the program is built with its loss and
optimizer, as ``models/gpt.py::build_gpt_lm_train`` builds GPT's.

    h = h + Op(RMSNorm(h));  h = h + FFN(RMSNorm(h))

    conv:       B, C, x = split3(u W_in);  Op = (C * conv3(B * x)) W_out
    attention:  q, k = RMSNorm_head(u Wq), RMSNorm_head(u Wk), rotated
                (half-split form, all of the head) ; causal softmax of
                q k^T / sqrt(d), a key head shared by heads / kv_heads
                query heads; Op = concat(heads) Wo
    experts:    ``decoder_common.routed_experts``: sigmoid scores, the
                top k of score + bias, gates the chosen scores over their
                sum + 1e-6, the experts HELD here

a final RMSNorm, and logits through the token embedding's transpose. No
bias anywhere. The loss is the mean next-token cross entropy in float32.

Parameters are created in ``cfg.dtype`` (float32: the master weights of
a trained model); under ``mixed_precision.decorate`` the matmuls, the
grouped products and the flash kernels take bfloat16 operands and
accumulate in float32, the residual stream is bfloat16 between blocks,
and norms' statistics, rotary angles, the convolution, the router and
the logits are float32. The router's bias chooses and does not weigh; it
is a buffer (``trainable=False``) that no optimizer touches.

Grouped queries reach the training flash kernels with each key head
repeated for its group (``fluid/ops/nn_ops.py::_repeat_key_heads``): at
the published widths K and V of one attention layer grow from 8 to 32
heads, 2 x 33.5 MB a step at seq 4096 batch 2 in bfloat16, and the
kernels read a key head 4 times; dK/dV add up over the group.
"""

import paddle_tpu.fluid as fluid

from . import decoder_common as _dc
from .decoder_common import linear as _linear, norm as _norm, param as _param

CONFIG_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "layer_types", "num_dense_layers", "num_attention_heads",
    "num_key_value_heads", "conv_L_cache", "conv_bias",
    "moe_intermediate_size", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "use_expert_bias", "routed_scaling_factor", "norm_eps",
    "rope_theta", "max_position_embeddings",
)
# the pattern of ``LiquidAI/LFM2-8B-A1B``: 18 conv + 6 attention layers
LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


class LFM2Config(object):
    """The keys of an ``lfm2_moe`` ``config.json`` (defaults:
    ``LiquidAI/LFM2-8B-A1B``), the share of the experts held here
    (``experts_held`` of ``num_experts``, from ``expert_offset``), and the
    builder's knobs: ``dtype`` of the parameters and ``flash_interpret``
    (tests: the flash kernels under the Pallas interpreter)."""

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 intermediate_size=7168, num_hidden_layers=24,
                 layer_types=None, num_dense_layers=2,
                 num_attention_heads=32, num_key_value_heads=8,
                 conv_L_cache=3, conv_bias=False, moe_intermediate_size=1792,
                 num_experts=32, num_experts_per_tok=4, norm_topk_prob=True,
                 use_expert_bias=True, routed_scaling_factor=1.0,
                 norm_eps=1e-5, rope_theta=1000000.0,
                 max_position_embeddings=128000, experts_held=None,
                 expert_offset=0, dtype="float32", flash_interpret=False):
        if conv_bias:
            raise NotImplementedError("lfm2: conv_bias true")
        if layer_types is None:
            layer_types = LAYER_TYPES
        layer_types = tuple(layer_types)[:num_hidden_layers]
        if len(layer_types) != num_hidden_layers:
            raise ValueError("lfm2: %d layer_types for %d layers"
                             % (len(layer_types), num_hidden_layers))
        for key in CONFIG_KEYS:
            setattr(self, key, locals()[key])
        self.head_dim = hidden_size // num_attention_heads
        self.experts_held = (num_experts if experts_held is None
                             else experts_held)
        self.expert_offset = expert_offset
        self.dtype = dtype
        self.flash_interpret = flash_interpret
        # the names ``decoder_common`` reads
        self.rms_norm_eps = norm_eps
        self.n_routed_experts = num_experts
        self.n_shared_experts = 0
        self.router_norm_eps = 1e-6     # the family's, under the gates' sum
        # use_expert_bias: the bias chooses; it is a buffer, never trained
        self.router_bias_trainable = False

    @classmethod
    def from_config(cls, config, **kw):
        """From a configuration dict. Where it is a chip's share of a
        deployment (``published`` beside ``reduced`` keys), its
        ``num_experts`` counts the experts HELD, from ``expert_offset``,
        and the router keeps the published width; ``layers_kept`` names
        the published layers a cut in depth keeps."""
        keys = {k: config[k] for k in CONFIG_KEYS if k in config}
        width = config.get("published", {}).get("num_experts")
        if width is not None:
            keys.update(experts_held=keys["num_experts"], num_experts=width,
                        expert_offset=config.get("expert_offset", 0))
        kept = config.get("layers_kept")
        if kept is not None:
            # a cut in depth keeps these of the published layers: the
            # pattern and the count of leading dense layers follow it
            keys["layer_types"] = [config["layer_types"][i] for i in kept]
            keys["num_dense_layers"] = sum(
                i < config["num_dense_layers"] for i in kept)
        return cls(**dict(keys, **kw))

    @classmethod
    def tiny(cls, **kw):
        """Toy widths: one dense block and one whole period (attention,
        conv, conv, conv), 4 query heads on 2 key heads of 8, 8 experts
        top 2."""
        base = dict(vocab_size=211, hidden_size=32, intermediate_size=48,
                    num_hidden_layers=5, num_dense_layers=1,
                    layer_types=("conv", "full_attention", "conv", "conv",
                                 "conv"),
                    num_attention_heads=4, num_key_value_heads=2,
                    moe_intermediate_size=16, num_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=64)
        return cls(**dict(base, **kw))


def _conv_operator(u, cfg, name):
    """(C * conv(B * x)) W_out with B, C, x = split3(u W_in)."""
    width = cfg.hidden_size
    mixed = fluid.layers.gated_short_conv(
        _linear(u, 3 * width, name + "_in"),
        _param(name + "_conv", [cfg.conv_L_cache, width], cfg))
    return _linear(mixed, width, name + "_out")


def _attention_operator(u, pos, cfg, name):
    """Grouped-query causal attention: per-head RMSNorm of q and k, then
    rotary positions on the whole head, then the flash kernels."""
    heads, kv_heads, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.head_dim)

    def split(x, n):
        x = fluid.layers.reshape(x, shape=[0, 0, n, d])
        return fluid.layers.transpose(x, perm=[0, 2, 1, 3])

    def normed_rotated(x, n, gain):
        x = fluid.layers.rms_norm(
            fluid.layers.reshape(x, shape=[0, 0, n, d]),
            _param(gain, [d], cfg, value=1.0), epsilon=cfg.norm_eps)
        x = fluid.layers.rotary_embedding(
            fluid.layers.reshape(x, shape=[0, 0, n * d]), pos, head_dim=d,
            rope_dim=d, theta=cfg.rope_theta)
        return split(x, n)

    q = normed_rotated(_linear(u, heads * d, name + "_q"), heads,
                       name + "_q_norm")
    k = normed_rotated(_linear(u, kv_heads * d, name + "_k"), kv_heads,
                       name + "_k_norm")
    v = split(_linear(u, kv_heads * d, name + "_v"), kv_heads)
    ctx = fluid.layers.flash_attention(
        q, k, v, causal=True, interpret=cfg.flash_interpret)
    ctx = fluid.layers.reshape(
        fluid.layers.transpose(ctx, perm=[0, 2, 1, 3]),
        shape=[0, 0, heads * d])
    return _linear(ctx, cfg.hidden_size, name + "_o")


def lfm2_decoder(ids, pos_ids, cfg):
    """ids, pos_ids [N, T, 1] -> (hidden [N, T, H], the embedding
    parameter, per expert layer its counts int32 [experts_held])."""
    h = fluid.layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
        param_attr=fluid.ParamAttr(name="lfm2_embed"))
    embed = h.block.program.global_block().var("lfm2_embed")
    counts = []
    for i, kind in enumerate(cfg.layer_types):
        name = "lfm2_%d" % i
        u = _norm(h, cfg, name + "_op_norm")
        if kind == "full_attention":
            mixed = _attention_operator(u, pos_ids, cfg, name + "_att")
        elif kind == "conv":
            mixed = _conv_operator(u, cfg, name + "_conv")
        else:
            raise ValueError("lfm2: layer type %r" % (kind,))
        h = fluid.layers.elementwise_add(h, mixed)
        v = _norm(h, cfg, name + "_ffn_norm")
        if i < cfg.num_dense_layers:
            ffn = _dc.gated_mlp(v, cfg.intermediate_size, cfg.hidden_size,
                                name + "_mlp")
        else:
            ffn, held, _zero = _dc.routed_experts(v, cfg, name + "_moe")
            counts.append(held)
        h = fluid.layers.elementwise_add(h, ffn)
    return h, embed, counts


def tied_logits(h, embed, cfg):
    """Final RMSNorm (``lfm2_norm``), then logits through the embedding's
    transpose, float32."""
    return _dc.float32_logits(
        _norm(h, cfg, "lfm2_norm"),
        fluid.layers.transpose(embed, perm=[1, 0]), "lfm2_head")


def build_lfm2_train(cfg, seq_len, learning_rate=3e-4, use_amp=False):
    """Next-token LM training graph: positions t predict tokens t + 1,
    every position real. Returns (main, startup, feeds, avg_loss, counts):
    ``counts`` int32 [expert layers, experts_held], the assignments each
    held expert received this step, to fetch with the loss."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[seq_len, 1],
                                dtype="int64")
        pos_ids = fluid.layers.data(name="pos_ids", shape=[seq_len, 1],
                                    dtype="int64")
        h, embed, counts = lfm2_decoder(ids, pos_ids, cfg)
        logits = tied_logits(h, embed, cfg)
        # shift: logits[:, :-1] predict ids[:, 1:]
        pred = fluid.layers.slice(logits, axes=[1], starts=[0],
                                  ends=[seq_len - 1])
        tgt = fluid.layers.slice(ids, axes=[1], starts=[1], ends=[seq_len])
        avg_loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(pred, tgt))
        counts = fluid.layers.stack(counts, axis=0)
        opt = fluid.optimizer.Adam(learning_rate=learning_rate)
        if use_amp:
            from paddle_tpu.fluid.contrib import mixed_precision as _mp

            opt = _mp.decorate(opt)
        opt.minimize(avg_loss)
    return main, startup, [ids, pos_ids], avg_loss, counts


def run_train_step(exe, program, feed, loss, counts, scope=None):
    """One step as ONE ``Executor.run`` under a ``train_step`` span: the
    loss and the held experts' counts are fetched together, and what the
    counts say is bumped and noted on the span
    (``decoder_common.expert_train_stats``). -> (loss, counts)"""
    import numpy as np

    from paddle_tpu.observability import trace

    with trace.span("train_step", cat="train") as span:
        loss_value, held = exe.run(program, feed=feed,
                                   fetch_list=[loss, counts], scope=scope)
        held = np.asarray(held)
        _dc.expert_train_stats(held, span)
    return float(np.asarray(loss_value).reshape(-1)[0]), held
