"""Decoder-only model of the ``deepseek_v3`` family: a pre-norm block of
multi-head LATENT attention (MLA, ``q_lora_rank: null``) and a gated SiLU
feed-forward that is dense in the leading layers and a routed-expert
layer with shared experts after them. Inference and serving only.

    h = x + MLA(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))

a final RMSNorm, an untied head without bias, rotary positions on a
64-wide part of every query head and on ONE key row shared by all heads.

The cache is the latent one: a token keeps, a layer, its normed latent
``c`` (``kv_lora_rank`` wide) and its rotated rope key, in one pool row
padded to a multiple of 128 lanes so that the T = 1 kernel
(``kernels/flash_attention.py::mla_decode_paged_attention``) takes the
pool as it lies. Attention has two forms that agree (``mla_attention``):
up-projected (``c Wkvb`` gives every head's key and value) for a window
of queries, absorbed (``Wkvb`` folded into the query and applied to the
weighted sum of latents) for the one query a slot of the T = 1 step.

Parameters are created in ``cfg.dtype`` (bfloat16 as published); matmuls
take operands in that dtype and accumulate in float32, the residual
stream stays in ``cfg.dtype`` between blocks, norms, softmax, the router
and the logits are float32.

The module answers ``serving/decode.py``'s questions under the names
``models/gpt.py`` answers them (``cache_kinds``, ``build_paged_window``,
``build_paged_step``, ``build_paged_block_copy``, ``UNSUPPORTED``).
"""

import paddle_tpu.fluid as fluid

from . import cache_kinds as _kinds
from . import decoder_common as _dc
from .decoder_common import (gated_mlp as _gated_mlp, linear as _linear,
                             norm as _norm, param as _param,
                             programs as _programs)

CONFIG_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
    "q_lora_rank", "intermediate_size", "moe_intermediate_size",
    "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
    "first_k_dense_replace", "routed_scaling_factor", "rope_theta",
    "rope_interleave", "rms_norm_eps", "max_position_embeddings",
)


class DeepseekConfig(object):
    """The keys of a ``deepseek_v3`` ``config.json`` this builder reads
    (defaults: ``kakaocorp/kanana-2-30b-a3b-instruct-2601``), plus the
    serving knobs ``dtype`` and ``flash_interpret`` (tests: the T = 1
    kernel under the Pallas interpreter)."""

    def __init__(self, vocab_size=128256, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=32,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 kv_lora_rank=512, q_lora_rank=None, intermediate_size=6144,
                 moe_intermediate_size=768, n_routed_experts=128,
                 num_experts_per_tok=6, n_shared_experts=2,
                 first_k_dense_replace=1, routed_scaling_factor=2.448,
                 rope_theta=1e6, rope_interleave=True, rms_norm_eps=1e-6,
                 max_position_embeddings=32768, dtype="bfloat16",
                 flash_interpret=False):
        for key in CONFIG_KEYS:
            setattr(self, key, locals()[key])
        self.dtype = dtype
        self.flash_interpret = flash_interpret
        self.is_test = True
        # every routed expert lies here (``decoder_common.expert_layer``)
        self.experts_held, self.expert_offset = n_routed_experts, 0
        # no scale on the query or on the normed latent (``mla_attention``)
        self.q_lora_scale = self.kv_lora_scale = 1.0

    @classmethod
    def from_config(cls, config, **kw):
        """From a ``config.json`` dict; keys this builder does not read
        are passed over."""
        return cls(**dict({k: config[k] for k in CONFIG_KEYS
                           if k in config}, **kw))

    @classmethod
    def tiny(cls, **kw):
        """Toy widths: 4 heads of 24 = 16 + 8, latent 32, 8 experts top 2,
        one shared, one dense and two expert layers."""
        base = dict(vocab_size=211, hidden_size=64, num_hidden_layers=3,
                    num_attention_heads=4, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                    intermediate_size=128, moe_intermediate_size=32,
                    n_routed_experts=8, num_experts_per_tok=2,
                    n_shared_experts=1, first_k_dense_replace=1,
                    routed_scaling_factor=2.448, rope_theta=1e4,
                    max_position_embeddings=64, dtype="float32")
        return cls(**dict(base, **kw))

    @property
    def latent_row(self):
        return latent_row(self)


def latent_row(cfg):
    """Lanes of a token's pool row: latent, rope key, zeros up to a
    multiple of 128 (a narrower row would be copied whole into a padded
    layout before every kernel call)."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def cache_kinds(cfg):
    """Per layer ONE pool: a token's row is ``[1, latent_row]`` of
    ``cfg.dtype`` (normed latent ‖ rotated rope key ‖ zeros)."""
    return [(_kinds.CachePool("ds_paged_latent_%d" % i,
                              [1, cfg.latent_row], cfg.dtype),)
            for i in range(cfg.num_hidden_layers)]


# modes of ``serving/decode.py`` that are not built for a latent cache;
# the engine raises NotImplementedError naming the mode
UNSUPPORTED = {
    "spec_tokens": "speculative step widths > 1",
    "tp": "tensor-parallel serving (tp > 1)",
    "kv_host_tier": "the host KV tier (kv_tier_host_mb)",
}


def _times(x, factor, cfg):
    """``factor * x`` with the product taken in float32 (in bfloat16 the
    factor itself would be rounded: 3.464 to 3.469); no op for 1."""
    if factor == 1.0:
        return x
    return fluid.layers.cast(fluid.layers.scale(
        fluid.layers.cast(x, "float32"), scale=factor), cfg.dtype)


def mla_attention(x, pos, cfg, name, cache=None):
    """Latent attention on ``x`` [N, T, hidden] at the fed positions
    ``pos`` [N, T, 1]. Without a cache, and for a prefill window
    (``cache["mode"] == "paged_window"``), the UP-PROJECTED form over the
    latent rows (the window's own, or the slot's whole row gathered
    through its table after the window's rows were written); for the
    T = 1 step (``"paged_step"``) the ABSORBED form against the pool.

    ``cfg.q_lora_rank``: None, ``q = x Wq``; a rank, the query LoRA ``q =
    RMSNorm(x Wqa) Wqb``. ``cfg.q_lora_scale`` multiplies the query,
    ``cfg.kv_lora_scale`` the normed latent (1: no op); the pool keeps the
    SCALED latent, so both forms read one row."""
    heads = cfg.num_attention_heads
    nope, rope, vdim = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim)
    latent = cfg.kv_lora_rank
    rot = dict(rope_dim=rope, theta=cfg.rope_theta,
               interleaved=cfg.rope_interleave)
    if cfg.q_lora_rank is None:
        q = _linear(x, heads * (nope + rope), name + "_q")
    else:
        q = _linear(_norm(_linear(x, cfg.q_lora_rank, name + "_qa"), cfg,
                          name + "_q_norm"),
                    heads * (nope + rope), name + "_qb")
    q = fluid.layers.rotary_embedding(
        _times(q, cfg.q_lora_scale, cfg), pos, head_dim=nope + rope, **rot)
    kva = fluid.layers.rotary_embedding(
        _linear(x, latent + rope, name + "_kva"), pos,
        head_dim=latent + rope, **rot)
    c = _norm(fluid.layers.slice(kva, axes=[2], starts=[0], ends=[latent]),
              cfg, name + "_kv_norm")
    c = _times(c, cfg.kv_lora_scale, cfg)
    k_rope = fluid.layers.slice(kva, axes=[2], starts=[latent],
                                ends=[latent + rope])
    rows = fluid.layers.pad(
        fluid.layers.concat([c, k_rope], axis=2),
        paddings=[0, 0, 0, 0, 0, cfg.latent_row - latent - rope])
    wkvb = _param(name + "_kvb.w_0", [latent, heads * (nope + vdim)], cfg)
    dims = dict(num_heads=heads, nope_dim=nope, rope_dim=rope, v_dim=vdim)
    if cache is None:
        ctxt = fluid.layers.mla_window_attention(q, rows, wkvb, pos, **dims)
    else:
        pool = fluid.layers.kv_cache_write_paged(
            cache["pool"], fluid.layers.unsqueeze(rows, axes=[1]),
            cache["tables"], cache["pos"])
        if cache["mode"] == "paged_window":
            row = fluid.layers.reshape(
                fluid.layers.kv_cache_gather_paged(pool, cache["tables"]),
                shape=[0, -1, cfg.latent_row])
            ctxt = fluid.layers.mla_window_attention(
                q, row, wkvb, pos, **dims)
        else:
            ctxt = fluid.layers.mla_decode_paged_attention(
                q, pool, cache["tables"], cache["lengths"], wkvb,
                interpret=cfg.flash_interpret, **dims)
    return _linear(ctxt, cfg.hidden_size, name + "_o")


def decoder(ids, pos, cfg, cache=None):
    """[N, T, 1] ids at positions ``pos`` [N, T, 1] -> (hidden [N, T, H]
    before the final norm, [per expert layer: counts])."""
    h = fluid.layers.embedding(
        input=ids, size=[cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
        param_attr=fluid.ParamAttr(name="ds_embed"))
    counts = []
    for i in range(cfg.num_hidden_layers):
        name = "ds_%d" % i
        cache_i = cache and dict(cache, pool=cache["pools"][i][0])
        attn = mla_attention(_norm(h, cfg, name + "_ln1"), pos, cfg,
                             name + "_att", cache=cache_i)
        h = fluid.layers.elementwise_add(h, attn)
        x = _norm(h, cfg, name + "_ln2")
        if i < cfg.first_k_dense_replace:
            ff = _gated_mlp(x, cfg.intermediate_size, cfg.hidden_size,
                            name + "_ffn")
        else:
            ff, c = _dc.expert_layer(x, cfg, name + "_moe")
            counts.append(c)
        h = fluid.layers.elementwise_add(h, ff)
    return h, counts


def lm_head(h, cfg):
    """Final RMSNorm and the untied head: float32 logits."""
    return _dc.lm_head(h, cfg, "ds")


def build_deepseek_infer(cfg, seq_len):
    """Whole-prompt inference graph (the export): feeds ``ids``,
    ``pos_ids`` [N, seq_len, 1] -> logits [N, seq_len, vocab] float32.
    Returns (main, startup, feed names, logits)."""
    main, startup = _programs()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[seq_len, 1],
                                dtype="int64")
        pos_ids = fluid.layers.data(name="pos_ids", shape=[seq_len, 1],
                                    dtype="int64")
        h, _counts = decoder(ids, pos_ids, cfg)
        logits = lm_head(h, cfg)
    return main, startup, ["ids", "pos_ids"], logits


def build_deepseek_paged_window(cfg, blocks, block, max_blocks, seq_len,
                                slots=None):
    """Paged prefill-window graph, the contract of
    ``gpt.build_gpt_paged_window`` without the fed bias: ONE prompt window
    lands through the slot's fed ``table`` at ``window_pos``, and its
    queries attend (up-projected) over the slot's gathered latent row
    under the causal mask the fed ``pos_ids`` give. The last real token's
    hidden row is picked (``last_onehot``) BEFORE the head, so the head
    runs on one row. Returns (main, startup, feed names, next_logits
    [1, vocab])."""
    main, startup = _programs(donate=True)
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[seq_len, 1],
                                dtype="int64")
        pos_ids = fluid.layers.data(name="pos_ids", shape=[seq_len, 1],
                                    dtype="int64")
        table = fluid.layers.data(name="table", shape=[max_blocks],
                                  dtype="int64")
        window_pos = fluid.layers.data(name="window_pos", shape=[1],
                                       dtype="int64")
        last_onehot = fluid.layers.data(
            name="last_onehot", shape=[seq_len, 1], dtype="float32")
        cache = {"mode": "paged_window", "tables": table, "pos": window_pos,
                 "pools": _kinds.declare_pools(cache_kinds(cfg), blocks,
                                               block)}
        h, _counts = decoder(ids, pos_ids, cfg, cache=cache)
        next_logits = _dc.last_row_logits(h, last_onehot, cfg, "ds")
    return (main, startup,
            ["ids", "pos_ids", "table", "window_pos", "last_onehot"],
            next_logits)


def build_deepseek_paged_step(cfg, slots, blocks, block, max_blocks,
                              step_w=1):
    """The fused T = 1 step: every slot's newest token lands at
    ``step_pos`` through its ``tables`` row and attends (absorbed) over
    its ``step_pos + 1`` live latent rows; an inactive slot feeds token 0
    at position 0 of an all-sink table and reads one sink row. The
    program also gives, per expert layer, the assignments each expert
    received (``main._step_stats``: fetched beside the logits, read by
    ``step_stats``). Returns (main, startup, feed names, step_logits
    [slots, vocab])."""
    if step_w != 1:
        raise NotImplementedError(
            "latent cache: " + UNSUPPORTED["spec_tokens"])
    main, startup = _programs(donate=True)
    with fluid.program_guard(main, startup):
        step_ids = fluid.layers.data(name="step_ids", shape=[1, 1],
                                     dtype="int64")
        step_pos = fluid.layers.data(name="step_pos", shape=[1, 1],
                                     dtype="int64")
        tables = fluid.layers.data(name="tables", shape=[max_blocks],
                                   dtype="int64")
        write_pos = fluid.layers.reshape(step_pos, shape=[-1])
        lengths = fluid.layers.scale(write_pos, bias=1.0)
        cache = {"mode": "paged_step", "tables": tables, "pos": write_pos,
                 "lengths": lengths,
                 "pools": _kinds.declare_pools(cache_kinds(cfg), blocks,
                                               block)}
        h, counts = decoder(step_ids, step_pos, cfg, cache=cache)
        step_logits = fluid.layers.reshape(lm_head(h, cfg),
                                           shape=[-1, cfg.vocab_size])
        main._step_stats = ([fluid.layers.stack(counts, axis=0).name]
                            if counts else [])
    return main, startup, ["step_ids", "step_pos", "tables"], step_logits


def build_deepseek_paged_block_copy(cfg, blocks, block, npairs):
    """ONE compiled pool-internal block copy across every layer's latent
    pool (copy-on-write), as ``gpt.build_gpt_paged_block_copy``."""
    main, startup = _programs(donate=True)
    with fluid.program_guard(main, startup):
        src = fluid.layers.data(name="src", shape=[npairs], dtype="int64")
        dst = fluid.layers.data(name="dst", shape=[npairs], dtype="int64")
        for (pool,) in _kinds.declare_pools(cache_kinds(cfg), blocks, block):
            fluid.layers.kv_cache_block_copy(pool, src, dst)
        ok = fluid.layers.fill_constant(shape=[1], dtype="int32", value=1)
    return main, startup, ["src", "dst"], ok


def step_stats(fetched, live_rows, **_unused):
    """What one T = 1 step's expert counts say, for the
    ``decode_paged_step`` span and ``/metrics``: ``fetched`` is
    ``main._step_stats`` as fetched ([expert layers, experts] int32)."""
    out = {"latent_rows_live": int(live_rows)}
    if fetched:
        out.update(_dc.expert_step_stats(fetched[0]))
    return out


build_infer = build_deepseek_infer
build_paged_window = build_deepseek_paged_window
build_paged_step = build_deepseek_paged_step
build_paged_block_copy = build_deepseek_paged_block_copy
