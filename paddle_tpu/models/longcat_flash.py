"""Decoder-only language model of the ``LongCat-Flash`` family (the
language model of ``meituan-longcat/LongCat-Flash-Omni``; its audio and
vision encoders and its codec decoder are not built): a DOUBLE layer of
two latent attentions and two dense gated-SiLU feed-forwards, with a
routed-expert branch that leaves the residual stream after the first
attention and rejoins it after the second feed-forward (the shortcut).
Inference and serving only.

    a1 = x  + MLA_0(RMSNorm(x));     u1 = RMSNorm(a1)
    s  = MoE(u1)                                     # the shortcut branch
    b1 = a1 + FFN_0(u1)
    a2 = b1 + MLA_1(RMSNorm(b1))
    y  = a2 + FFN_1(RMSNorm(a2)) + s

a final RMSNorm and an untied head without bias. ``MoE`` routes every
token over ``n_routed_experts + zero_expert_num`` outputs (float32
softmax scores, top ``moe_topk`` of score + bias, gates NOT renormalised,
times ``routed_scaling_factor``): the first are experts with weights, of
which ``experts_held`` lie here, the last are IDENTITY experts that add
``gate * u1`` and cost nothing; there is no shared expert. ``MLA`` is
``models/deepseek.py::mla_attention`` with the query LoRA and the two
LoRA scales (``sqrt(hidden / rank)`` on the query and on the normed
latent).

The cache is two latent pools a double layer under ONE block table:
``cache_kinds`` gives ``2 * num_layers`` entries of one pool each, entry
``2l + j`` attention ``j`` of double layer ``l``; a token's row is
deepseek's (scaled normed latent ‖ rotated rope key ‖ zeros, a multiple
of 128 lanes).

Dtypes as ``models/deepseek.py``. The module answers
``serving/decode.py``'s questions under the names ``models/deepseek.py``
answers them.
"""

import math

import paddle_tpu.fluid as fluid

from . import cache_kinds as _kinds
from . import decoder_common as _dc
from . import deepseek as _ds
from .decoder_common import gated_mlp as _gated_mlp, norm as _norm

CONFIG_KEYS = (
    "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
    "num_layers", "num_attention_heads", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim",
    "mla_scale_q_lora", "mla_scale_kv_lora", "routed_scaling_factor",
    "n_routed_experts", "zero_expert_num", "moe_topk", "rope_theta",
    "rms_norm_eps", "max_position_embeddings",
)


class LongcatFlashConfig(object):
    """The keys of a ``LongCat-Flash`` ``config.json`` this builder reads
    (defaults: ``meituan-longcat/LongCat-Flash-Omni``), the share of the
    experts held here (``experts_held`` of ``n_routed_experts``, from
    ``expert_offset``), and the serving knobs ``dtype`` and
    ``flash_interpret`` (tests: the T = 1 kernel under the Pallas
    interpreter). The other names are those ``deepseek.mla_attention``
    and ``decoder_common.routed_experts`` read."""

    router_scoring = "softmax"
    norm_topk_prob = False
    rope_interleave = True

    def __init__(self, vocab_size=131072, hidden_size=6144,
                 ffn_hidden_size=12288, expert_ffn_hidden_size=2048,
                 num_layers=28, num_attention_heads=64, kv_lora_rank=512,
                 q_lora_rank=1536, qk_rope_head_dim=64, v_head_dim=128,
                 qk_nope_head_dim=128, mla_scale_q_lora=True,
                 mla_scale_kv_lora=True, routed_scaling_factor=6.0,
                 n_routed_experts=512, zero_expert_num=256, moe_topk=12,
                 rope_theta=1e7, rms_norm_eps=1e-5,
                 max_position_embeddings=131072, experts_held=None,
                 expert_offset=0, dtype="bfloat16", flash_interpret=False):
        for key in CONFIG_KEYS:
            setattr(self, key, locals()[key])
        self.q_lora_scale = (math.sqrt(hidden_size / q_lora_rank)
                             if mla_scale_q_lora else 1.0)
        self.kv_lora_scale = (math.sqrt(hidden_size / kv_lora_rank)
                              if mla_scale_kv_lora else 1.0)
        self.moe_intermediate_size = expert_ffn_hidden_size
        self.num_experts_per_tok = moe_topk
        self.zero_experts = zero_expert_num
        self.experts_held = (n_routed_experts if experts_held is None
                             else experts_held)
        self.expert_offset = expert_offset
        self.dtype = dtype
        self.flash_interpret = flash_interpret
        self.is_test = True

    @classmethod
    def from_config(cls, config, **kw):
        """From a configuration dict. Where it is a chip's share of a
        deployment (``published`` beside ``reduced`` keys), its
        ``n_routed_experts`` counts the experts HELD, from
        ``expert_offset``, and the router keeps the published width."""
        keys = {k: config[k] for k in CONFIG_KEYS if k in config}
        width = config.get("published", {}).get("n_routed_experts")
        if width is not None:
            keys.update(experts_held=keys["n_routed_experts"],
                        n_routed_experts=width,
                        expert_offset=config.get("expert_offset", 0))
        return cls(**dict(keys, **kw))

    @classmethod
    def tiny(cls, **kw):
        """Toy widths: two double layers, 4 heads of 24 = 16 + 8, query
        rank 16 (scale 2), latent 32 (scale sqrt 2), 8 experts and 4
        identity experts, top 3."""
        base = dict(vocab_size=211, hidden_size=64, ffn_hidden_size=96,
                    expert_ffn_hidden_size=32, num_layers=2,
                    num_attention_heads=4, kv_lora_rank=32, q_lora_rank=16,
                    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
                    n_routed_experts=8, zero_expert_num=4, moe_topk=3,
                    rope_theta=1e4, max_position_embeddings=64,
                    dtype="float32")
        return cls(**dict(base, **kw))

    @property
    def latent_row(self):
        return _ds.latent_row(self)


def cache_kinds(cfg):
    """``2 * num_layers`` entries of ONE latent pool each (attention
    ``2l + j``): one block table addresses them all, and no entry is a
    (K, V) pair (``cache_kinds.kv_pools`` raises ``TypeError``)."""
    return [(_kinds.CachePool("lc_paged_latent_%d" % i,
                              [1, cfg.latent_row], cfg.dtype),)
            for i in range(2 * cfg.num_layers)]


# modes of ``serving/decode.py`` that are not built for two latent pools
# a layer; the engine raises NotImplementedError naming the mode
UNSUPPORTED = {
    "spec_tokens": "speculative step widths > 1",
    "tp": "tensor-parallel serving (tp > 1)",
    "kv_host_tier": "the host KV tier (kv_tier_host_mb)",
    "block_export": "block export and offer (a block is one latent row a "
                    "pool, not a (K, V) pair)",
}


def _attend(h, pos, cfg, name, index, cache):
    """h + attention ``index`` (``2l + j``) of the pre-normed ``h``."""
    pool = cache and dict(cache, pool=cache["pools"][index][0])
    j = index % 2
    return fluid.layers.elementwise_add(h, _ds.mla_attention(
        _norm(h, cfg, "%s_ln_att%d" % (name, j)), pos, cfg,
        "%s_att%d" % (name, j), cache=pool))


def decoder(ids, pos, cfg, cache=None):
    """[N, T, 1] ids at positions ``pos`` [N, T, 1] -> (hidden [N, T, H]
    before the final norm, [per double layer: the held experts' counts],
    [per double layer: the identity assignments])."""
    h = fluid.layers.embedding(
        input=ids, size=[cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
        param_attr=fluid.ParamAttr(name="lc_embed"))
    add = fluid.layers.elementwise_add
    width, hidden = cfg.ffn_hidden_size, cfg.hidden_size
    counts, zero_counts = [], []
    for i in range(cfg.num_layers):
        name = "lc_%d" % i
        h = _attend(h, pos, cfg, name, 2 * i, cache)
        x = _norm(h, cfg, name + "_ln_ffn0")
        # the branch: nothing below reads it before the last add, so the
        # compiler may run it beside the second attention and both FFNs
        shortcut, c, z = _dc.routed_experts(x, cfg, name + "_moe")
        counts.append(c)
        zero_counts.append(z)
        h = add(h, _gated_mlp(x, width, hidden, name + "_ffn0"))
        h = _attend(h, pos, cfg, name, 2 * i + 1, cache)
        x = _norm(h, cfg, name + "_ln_ffn1")
        h = add(add(h, _gated_mlp(x, width, hidden, name + "_ffn1")),
                shortcut)
    return h, counts, zero_counts


def build_infer(cfg, seq_len):
    """Whole-prompt inference graph (the export): feeds ``ids``,
    ``pos_ids`` [N, seq_len, 1] -> logits [N, seq_len, vocab] float32.
    Returns (main, startup, feed names, logits)."""
    main, startup = _dc.programs()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[seq_len, 1],
                                dtype="int64")
        pos_ids = fluid.layers.data(name="pos_ids", shape=[seq_len, 1],
                                    dtype="int64")
        h, _counts, _zeros = decoder(ids, pos_ids, cfg)
        logits = _dc.lm_head(h, cfg, "lc")
    return main, startup, ["ids", "pos_ids"], logits


def build_paged_window(cfg, blocks, block, max_blocks, seq_len, slots=None):
    """Paged prefill-window graph, the contract of
    ``deepseek.build_deepseek_paged_window``: ONE prompt window lands in
    all ``2 * num_layers`` pools through the slot's fed ``table`` at
    ``window_pos``. Returns (main, startup, feed names, next_logits
    [1, vocab])."""
    main, startup = _dc.programs(donate=True)
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
        h, _counts, _zeros = decoder(ids, pos_ids, cfg, cache=cache)
        next_logits = _dc.last_row_logits(h, last_onehot, cfg, "lc")
    return (main, startup,
            ["ids", "pos_ids", "table", "window_pos", "last_onehot"],
            next_logits)


def build_paged_step(cfg, slots, blocks, block, max_blocks, step_w=1):
    """The fused T = 1 step, the contract of
    ``deepseek.build_deepseek_paged_step`` over two pools a double layer.
    The program also gives, per double layer, the assignments each held
    expert received and those that went to identity experts
    (``main._step_stats``, read by ``step_stats``). Returns (main,
    startup, feed names, step_logits [slots, vocab])."""
    if step_w != 1:
        raise NotImplementedError(
            "longcat_flash: " + UNSUPPORTED["spec_tokens"])
    main, startup = _dc.programs(donate=True)
    with fluid.program_guard(main, startup):
        step_ids = fluid.layers.data(name="step_ids", shape=[1, 1],
                                     dtype="int64")
        step_pos = fluid.layers.data(name="step_pos", shape=[1, 1],
                                     dtype="int64")
        tables = fluid.layers.data(name="tables", shape=[max_blocks],
                                   dtype="int64")
        write_pos = fluid.layers.reshape(step_pos, shape=[-1])
        cache = {"mode": "paged_step", "tables": tables, "pos": write_pos,
                 "lengths": fluid.layers.scale(write_pos, bias=1.0),
                 "pools": _kinds.declare_pools(cache_kinds(cfg), blocks,
                                               block)}
        h, counts, zeros = decoder(step_ids, step_pos, cfg, cache=cache)
        step_logits = fluid.layers.reshape(_dc.lm_head(h, cfg, "lc"),
                                           shape=[-1, cfg.vocab_size])
        main._step_stats = [fluid.layers.stack(counts, axis=0).name,
                            fluid.layers.stack(zeros, axis=0).name]
    return main, startup, ["step_ids", "step_pos", "tables"], step_logits


def build_paged_block_copy(cfg, blocks, block, npairs):
    """ONE compiled pool-internal block copy across all ``2 *
    num_layers`` latent pools (copy-on-write)."""
    main, startup = _dc.programs(donate=True)
    with fluid.program_guard(main, startup):
        src = fluid.layers.data(name="src", shape=[npairs], dtype="int64")
        dst = fluid.layers.data(name="dst", shape=[npairs], dtype="int64")
        for (pool,) in _kinds.declare_pools(cache_kinds(cfg), blocks, block):
            fluid.layers.kv_cache_block_copy(pool, src, dst)
        ok = fluid.layers.fill_constant(shape=[1], dtype="int32", value=1)
    return main, startup, ["src", "dst"], ok


def step_stats(fetched, live_rows, **_unused):
    """What one T = 1 step did, for the ``decode_paged_step`` span and
    ``/metrics``: the held experts' counts and the identity assignments
    (``fetched``: ``main._step_stats`` as fetched, [double layers, experts
    held] and [double layers, 1] int32), and the live latent rows as the
    kernel reads them: a token's row in each of a double layer's two
    pools."""
    return dict(_dc.expert_step_stats(fetched[0], fetched[1]),
                latent_rows_live=2 * int(live_rows))
