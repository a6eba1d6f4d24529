"""Decoder-only model of the ``solar_open2`` family: a pre-norm block whose
mixing layer is, in a period of four (``gqa_layers``), one gated
grouped-query softmax layer WITHOUT positions and three gated delta-rule
layers (Kimi Delta Attention, arXiv:2510.26692), and whose feed-forward
is a routed-expert layer with a shared expert in every block. Inference
and serving only.

    h = x + Mix_l(RMSNorm(x));  y = h + MoE(RMSNorm(h))

a final RMSNorm and an untied head without bias. No rotary anywhere.

A sequence keeps two kinds of things (``cache_kinds``): a softmax layer a
K row and a V row a TOKEN, paged, the key heads side by side
(``num_key_value_heads * head_dim`` lanes); a delta-rule layer a state a
SLOT, ``S`` [heads, key, value] float32 and the last three rows of
``q~ ‖ k~ ‖ v~`` before the short convolution, whatever the length.

Parameters are created in ``cfg.dtype`` (bfloat16 as published; ``A_log``
and ``dt_bias`` float32); matmuls take operands in that dtype and
accumulate in float32, the residual stream stays in ``cfg.dtype`` between
blocks; norms, softmax, the router, the gates' sigmoids, everything of
the delta rule after the convolution's input, and the logits are float32.

The module answers ``serving/decode.py``'s questions under the names
``models/gpt.py`` and ``models/deepseek.py`` answer them.
"""

import math

import paddle_tpu.fluid as fluid

from . import cache_kinds as _kinds
from . import decoder_common as _dc
from .decoder_common import linear as _linear, norm as _norm, param as _param

CONFIG_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "linear_attn_config", "gqa_layers",
    "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "routed_scaling_factor", "first_k_dense_replace",
    "rms_norm_eps", "max_position_embeddings",
)


class SolarOpen2Config(object):
    """The keys of a ``solar_open2`` ``config.json`` this builder reads
    (defaults: ``upstage/Solar-Open2-250B``), the share of the experts
    held here (``experts_held`` of ``n_routed_experts``, from
    ``expert_offset``), and the serving knobs ``dtype`` and
    ``flash_interpret`` (tests: both T = 1 kernels under the Pallas
    interpreter)."""

    def __init__(self, vocab_size=196608, hidden_size=4096,
                 num_hidden_layers=48, num_attention_heads=64,
                 num_key_value_heads=8, head_dim=128,
                 linear_attn_config=None, gqa_layers=None,
                 moe_intermediate_size=1280, n_routed_experts=320,
                 n_shared_experts=1, num_experts_per_tok=8,
                 routed_scaling_factor=1.0, first_k_dense_replace=0,
                 rms_norm_eps=1e-5, max_position_embeddings=1048576,
                 experts_held=None, expert_offset=0, dtype="bfloat16",
                 flash_interpret=False):
        if first_k_dense_replace:
            raise NotImplementedError(
                "solar_open2: every layer is a routed-expert layer "
                "(first_k_dense_replace %r)" % (first_k_dense_replace,))
        kda = dict(linear_attn_config or {
            "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64})
        if kda.get("num_kv_heads") not in (None, kda["num_heads"]):
            raise NotImplementedError(
                "solar_open2: delta-rule layers with grouped key heads")
        if gqa_layers is None:
            gqa_layers = range(0, num_hidden_layers, 4)
        for key in CONFIG_KEYS:
            setattr(self, key, locals()[key])
        self.gqa_layers = tuple(i for i in gqa_layers
                                if i < num_hidden_layers)
        self.kda_heads, self.kda_head_dim = kda["num_heads"], kda["head_dim"]
        self.conv_taps = kda["short_conv_kernel_size"]
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
        """Toy widths: two periods (G K K K G K K K), 4 query heads on 2
        key heads of 16, 2 delta-rule heads of 16, 8 experts top 2, one
        shared."""
        base = dict(vocab_size=211, hidden_size=32, num_hidden_layers=8,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16,
                    linear_attn_config={"short_conv_kernel_size": 4,
                                        "head_dim": 16, "num_heads": 2},
                    moe_intermediate_size=16, n_routed_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=64,
                    dtype="float32")
        return cls(**dict(base, **kw))

    def is_gqa(self, layer):
        return layer in self.gqa_layers


def cache_kinds(cfg):
    """Per layer: a softmax layer its (K pool, V pool), a token's row the
    key heads side by side, ``[1, kv_heads * head_dim]`` of ``cfg.dtype``;
    a delta-rule layer its (``S`` state float32, convolution tail in
    ``cfg.dtype``), a row a slot."""
    row = [1, cfg.num_key_value_heads * cfg.head_dim]
    h, d = cfg.kda_heads, cfg.kda_head_dim
    return [
        (_kinds.CachePool("so2_paged_k_%d" % i, row, cfg.dtype),
         _kinds.CachePool("so2_paged_v_%d" % i, row, cfg.dtype))
        if cfg.is_gqa(i) else
        (_kinds.CacheState("so2_kda_state_%d" % i, [h, d, d], "float32"),
         _kinds.CacheState("so2_kda_conv_%d" % i,
                           [cfg.conv_taps - 1, 3 * h * d], cfg.dtype))
        for i in range(cfg.num_hidden_layers)]


# modes of ``serving/decode.py`` that are not built for a per-slot state:
# each would hand a slot K/V blocks and no state (snapshots of the state
# at block boundaries are later work); the engine raises
# NotImplementedError naming the mode where it would arm it
UNSUPPORTED = {
    "prefix_cache": "the prefix cache (prefix_cache_mb > 0): a hit gives "
                    "a slot K/V blocks and no recurrent state",
    "kv_host_tier": "the host KV tier (kv_tier_host_mb)",
    "tp": "tensor-parallel serving (tp > 1)",
    "spec_tokens": "speculative step widths > 1",
    "block_export": "block export and offer (a block carries no state)",
}


def _gated(o, gate, cfg):
    """o * sigmoid(gate), in float32, back in ``cfg.dtype``."""
    return fluid.layers.cast(fluid.layers.elementwise_mul(
        fluid.layers.cast(o, "float32"),
        fluid.layers.sigmoid(fluid.layers.cast(gate, "float32"))), cfg.dtype)


def kda_mix(x, cfg, name, cache=None):
    """The gated delta-rule layer on ``x`` [N, T, hidden]: chunked over a
    window (from zeros without a cache; from the slot's state row, unless
    the window is the prompt's first, with one), one kernel step a slot
    for T = 1."""
    h, d, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.conv_taps
    hd = h * d
    qkv = fluid.layers.concat(
        [_linear(x, hd, name + "_" + p) for p in "qkv"], axis=2)
    conv_w = fluid.layers.concat(
        [_param(name + "_conv_" + p, [taps, hd], cfg) for p in "qkv"], axis=1)
    f = _linear(_linear(x, d, name + "_f_down"), hd, name + "_f_up")
    b = _linear(x, h, name + "_b")
    args = (qkv, f, b, conv_w,
            _param(name + "_a_log", [h], cfg, dtype="float32", value=0.0),
            _param(name + "_dt_bias", [hd], cfg, dtype="float32", value=0.0),
            h, d)
    if cache is None:
        o = fluid.layers.kda_window(*args)
    elif cache["mode"] == "paged_window":
        o = fluid.layers.kda_window(
            *args, state=cache["vars"], row=cache["state_rows"],
            start=cache["pos"], length=cache["window_len"])
    else:
        o = fluid.layers.kda_step(*args, state=cache["vars"],
                                  rows=cache["state_rows"],
                                  interpret=cfg.flash_interpret)
    o = fluid.layers.reshape(
        _norm(fluid.layers.reshape(o, shape=[0, -1, h, d]), cfg,
              name + "_o_norm"), shape=[0, -1, hd])
    gate = _linear(_linear(x, d, name + "_g_down"), hd, name + "_g_up")
    return _linear(_gated(o, gate, cfg), cfg.hidden_size, name + "_o")


def gqa_mix(x, pos, cfg, name, cache=None):
    """The gated grouped-query softmax layer, no positions encoded: over
    the window's own rows without a cache, over the slot's gathered rows
    for a prefill window, through the paged T = 1 kernel for the step."""
    heads, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
    q = _linear(x, heads * d, name + "_q")
    k = _linear(x, kvh * d, name + "_k")
    v = _linear(x, kvh * d, name + "_v")
    if cache is None:
        ctxt = fluid.layers.gqa_window_attention(q, k, v, pos, kvh, d)
    else:
        kp, vp = (fluid.layers.kv_cache_write_paged(
            pool, fluid.layers.unsqueeze(rows, axes=[1]), cache["tables"],
            cache["pos"]) for pool, rows in zip(cache["vars"], (k, v)))
        if cache["mode"] == "paged_window":
            krow, vrow = (fluid.layers.reshape(
                fluid.layers.kv_cache_gather_paged(pool, cache["tables"]),
                shape=[0, -1, kvh * d]) for pool in (kp, vp))
            ctxt = fluid.layers.gqa_window_attention(q, krow, vrow, pos,
                                                     kvh, d)
        else:
            ctxt = fluid.layers.reshape(
                fluid.layers.flash_decode_paged_attention(
                    fluid.layers.reshape(q, shape=[-1, heads, 1, d]), kp, vp,
                    cache["tables"], lengths=cache["lengths"],
                    scale=d ** -0.5, interpret=cfg.flash_interpret),
                shape=[-1, 1, heads * d])
    gate = _linear(x, heads * d, name + "_gate")
    return _linear(_gated(ctxt, gate, cfg), cfg.hidden_size, name + "_o")


def decoder(ids, pos, cfg, cache=None):
    """[N, T, 1] ids (``pos`` [N, T, 1] orders the softmax layers' keys;
    nothing encodes it) -> (hidden [N, T, H] before the final norm, [per
    layer: the held experts' assignment counts])."""
    h = fluid.layers.embedding(
        input=ids, size=[cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
        param_attr=fluid.ParamAttr(name="so2_embed"))
    counts = []
    for i in range(cfg.num_hidden_layers):
        name = "so2_%d" % i
        cache_i = cache and dict(cache, vars=cache["kinds"][i])
        x = _norm(h, cfg, name + "_ln1")
        if cfg.is_gqa(i):
            mix = gqa_mix(x, pos, cfg, name + "_att", cache=cache_i)
        else:
            mix = kda_mix(x, cfg, name + "_kda", cache=cache_i)
        h = fluid.layers.elementwise_add(h, mix)
        ff, c = _dc.expert_layer(_norm(h, cfg, name + "_ln2"), cfg,
                                 name + "_moe")
        counts.append(c)
        h = fluid.layers.elementwise_add(h, ff)
    return h, counts


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
        h, _counts = decoder(ids, pos_ids, cfg)
        logits = _dc.lm_head(h, cfg, "so2")
    return main, startup, ["ids", "pos_ids"], logits


def build_paged_window(cfg, blocks, block, max_blocks, seq_len, slots=None):
    """Prefill-window graph, the contract of
    ``deepseek.build_deepseek_paged_window`` plus the state: ONE prompt
    window lands through the slot's fed ``table`` at ``window_pos`` and
    continues the slot's fed ``state_row`` (from zeros where
    ``window_pos`` is 0: no reset program, no extra dispatch at
    admission); tokens at or past the fed ``window_len`` are the bucket's
    padding and leave state and convolution tail as of the last real
    token. Returns (main, startup, feed names, next_logits [1, vocab])."""
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
        state_row = fluid.layers.data(name="state_row", shape=[1],
                                      dtype="int64")
        window_len = fluid.layers.data(name="window_len", shape=[1],
                                       dtype="int64")
        cache = {"mode": "paged_window", "tables": table, "pos": window_pos,
                 "state_rows": state_row, "window_len": window_len,
                 "kinds": _kinds.declare_pools(cache_kinds(cfg), blocks,
                                               block, slots)}
        h, _counts = decoder(ids, pos_ids, cfg, cache=cache)
        next_logits = _dc.last_row_logits(h, last_onehot, cfg, "so2")
    return (main, startup,
            ["ids", "pos_ids", "table", "window_pos", "last_onehot",
             "state_row", "window_len"], next_logits)


def build_paged_step(cfg, slots, blocks, block, max_blocks, step_w=1):
    """The fused T = 1 step: every slot's newest token lands at
    ``step_pos`` through its ``tables`` row and steps the state row its
    ``state_rows`` entry names. An inactive slot (idle, or between two
    prefill windows) feeds token 0 at position 0 of an all-sink table and
    state row 0, the sink, so the step cannot touch what a prefilling
    slot has built. The program also gives, per layer, the assignments
    each held expert received (``main._step_stats``). Returns (main,
    startup, feed names, step_logits [slots, vocab])."""
    if step_w != 1:
        raise NotImplementedError(
            "solar_open2: " + UNSUPPORTED["spec_tokens"])
    main, startup = _dc.programs(donate=True)
    with fluid.program_guard(main, startup):
        step_ids = fluid.layers.data(name="step_ids", shape=[1, 1],
                                     dtype="int64")
        step_pos = fluid.layers.data(name="step_pos", shape=[1, 1],
                                     dtype="int64")
        tables = fluid.layers.data(name="tables", shape=[max_blocks],
                                   dtype="int64")
        state_rows = fluid.layers.data(name="state_rows", shape=[1],
                                       dtype="int64")
        write_pos = fluid.layers.reshape(step_pos, shape=[-1])
        cache = {"mode": "paged_step", "tables": tables, "pos": write_pos,
                 "lengths": fluid.layers.scale(write_pos, bias=1.0),
                 "state_rows": state_rows,
                 "kinds": _kinds.declare_pools(cache_kinds(cfg), blocks,
                                               block, slots)}
        h, counts = decoder(step_ids, step_pos, cfg, cache=cache)
        step_logits = fluid.layers.reshape(_dc.lm_head(h, cfg, "so2"),
                                           shape=[-1, cfg.vocab_size])
        main._step_stats = [fluid.layers.stack(counts, axis=0).name]
    return (main, startup, ["step_ids", "step_pos", "tables", "state_rows"],
            step_logits)


def build_paged_block_copy(cfg, blocks, block, npairs):
    """ONE compiled pool-internal block copy across every softmax layer's
    K and V pool (copy-on-write); the states are no blocks."""
    main, startup = _dc.programs(donate=True)
    with fluid.program_guard(main, startup):
        src = fluid.layers.data(name="src", shape=[npairs], dtype="int64")
        dst = fluid.layers.data(name="dst", shape=[npairs], dtype="int64")
        paged = [tuple(_kinds.pools(layer)) for layer in cache_kinds(cfg)]
        for layer in _kinds.declare_pools(paged, blocks, block):
            for pool in layer:
                fluid.layers.kv_cache_block_copy(pool, src, dst)
        ok = fluid.layers.fill_constant(shape=[1], dtype="int32", value=1)
    return main, startup, ["src", "dst"], ok


def _state_bytes(cfg):
    """Bytes of ``S`` a slot keeps over the delta-rule layers (the
    convolution tails, 3 % of it, ride outside the kernel)."""
    return sum(s.bytes_per_slot for layer in cache_kinds(cfg)
               for s in _kinds.states(layer)[:1])


def step_stats(fetched, live_rows, live_slots=0, cfg=None):
    """What one T = 1 step did, for the ``decode_paged_step`` span and
    ``/metrics``: the states stepped and their bytes read + written, the
    live K/V rows, and the held experts' counts (``fetched``:
    ``main._step_stats`` as fetched, [layers, experts held] int32)."""
    from paddle_tpu.fluid import profiler

    moved = 2 * int(live_slots) * _state_bytes(cfg)
    profiler.bump_counter("kda_state_bytes", moved)
    return dict(_dc.expert_step_stats(fetched[0]),
                state_slots_live=int(live_slots), state_bytes=moved,
                kv_rows_live=int(live_rows))


def window_stats(cfg, offset, real, padded):
    """What one prefill window did, for the ``decode_paged_window`` span:
    chunks of the delta rule's scan (``decoder_ops._KDA_CHUNK`` tokens, a
    layer) and the bucket's padding; a window at offset 0 starts a state
    from zeros (``kda_state_resets``)."""
    from paddle_tpu.fluid import profiler
    from paddle_tpu.fluid.ops import decoder_ops

    if offset == 0:
        profiler.bump_counter("kda_state_resets")
    return dict(kda_chunks=padded // math.gcd(padded, decoder_ops._KDA_CHUNK),
                window_tokens_real=int(real),
                window_tokens_padded=int(padded))
