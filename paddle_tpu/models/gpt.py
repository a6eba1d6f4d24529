"""Decoder-only causal language model (GPT-style).

The reference era's language model is the PTB LSTM
(reference: python/paddle/fluid/tests/book/test_rnn_encoder_decoder.py,
and the word-language-model configs); a decoder-only transformer LM is
the modern successor built from the SAME fluid pieces this repo already
ships: embedding + the shared ``multi_head_attention`` (models/bert.py,
with its fused flash-attention path) under the kernel's causal flag +
post-LN residual FFN blocks + an (untied) LM softmax head.

TPU-first notes: with ``cfg.use_flash_attention`` the causal mask rides
the Pallas kernel's static flag (no [T, T] bias tensor is built), the
whole step compiles to one XLA computation, and long-context training
composes with the sequence-parallel machinery (parallel/ring_attention
runs the same kernels per ring hop).
"""

import numpy as np

import paddle_tpu.fluid as fluid

from . import bert as _bert
from . import cache_kinds as _kinds


class GPTConfig(object):
    def __init__(self, vocab_size=50257, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072,
                 max_position_embeddings=1024, hidden_dropout=0.1,
                 attention_dropout=0.1, is_test=False,
                 use_flash_attention=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.hidden_dropout = hidden_dropout
        self.attention_dropout = attention_dropout
        self.is_test = is_test
        self.use_flash_attention = use_flash_attention

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 211)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("max_position_embeddings", 64)
        return cls(**kw)


def _causal_bias(seq_len):
    """[1, T, T] additive bias (0 attendable / -1e4 future) for the dense
    path; the flash path masks inside the kernel instead."""
    tri = np.tril(np.ones((1, seq_len, seq_len), np.float32))
    bias = fluid.layers.assign((tri - 1.0) * 1e4)
    bias = fluid.layers.unsqueeze(bias, axes=[1])  # [1, 1, T, T]
    bias.stop_gradient = True
    return bias


def gpt_decoder(ids, pos_ids, input_mask, cfg, kv_cache=None):
    """Decoder stack on [N, T, 1] int64 ids; returns hidden [N, T, H].

    ``kv_cache`` (None for training/full-forward inference) threads the
    decode runtime's cache plumbing through every layer's attention —
    see ``build_gpt_paged_window`` / ``build_gpt_paged_step``. With a
    cache ``input_mask`` is unused: the fed bias carries all masking."""
    emb = fluid.layers.embedding(
        input=ids, size=[cfg.vocab_size, cfg.hidden_size],
        param_attr=fluid.ParamAttr(name="tok_embedding"),
    )
    pos = fluid.layers.embedding(
        input=pos_ids, size=[cfg.max_position_embeddings, cfg.hidden_size],
        param_attr=fluid.ParamAttr(name="pos_embedding"),
    )
    h = fluid.layers.elementwise_add(emb, pos)
    h = _bert._dropout(h, cfg.hidden_dropout, cfg.is_test)

    key_bias = None
    attn_bias = None
    mode = kv_cache["mode"] if kv_cache is not None else None
    if mode == "paged_window":
        # prefill window: masking lives entirely in the fed
        # [T, max_blocks*block] resume bias (offset-shifted causal +
        # prefix), and attention is dense window×row by design — see
        # multi_head_attention's window branch
        use_flash = False
    elif mode == "paged_step":
        # fused paged step/verify: masking lives in the fed per-slot
        # step bias; flash (the table-chasing decode kernel) engages
        # only on the T=1 single-query form — the T=k verify is the
        # window×row dense regime like a prefill window. The flash
        # policy keys on the CACHE length (the kv extent the kernel
        # actually sweeps), not the length-1 query
        use_flash = _bert.flash_wanted(
            cfg, seq_len=int(kv_cache["max_len"])
        )
    else:
        # resolve the flash policy ONCE and pass the decision down: the
        # attention helper re-deriving it from a possibly-dynamic q_in seq
        # dim could silently take the dense branch with attn_bias=None,
        # dropping causal+padding masking entirely (ADVICE r5)
        _s = ids.shape[1] if len(ids.shape) >= 2 else -1
        use_flash = _bert.flash_wanted(
            cfg, seq_len=None if _s in (-1, None) else int(_s)
        )
        if use_flash:
            # padding as a key-only bias; causality rides the kernel flag
            key_bias = _bert.mask_to_key_bias(input_mask)
        else:
            # dense path: causal [1,1,T,T] + key padding [N,1,1,T]
            # broadcast. Built whenever the shared attention helper would
            # take its dense branch (attention dropout no longer forces
            # it — the kernel drops in-VMEM), which would otherwise run
            # with neither mask
            pad = fluid.layers.scale(
                fluid.layers.reshape(input_mask, shape=[0, 1, 1, -1]),
                scale=1e4, bias=-1e4,
            )
            pad.stop_gradient = True
            attn_bias = fluid.layers.elementwise_add(
                _causal_bias(ids.shape[1]), pad
            )
    for i in range(cfg.num_layers):
        name = "gpt_%d" % i
        cache_i = None
        if kv_cache is not None:
            k_var, v_var = kv_cache["caches"][i]
            cache_i = {"k": k_var, "v": v_var, "mode": mode,
                       "tables": kv_cache["tables"],
                       "pos": kv_cache["pos"]}
            if mode == "paged_window":
                cache_i["resume_bias"] = kv_cache["resume_bias"]
            else:
                cache_i["step_bias"] = kv_cache["step_bias"]
        attn = _bert.multi_head_attention(
            h, h, attn_bias, cfg, name + "_att", key_bias=key_bias,
            causal=True, use_flash=use_flash, cache=cache_i,
        )
        attn = _bert._dropout(attn, cfg.hidden_dropout, cfg.is_test)
        h = fluid.layers.layer_norm(
            fluid.layers.elementwise_add(h, attn), begin_norm_axis=2,
            name=name + "_ln1",
        )
        ff = _bert._dropout(
            _bert._ffn(h, cfg, name + "_ffn"), cfg.hidden_dropout,
            cfg.is_test,
        )
        h = fluid.layers.layer_norm(
            fluid.layers.elementwise_add(h, ff), begin_norm_axis=2,
            name=name + "_ln2",
        )
    return h


def gpt_lm_logits(ids, pos_ids, input_mask, cfg, kv_cache=None):
    """[N, T, vocab] next-token logits."""
    h = gpt_decoder(ids, pos_ids, input_mask, cfg, kv_cache=kv_cache)
    return fluid.layers.fc(
        input=h, size=cfg.vocab_size, num_flatten_dims=2, name="lm_head"
    )


def build_gpt_lm_train(cfg, seq_len, learning_rate=3e-4, use_amp=False):
    """Next-token LM training graph: positions t predict tokens t+1,
    padded positions masked out of the loss.

    Returns (main, startup, feeds, avg_loss)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[seq_len, 1],
                                dtype="int64")
        pos_ids = fluid.layers.data(name="pos_ids", shape=[seq_len, 1],
                                    dtype="int64")
        input_mask = fluid.layers.data(
            name="input_mask", shape=[seq_len, 1], dtype="float32"
        )
        logits = gpt_lm_logits(ids, pos_ids, input_mask, cfg)
        # shift: logits[:, :-1] predict ids[:, 1:]
        pred = fluid.layers.slice(logits, axes=[1], starts=[0],
                                  ends=[seq_len - 1])
        tgt = fluid.layers.slice(ids, axes=[1], starts=[1], ends=[seq_len])
        loss = fluid.layers.softmax_with_cross_entropy(pred, tgt)
        # mask the loss at padded TARGET positions
        tgt_mask = fluid.layers.slice(input_mask, axes=[1], starts=[1],
                                      ends=[seq_len])
        loss = fluid.layers.elementwise_mul(loss, tgt_mask)
        denom = fluid.layers.reduce_sum(tgt_mask)
        avg_loss = fluid.layers.elementwise_div(
            fluid.layers.reduce_sum(loss), denom
        )
        opt = fluid.optimizer.Adam(learning_rate=learning_rate)
        if use_amp:
            from paddle_tpu.fluid.contrib import mixed_precision as _mp

            opt = _mp.decorate(opt)
        opt.minimize(avg_loss)
    feeds = [ids, pos_ids, input_mask]
    return main, startup, feeds, avg_loss


def build_gpt_infer(cfg, seq_len):
    """Inference graph (is_test semantics): returns (main, startup,
    feed names, logits). The caller's config is not mutated."""
    import copy

    cfg = copy.copy(cfg)
    cfg.is_test = True
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[seq_len, 1],
                                dtype="int64")
        pos_ids = fluid.layers.data(name="pos_ids", shape=[seq_len, 1],
                                    dtype="int64")
        input_mask = fluid.layers.data(
            name="input_mask", shape=[seq_len, 1], dtype="float32"
        )
        logits = gpt_lm_logits(ids, pos_ids, input_mask, cfg)
    return main, startup, ["ids", "pos_ids", "input_mask"], logits


# ---------------------------------------------------------------------------
# autoregressive decode runtime graphs: a paged KV pool (block-table
# addressing: ONE shared pool for live slots AND the prefix cache; a slot's
# row is whatever its fed table maps to), prefill windows and the fused step
# ---------------------------------------------------------------------------


def cache_kinds(cfg):
    """Per layer, the pools the decode runtime keeps (``cache_kinds.py``):
    K and V, float32. A token's keys (and values) are ONE row,
    ``[1, heads * d_head]``: the heads side by side on the lanes, in the
    order ``multi_head_attention`` splits them. A row whose width is a
    multiple of 128 lanes lies in the device's own tiling, so the scatter,
    the gather and the paged kernel take the pool as it lies; with the
    heads as a dim of their own (64-lane rows) every program copied every
    pool whole, three times a step."""
    row = [1, cfg.hidden_size]
    return [
        (_kinds.CachePool("gpt_paged_k_%d" % i, row, "float32"),
         _kinds.CachePool("gpt_paged_v_%d" % i, row, "float32"))
        for i in range(cfg.num_layers)
    ]


def paged_pool_names(cfg, blocks, block):
    """Per-layer (K, V) paged-pool var names. Pool geometry is part of
    the name: two sessions sharing one scope (a 1-slot greedy_generate
    session next to a serving engine) must never read each other's
    differently-shaped pools."""
    return [tuple(p.name(blocks, block) for p in layer)
            for layer in cache_kinds(cfg)]


def paged_pool_shape(cfg, blocks, block):
    """``[blocks, 1, block, hidden]``: every pool of ``cache_kinds``."""
    return cache_kinds(cfg)[0][0].shape(blocks, block)


def paged_block_bytes(cfg, block):
    """Device bytes one pool block costs across all layers (K + V,
    fp32) — what sizes the allocator and the HBM-footprint accounting
    (a slot costs ``ceil(len/block)`` of these, not ``max_len``)."""
    return _kinds.bytes_per_token(cache_kinds(cfg)) * int(block)


def build_gpt_paged_window(cfg, blocks, block, max_blocks, seq_len,
                           slots=None):
    """Paged prefill-window graph: ONE prompt window (batch 1, padded to
    the ``seq_len`` bucket) lands THROUGH the slot's fed block table —
    the runtime's only prefill form (a whole prompt is a window at
    position 0). Per layer the window's K/V scatters into the
    pool blocks its ``table`` [max_blocks] maps logical positions
    ``window_pos .. window_pos+T-1`` to, then the window's queries
    attend dense over the gathered logical row under the fed
    ``resume_bias`` [seq_len, max_blocks*block] (offset-shifted causal;
    -1e4 also buries sink-block garbage past the live length). Table,
    position, and bias are all runtime data: one program per bucket, 0
    steady-state recompiles. ``slots`` sizes a model's per-slot states
    (``cache_kinds.CacheState``); this model keeps none.

    Returns (main, startup, feed names, next_logits [1, vocab])."""
    import copy

    cfg = copy.copy(cfg)
    cfg.is_test = True
    main, startup = fluid.Program(), fluid.Program()
    main._donate_mutable = True
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[seq_len, 1],
                                dtype="int64")
        pos_ids = fluid.layers.data(name="pos_ids", shape=[seq_len, 1],
                                    dtype="int64")
        table = fluid.layers.data(name="table", shape=[max_blocks],
                                  dtype="int64")
        window_pos = fluid.layers.data(name="window_pos", shape=[1],
                                       dtype="int64")
        resume_bias = fluid.layers.data(
            name="resume_bias", shape=[seq_len, max_blocks * block],
            dtype="float32"
        )
        last_onehot = fluid.layers.data(
            name="last_onehot", shape=[seq_len, 1], dtype="float32"
        )
        kv_cache = {
            "mode": "paged_window",
            "caches": _kinds.declare_pools(
                cache_kinds(cfg), blocks, block),
            "tables": table,
            "pos": window_pos,
            "resume_bias": resume_bias,
            "max_len": max_blocks * block,
        }
        logits = gpt_lm_logits(ids, pos_ids, None, cfg, kv_cache=kv_cache)
        next_logits = fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(logits, last_onehot), dim=1
        )
    feeds = ["ids", "pos_ids", "table", "window_pos", "resume_bias",
             "last_onehot"]
    return main, startup, feeds, next_logits


def build_gpt_paged_step(cfg, slots, blocks, block, max_blocks, step_w=1):
    """Unified paged step/verify graph: every slot advances a
    ``step_w``-token window per tick against the shared paged pool —
    ``step_w=1`` is the fused decode step, ``step_w=k`` the speculative
    VERIFY program that scores all k draft positions in one call. Feeds
    (all fixed-shape; tables/positions/bias are runtime data, so one
    compiled program per window width serves every table layout):

    - ``step_ids`` / ``step_pos`` [slots, step_w, 1] int64: each slot's
      token window and its contiguous cache positions (window start =
      ``step_pos[s, 0]``); inactive slots park their table on the sink
      block and tolerate any position;
    - ``tables`` [slots, max_blocks] int64 block tables;
    - ``step_bias`` [slots, step_w, max_blocks*block]: additive mask, 0
      where cache position j <= step_pos[s, i] for window query i, -1e4
      beyond — per-query causal by construction, and it buries sink /
      stale-tail garbage.

    Returns (main, startup, feeds, step_logits [slots, step_w, vocab]
    reshaped to [slots*step_w, vocab])."""
    import copy

    cfg = copy.copy(cfg)
    cfg.is_test = True
    main, startup = fluid.Program(), fluid.Program()
    main._donate_mutable = True
    with fluid.program_guard(main, startup):
        step_ids = fluid.layers.data(name="step_ids", shape=[step_w, 1],
                                     dtype="int64")
        step_pos = fluid.layers.data(name="step_pos", shape=[step_w, 1],
                                     dtype="int64")
        tables = fluid.layers.data(name="tables", shape=[max_blocks],
                                   dtype="int64")
        step_bias = fluid.layers.data(
            name="step_bias", shape=[step_w, max_blocks * block],
            dtype="float32"
        )
        # write start = each slot's first window position
        write_pos = fluid.layers.reshape(
            fluid.layers.slice(step_pos, axes=[1], starts=[0], ends=[1]),
            shape=[-1],
        )
        kv_cache = {
            "mode": "paged_step",
            "caches": _kinds.declare_pools(
                cache_kinds(cfg), blocks, block),
            "tables": tables,
            "pos": write_pos,
            "step_bias": step_bias,
            "max_len": max_blocks * block,
        }
        logits = gpt_lm_logits(step_ids, step_pos, None, cfg,
                               kv_cache=kv_cache)
        step_logits = fluid.layers.reshape(
            logits, shape=[-1, cfg.vocab_size]
        )
    feeds = ["step_ids", "step_pos", "tables", "step_bias"]
    return main, startup, feeds, step_logits


def build_gpt_paged_block_copy(cfg, blocks, block, npairs):
    """ONE compiled pool-internal block copy across every layer's K and
    V: ``cache[dst[i]] = cache[src[i]]`` for each of the ``npairs`` fed
    pairs — the copy-on-write program (duplicate a shared block before
    its new owner writes the partial tail). Pad unused pairs with
    src==dst identity copies to reuse one compiled pair count.

    Returns (main, startup, feed names, ok)."""
    main, startup = fluid.Program(), fluid.Program()
    main._donate_mutable = True
    with fluid.program_guard(main, startup):
        src = fluid.layers.data(name="src", shape=[npairs], dtype="int64")
        dst = fluid.layers.data(name="dst", shape=[npairs], dtype="int64")
        for pk, pv in _kinds.declare_pools(
                cache_kinds(cfg), blocks, block):
            fluid.layers.kv_cache_block_copy(pk, src, dst)
            fluid.layers.kv_cache_block_copy(pv, src, dst)
        ok = fluid.layers.fill_constant(shape=[1], dtype="int32", value=1)
    return main, startup, ["src", "dst"], ok


# what ``serving/decode.py`` asks a served model's module for, under the
# names every such module gives them; every mode is built for this cache
UNSUPPORTED = {}
build_paged_window = build_gpt_paged_window
build_paged_step = build_gpt_paged_step
build_paged_block_copy = build_gpt_paged_block_copy


def _reference_generate(exe, infer_prog, logits_var, cfg, prompt_ids,
                        max_len, scope=None):
    """The ORACLE: host-driven greedy decode recomputing the full
    [1, max_len] forward per emitted token. O(T^2) model forwards — kept
    verbatim (minus rebuilding the loop-constant pos_ids / position-index
    arrays every iteration) as the parity reference the decode runtime's
    tests and probe compare token-for-token against."""
    ids = list(prompt_ids)
    pos_ids = np.arange(max_len).reshape(1, max_len, 1).astype("int64")
    positions = np.arange(max_len)
    padded = np.zeros((1, max_len, 1), "int64")
    padded[0, : len(ids), 0] = ids
    for _ in range(max_len - len(prompt_ids)):
        cur = len(ids)
        padded[0, :cur, 0] = ids
        feed = {
            "ids": padded,
            "pos_ids": pos_ids,
            "input_mask": (positions < cur)
            .astype("float32").reshape(1, max_len, 1),
        }
        (lv,) = exe.run(infer_prog, feed=feed, fetch_list=[logits_var],
                        scope=scope)
        nxt = int(np.asarray(lv)[0, cur - 1].argmax())
        ids.append(nxt)
    return ids


def greedy_generate(exe, infer_prog, logits_var, cfg, prompt_ids, max_len,
                    scope=None):
    """Greedy decode through the KV-cache runtime: one prefill window over
    the prompt, then O(1)-length incremental steps against the cache — O(T)
    total model work instead of the O(T^2) full-forward-per-token loop
    (kept as ``_reference_generate``, the parity oracle). Output is
    token-exact vs the oracle: the cached K/V are the same projections
    the full forward computes, masked-out positions carry exactly-zero
    softmax weight in fp32, and the argmax sees bitwise-equal logits.

    The single-slot decode session is cached per (scope, model geometry),
    so repeated calls reuse the compiled window/step programs."""
    ids = list(prompt_ids)
    if len(ids) >= max_len:
        return ids
    from paddle_tpu.serving import decode as _decode

    sess = _decode.session_for_generate(exe, cfg, scope, max_len,
                                        infer_prog)
    # the one slot owns the whole pool past the sink: an identity table
    table = list(range(1, sess.max_blocks + 1))
    # the session is cached per (scope, geometry): concurrent callers
    # (the old per-call loop was trivially reentrant) serialize on its
    # lock for the WHOLE generation so interleaved steps can never read
    # each other's blocks
    with sess.lock:
        logits = sess.paged_window(table, ids, 0)
        ids.append(int(np.asarray(logits).ravel().argmax()))
        while len(ids) < max_len:
            step = sess.paged_step([[ids[-1]]], [len(ids) - 1], [table],
                                   [True])
            ids.append(int(step[0, 0].argmax()))
    return ids
