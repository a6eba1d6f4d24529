"""BERT encoder — bring-up config 3 (BASELINE.json "BERT-base blocks") and
the second headline benchmark model.

The reference era's BERT implementations on Fluid (e.g. the
`multihead_matmul_fuse_pass` fusion target, ir/multihead_matmul_fuse_pass.cc)
build attention exactly from this op sequence: fc(Q/K/V) -> reshape ->
transpose -> matmul(QK^T)*scale -> softmax -> dropout -> matmul(V) ->
transpose -> reshape -> fc. On TPU the whole sequence fuses inside one XLA
computation (the fusion pass's job is subsumed by the compiler); matmuls run
on the MXU in bf16 when AMP is on.
"""

import math

import paddle_tpu.fluid as fluid


class BertConfig(object):
    def __init__(self, vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072,
                 max_position_embeddings=512, type_vocab_size=2,
                 hidden_dropout=0.1, attention_dropout=0.1, is_test=False,
                 use_flash_attention=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout = hidden_dropout
        self.attention_dropout = attention_dropout
        self.is_test = is_test
        self.use_flash_attention = use_flash_attention

    @classmethod
    def base(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 1024)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("max_position_embeddings", 64)
        return cls(**kw)


def _dropout(x, rate, is_test):
    if is_test or rate <= 0.0:
        return x
    return fluid.layers.dropout(x, dropout_prob=rate)


def mask_to_bias(mask_2d):
    """[N, S, S] 0/1 attention mask -> additive bias [N, 1, S, S]
    (0 where attendable, -10000 where masked), broadcast over heads."""
    neg = fluid.layers.elementwise_mul(
        fluid.layers.elementwise_add(
            mask_2d,
            fluid.layers.fill_constant(shape=[1], dtype="float32", value=-1.0),
        ),
        fluid.layers.fill_constant(shape=[1], dtype="float32", value=10000.0),
    )
    bias = fluid.layers.unsqueeze(neg, axes=[1])
    bias.stop_gradient = True
    return bias


def mask_to_key_bias(mask):
    """[N, S, 1] 0/1 token mask -> key-only additive bias [N, S]
    ((m-1)*1e4: 0 where attendable, -1e4 on padded keys) for the fused
    flash-attention path; the query side needs no mask because padded-
    query rows never reach a loss term."""
    b = fluid.layers.scale(
        fluid.layers.reshape(mask, shape=[0, -1]), scale=1e4, bias=-1e4
    )
    b.stop_gradient = True
    return b


# Measured dense/flash crossover on the v5e bench chip (BENCH_BANK.json,
# round 5, post-AMP-harmonization numbers): XLA's fused dense attention
# wins at seq 384 (351 vs 272 seq/s — it runs near the HBM roofline) and
# seq 512 (237 vs 201); GPT-2 at seq 1024 is parity-to-slight-flash-win
# (79.5k vs 78.0k tok/s); at 4096 flash runs +35% over dense's best
# FEASIBLE batch — dense b4 cannot even compile there (the [S, S]
# softmax activations exceed HBM), which is the kernel's real value.
FLASH_AUTO_SEQ_THRESHOLD = 1024


def flash_engages(cfg, key_bias, seq_len=None):
    """True when multi_head_attention will actually run the fused flash
    path (vs the dense fallback). Model builders that skip constructing a
    dense attention bias on the flash path MUST consult this — a silent
    fallback without the dense bias would drop masking entirely.
    Attention dropout no longer forces the fallback: the kernel applies
    it in-VMEM from a stateless per-step hash (kernels/flash_attention.py
    dropout_rate).

    ``cfg.use_flash_attention`` may be True (always fuse), False/None
    (never), or ``"auto"``: fuse when the static query length is at or
    beyond the measured crossover (``FLASH_AUTO_SEQ_THRESHOLD``,
    overridable per-config via ``cfg.flash_auto_threshold``) — below it
    XLA's dense attention is the faster program on TPU."""
    return bool(flash_wanted(cfg, seq_len) and key_bias is not None)


def flash_wanted(cfg, seq_len=None):
    """Resolve ``cfg.use_flash_attention`` (True/False/"auto") to a bool
    without needing the mask — model builders use this to decide WHICH
    mask to construct (key-only for the kernel, dense bias otherwise)."""
    want = getattr(cfg, "use_flash_attention", False)
    if want == "auto":
        thr = getattr(cfg, "flash_auto_threshold", FLASH_AUTO_SEQ_THRESHOLD)
        want = seq_len is not None and seq_len >= thr
    return bool(want)


def _apply_kv_cache(cache, k, v, cfg):
    """Write this call's K/V projections, ``[N, T, hidden]`` as they
    leave ``fc`` (heads side by side: a token's row of the pool), into
    the paged pool described by ``cache`` (see ``multi_head_attention``)
    via ``kv_cache_write_paged`` — O(written bytes), with the block table
    and the write position as runtime DATA, so one compiled program
    covers every admission pattern. Returns (k, v) for the attention that
    follows: the split-head ``[1, heads, max_len, d_head]`` logical row
    for a window, the updated pool vars for a step."""
    k_upd = fluid.layers.kv_cache_write_paged(
        cache["k"], fluid.layers.unsqueeze(k, axes=[1]), cache["tables"],
        cache["pos"])
    v_upd = fluid.layers.kv_cache_write_paged(
        cache["v"], fluid.layers.unsqueeze(v, axes=[1]), cache["tables"],
        cache["pos"])
    if cache["mode"] == "paged_window":
        # batch-1 window through the slot's block TABLE: the window's
        # K/V lands at logical positions pos..pos+T-1, scattered into
        # whichever physical pool blocks the fed table row maps them
        # to, then the full logical row (every table block, sink
        # garbage included — resume_bias masks it) is gathered back for
        # the window's queries. Covers monolithic prefill (pos 0) and
        # chunked resume alike: offset, table, and positions are all
        # runtime data, so ONE program per bucket serves both.
        return (_gather_heads(k_upd, cache["tables"], cfg),
                _gather_heads(v_upd, cache["tables"], cfg))
    # paged_step, the fused multi-slot step (T=1 decode / T=k speculative
    # verify): each slot's T-token window scatters through its table
    # row; the attention branch reads the pool back through the tables
    # (paged flash kernel or gather+dense), so just return the updated
    # pool vars.
    return k_upd, v_upd


def _gather_heads(pool, tables, cfg):
    """Each slot's logical row read THROUGH its block table, heads split
    after the gather: [S, 1, max_len, hidden] -> [S, heads, max_len,
    d_head]."""
    rows = fluid.layers.kv_cache_gather_paged(pool, tables)
    rows = fluid.layers.reshape(
        rows, shape=[0, -1, cfg.num_heads, cfg.hidden_size // cfg.num_heads])
    return fluid.layers.transpose(rows, perm=[0, 2, 1, 3])


def multi_head_attention(q_in, kv_in, attn_bias, cfg, name, key_bias=None,
                         causal=False, use_flash=None, cache=None):
    """Self/cross attention on [N, S, H] inputs.

    With ``cfg.use_flash_attention`` the score/softmax/context chain runs
    as ONE fused flash-attention op — the Pallas kernel keeps the [S, S]
    scores in VMEM, applies attention dropout in-kernel (per-step seed
    from the executor key stream), and ``key_bias`` [N, S] carries the
    padding mask in key-only form.

    ``use_flash``: the builder's RESOLVED policy decision. Model builders
    choose which mask to construct from ``flash_wanted`` and must pass
    that same decision down, so a dynamic query dim here can never
    silently diverge from the mask they built (ADVICE r5). ``None`` keeps
    the legacy behavior of re-resolving from the static query length.

    ``cache``: KV-cache plumbing for autoregressive serving (None for
    training/encoder use). A dict with ``k``/``v`` — persistable
    [blocks, 1, block, hidden] pool vars, a token's keys (values) one
    row with the heads side by side — the fed block ``tables``,
    the write position ``pos``, plus ``mode``:

    - ``"paged_window"``: one prompt window (batch 1) lands through its
      table at ``pos`` and attends dense over the gathered logical row
      under the fed ``resume_bias`` [T, max_blocks*block];
    - ``"paged_step"``: the fused step. Each slot's T-token window lands
      through its table row at its ``pos`` [slots] (inactive slots feed
      an all-sink table), then attends over the slot's logical row under
      ``step_bias`` [slots, T, max_blocks*block] — via the table-chasing
      flash kernel when ``use_flash`` and T = 1, gather + dense otherwise.
      ``attn_bias``/``causal`` are ignored: the fed bias IS the causal
      mask, since a slot's row never holds an unmasked future token."""
    d_head = cfg.hidden_size // cfg.num_heads

    def _proj(x, suffix):
        return fluid.layers.fc(
            input=x, size=cfg.hidden_size, num_flatten_dims=2,
            name="%s_%s" % (name, suffix),
        )

    def _split_heads(x):
        # [N, S, H] -> [N, heads, S, d_head]
        x = fluid.layers.reshape(x, shape=[0, 0, cfg.num_heads, d_head])
        return fluid.layers.transpose(x, perm=[0, 2, 1, 3])

    q = _split_heads(_proj(q_in, "q"))
    if cache is None:
        k = _split_heads(_proj(kv_in, "k"))
        v = _split_heads(_proj(kv_in, "v"))
    else:
        # the pool keeps a token's row unsplit: heads part after the read
        k, v = _apply_kv_cache(
            cache, _proj(kv_in, "k"), _proj(kv_in, "v"), cfg)
    if cache is not None and cache["mode"] == "paged_step":
        # unified paged step/verify: q [slots, heads, T, d_head] (T=1
        # decode, T=k speculative verify) against each slot's logical
        # row read THROUGH its block table. ``step_bias``
        # [slots, T, max_blocks*block] is the fed offset-shifted causal
        # mask (0 where cache position j <= pos_s + i for window query
        # i, -1e4 beyond — which also buries sink-block garbage), so
        # inactive slots and every live-length mix share one program.
        scale_ = 1.0 / math.sqrt(d_head)
        T_static = q.shape[2]
        if use_flash and T_static == 1:
            # single-query path: the Pallas kernel chases the table via
            # scalar prefetch — the logical rows never materialize. The
            # slot's live keys (its write position + the token just
            # written) tell the kernel where the table row stops being
            # worth reading; the bias still masks inside the live blocks.
            kb = fluid.layers.reshape(cache["step_bias"], shape=[0, -1])
            kb.stop_gradient = True
            lengths = fluid.layers.scale(cache["pos"], bias=1.0)
            lengths.stop_gradient = True
            ctxt = fluid.layers.flash_decode_paged_attention(
                q, cache["k"], cache["v"], cache["tables"], key_bias=kb,
                scale=scale_, lengths=lengths,
                interpret=getattr(cfg, "flash_interpret", False),
            )
        else:
            rows_k = _gather_heads(cache["k"], cache["tables"], cfg)
            rows_v = _gather_heads(cache["v"], cache["tables"], cfg)
            scores = fluid.layers.matmul(
                q, rows_k, transpose_y=True, alpha=scale_
            )
            bias4 = fluid.layers.unsqueeze(cache["step_bias"], axes=[1])
            bias4.stop_gradient = True
            weights = fluid.layers.softmax(
                fluid.layers.elementwise_add(scores, bias4), axis=-1
            )
            ctxt = fluid.layers.matmul(weights, rows_v)
        ctxt = fluid.layers.transpose(ctxt, perm=[0, 2, 1, 3])
        ctxt = fluid.layers.reshape(ctxt, shape=[0, 0, cfg.hidden_size])
        return fluid.layers.fc(
            input=ctxt, size=cfg.hidden_size, num_flatten_dims=2,
            name="%s_out" % name,
        )
    if cache is not None:
        # prefill window: queries [1, heads, T, d] against the slot's
        # full updated row [1, heads, max_len, d] under the FED
        # [T, max_len] additive bias (0 on cache position j <= offset+i
        # for window query i, -1e4 beyond) — the causal mask shifted by
        # the runtime offset, which must stay out of the compiled shape.
        # Dense by design even for flash configs: the causal flash
        # kernel assumes an aligned q/k diagonal, and the window×row
        # product is the decode-step regime, not the [T, T] prefill one.
        scale_ = 1.0 / math.sqrt(d_head)
        scores = fluid.layers.matmul(q, k, transpose_y=True, alpha=scale_)
        bias4 = fluid.layers.unsqueeze(cache["resume_bias"], axes=[1])
        bias4.stop_gradient = True
        weights = fluid.layers.softmax(
            fluid.layers.elementwise_add(scores, bias4), axis=-1
        )
        ctxt = fluid.layers.matmul(weights, v)
        ctxt = fluid.layers.transpose(ctxt, perm=[0, 2, 1, 3])
        ctxt = fluid.layers.reshape(ctxt, shape=[0, 0, cfg.hidden_size])
        return fluid.layers.fc(
            input=ctxt, size=cfg.hidden_size, num_flatten_dims=2,
            name="%s_out" % name,
        )
    if use_flash is None:
        _sq = q_in.shape[1] if len(q_in.shape) >= 2 else -1
        use_flash = flash_engages(
            cfg, key_bias, seq_len=None if _sq in (-1, None) else int(_sq)
        )
    else:
        # the kernel still needs the key-side mask to ride along
        use_flash = bool(use_flash) and key_bias is not None
    import warnings

    if (key_bias is not None and not use_flash and attn_bias is None
            and not getattr(cfg, "_warned_flash_mask_drop", False)):
        # the builder prepared ONLY the key-only mask (flash path) but the
        # dense branch is about to run without any attn_bias: causal +
        # padding masking would be silently dropped (ADVICE r5)
        warnings.warn(
            "flash attention resolved off for %r but only a key-only mask "
            "was built: the dense fallback runs UNMASKED. Pass the "
            "builder's resolved use_flash down, or build a dense attn_bias "
            "for the fallback." % name, stacklevel=2)
        cfg._warned_flash_mask_drop = True  # once per config, not per layer
    # warn also for the other mismatch — an EXPLICIT True with no mask to
    # ride the kernel; "auto" choosing dense is working policy
    if (getattr(cfg, "use_flash_attention", False) is True and not use_flash
            and not getattr(cfg, "_warned_flash_fallback", False)):
        warnings.warn(
            "use_flash_attention=True but no key_bias/input_mask was "
            "built: falling back to dense attention", stacklevel=2)
        cfg._warned_flash_fallback = True  # once per config, not per layer
    if use_flash:
        # ``causal`` rides the kernel flag instead of a dense [T, T] bias;
        # attention dropout runs inside the kernel (per-step seed from the
        # executor key stream)
        ctxt = fluid.layers.flash_attention(
            q, k, v, key_bias=key_bias, causal=causal,
            scale=1.0 / math.sqrt(d_head),
            dropout_rate=cfg.attention_dropout, is_test=cfg.is_test,
            # tests force the Pallas kernels off-TPU via this cfg flag
            interpret=getattr(cfg, "flash_interpret", False),
        )
    else:
        scores = fluid.layers.matmul(
            q, k, transpose_y=True, alpha=1.0 / math.sqrt(d_head)
        )
        if attn_bias is not None:
            scores = fluid.layers.elementwise_add(scores, attn_bias)
        weights = fluid.layers.softmax(scores, axis=-1)
        weights = _dropout(weights, cfg.attention_dropout, cfg.is_test)
        ctxt = fluid.layers.matmul(weights, v)  # [N, heads, S, d_head]
    ctxt = fluid.layers.transpose(ctxt, perm=[0, 2, 1, 3])
    ctxt = fluid.layers.reshape(ctxt, shape=[0, 0, cfg.hidden_size])
    return fluid.layers.fc(
        input=ctxt, size=cfg.hidden_size, num_flatten_dims=2,
        name="%s_out" % name,
    )


def _ffn(x, cfg, name):
    h = fluid.layers.fc(
        input=x, size=cfg.intermediate_size, num_flatten_dims=2,
        act="gelu", name="%s_fc0" % name,
    )
    return fluid.layers.fc(
        input=h, size=cfg.hidden_size, num_flatten_dims=2,
        name="%s_fc1" % name,
    )


def encoder_layer(x, attn_bias, cfg, name, key_bias=None, use_flash=None):
    attn = multi_head_attention(x, x, attn_bias, cfg, "%s_att" % name,
                                key_bias=key_bias, use_flash=use_flash)
    attn = _dropout(attn, cfg.hidden_dropout, cfg.is_test)
    x = fluid.layers.layer_norm(
        fluid.layers.elementwise_add(x, attn), begin_norm_axis=2,
        name="%s_ln1" % name,
    )
    ff = _dropout(_ffn(x, cfg, "%s_ffn" % name), cfg.hidden_dropout, cfg.is_test)
    return fluid.layers.layer_norm(
        fluid.layers.elementwise_add(x, ff), begin_norm_axis=2,
        name="%s_ln2" % name,
    )


def bert_encoder(src_ids, pos_ids, sent_ids, input_mask, cfg):
    """Returns (sequence_output [N,S,H], pooled_output [N,H]).

    ``input_mask``: [N, S, 1] float32, 1.0 for real tokens.
    """
    emb = fluid.layers.embedding(
        input=src_ids, size=[cfg.vocab_size, cfg.hidden_size],
        param_attr=fluid.ParamAttr(name="word_embedding"),
    )
    pos = fluid.layers.embedding(
        input=pos_ids, size=[cfg.max_position_embeddings, cfg.hidden_size],
        param_attr=fluid.ParamAttr(name="pos_embedding"),
    )
    sent = fluid.layers.embedding(
        input=sent_ids, size=[cfg.type_vocab_size, cfg.hidden_size],
        param_attr=fluid.ParamAttr(name="sent_embedding"),
    )
    emb = fluid.layers.elementwise_add(
        fluid.layers.elementwise_add(emb, pos), sent
    )
    emb = fluid.layers.layer_norm(emb, begin_norm_axis=2, name="emb_ln")
    emb = _dropout(emb, cfg.hidden_dropout, cfg.is_test)

    mask_t = fluid.layers.transpose(input_mask, perm=[0, 2, 1])
    attn_mask = fluid.layers.matmul(input_mask, mask_t)  # [N, S, S]
    attn_bias = mask_to_bias(attn_mask)
    # resolve the flash policy ONCE here (the dense attn_bias above is
    # always built, so a fallback stays masked either way) and pass the
    # decision down — the attention helper must never re-derive it from a
    # possibly-dynamic query dim (ADVICE r5)
    _s = src_ids.shape[1] if len(src_ids.shape) >= 2 else -1
    use_flash = flash_wanted(
        cfg, seq_len=None if _s in (-1, None) else int(_s)
    )
    key_bias = mask_to_key_bias(input_mask) if use_flash else None

    x = emb
    for i in range(cfg.num_layers):
        x = encoder_layer(x, attn_bias, cfg, "layer_%d" % i,
                          key_bias=key_bias, use_flash=use_flash)

    first_tok = fluid.layers.slice(x, axes=[1], starts=[0], ends=[1])
    first_tok = fluid.layers.reshape(first_tok, shape=[-1, cfg.hidden_size])
    pooled = fluid.layers.fc(
        input=first_tok, size=cfg.hidden_size, act="tanh", name="pooler"
    )
    return x, pooled


def build_bert_classifier(cfg, seq_len, num_classes=2, learning_rate=2e-5,
                          use_amp=False):
    """Sequence-classification fine-tune graph (config 3 / SQuAD-style head).

    ``use_amp``: bf16 mixed precision via the AMP program rewrite — the
    attention/FFN matmuls run bf16 on the MXU, layer-norm statistics and
    the Adam update stay fp32 (gray-list propagation).

    Returns (main, startup, feeds, avg_loss, acc)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        src_ids = fluid.layers.data(name="src_ids", shape=[seq_len, 1], dtype="int64")
        pos_ids = fluid.layers.data(name="pos_ids", shape=[seq_len, 1], dtype="int64")
        sent_ids = fluid.layers.data(name="sent_ids", shape=[seq_len, 1], dtype="int64")
        input_mask = fluid.layers.data(
            name="input_mask", shape=[seq_len, 1], dtype="float32"
        )
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        _, pooled = bert_encoder(src_ids, pos_ids, sent_ids, input_mask, cfg)
        pooled = _dropout(pooled, cfg.hidden_dropout, cfg.is_test)
        logits = fluid.layers.fc(input=pooled, size=num_classes, name="cls")
        loss = fluid.layers.softmax_with_cross_entropy(logits, label)
        avg_loss = fluid.layers.mean(loss)
        acc = fluid.layers.accuracy(
            input=fluid.layers.softmax(logits), label=label
        )
        opt = fluid.optimizer.Adam(learning_rate=learning_rate)
        if use_amp:
            from paddle_tpu.fluid.contrib import mixed_precision as _mp

            opt = _mp.decorate(opt)
        opt.minimize(avg_loss)
    feeds = [src_ids, pos_ids, sent_ids, input_mask, label]
    return main, startup, feeds, avg_loss, acc
