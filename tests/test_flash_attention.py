"""Pallas flash-attention kernel tests (interpret mode on CPU).

The kernel is the TPU-native answer to the reference's fused
multihead_matmul CUDA kernel: online-softmax attention that never
materializes the [S, S] score matrix in HBM. Checked against the pure
jnp reference for plain / causal / key-masked cases, plus gradient
parity through the custom VJP."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import flash_attention
from paddle_tpu.kernels.flash_attention import (_fallback_keep,
                                                reference_attention)


def _inputs(B=2, N=2, S=64, D=16, seed=0):
    rs = np.random.RandomState(seed)
    q = rs.randn(B, N, S, D).astype("float32") * 0.5
    k = rs.randn(B, N, S, D).astype("float32") * 0.5
    v = rs.randn(B, N, S, D).astype("float32") * 0.5
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def test_flash_matches_reference():
    q, k, v = _inputs()
    out = flash_attention(q, k, v, interpret=True)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_causal():
    q, k, v = _inputs(seed=1)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # causality: perturbing a future key must not change past outputs
    k2 = k.at[:, :, -1, :].add(10.0)
    v2 = v.at[:, :, -1, :].add(10.0)
    out2 = flash_attention(q, k2, v2, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, :, :-1]),
                               np.asarray(out2[:, :, :-1]),
                               rtol=1e-5, atol=1e-5)


def test_flash_key_padding_mask():
    B, N, S, D = 2, 2, 64, 16
    q, k, v = _inputs(B, N, S, D, seed=2)
    valid = 40
    key_bias = np.zeros((B * N, S), np.float32)
    key_bias[:, valid:] = -1e9
    out = flash_attention(q, k, v, key_bias=jnp.asarray(key_bias),
                          interpret=True)
    ref = reference_attention(
        q, k, v,
        bias=jnp.asarray(key_bias).reshape(B, N, 1, S),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # masked keys truly dead: output == attention over the valid prefix
    ref_trunc = reference_attention(q, k[:, :, :valid], v[:, :, :valid])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_trunc),
                               rtol=2e-5, atol=2e-5)


def test_flash_non_multiple_seq_padding():
    """S not divisible by the block size exercises the internal pad+mask."""
    q, k, v = _inputs(S=56, seed=3)
    out = flash_attention(q, k, v, interpret=True)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_grad_matches_reference():
    q, k, v = _inputs(S=32, seed=4)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_bf16():
    q, k, v = _inputs(seed=5)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_cpu_fallback_is_reference():
    """Without interpret, non-TPU backends transparently use the jnp
    reference (same signature, models stay portable)."""
    q, k, v = _inputs(seed=6)
    out = flash_attention(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6)


@pytest.mark.slow  # ~16 s; fast equivalents: cpu_fallback_is_reference + gpt_flash_matches_dense (test_gpt) cover the flag->reference routing and flag-path model parity
def test_bert_flash_flag_matches_dense_path():
    """BERT with use_flash_attention must produce the same classifier loss
    as the dense path on padded batches (on CPU the flag routes through
    the jnp reference — kernel parity itself is covered above)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    def run(flash):
        cfg = bert.BertConfig.tiny(
            hidden_dropout=0.0, attention_dropout=0.0,
            use_flash_attention=flash,
        )
        S, N = 16, 4
        with fluid.unique_name.guard():
            main, startup, feeds, loss, acc = bert.build_bert_classifier(
                cfg, S, learning_rate=1e-3
            )
        main.random_seed = startup.random_seed = 33
        scope = fluid.core.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        rs = np.random.RandomState(0)
        mask = np.ones((N, S, 1), "float32")
        mask[:, 10:] = 0.0  # padded tail
        feed = {
            "src_ids": rs.randint(0, cfg.vocab_size, (N, S, 1)).astype("int64"),
            "pos_ids": np.tile(np.arange(S)[None, :, None],
                               (N, 1, 1)).astype("int64"),
            "sent_ids": np.zeros((N, S, 1), "int64"),
            "input_mask": mask,
            "label": rs.randint(0, 2, (N, 1)).astype("int64"),
        }
        out = []
        for _ in range(3):
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            out.append(float(np.asarray(lv).ravel()[0]))
        return out

    dense = run(False)
    flash = run(True)
    np.testing.assert_allclose(flash, dense, rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # ~14 s; fast parity retained: bert flag-path + kernel-level tests
def test_transformer_flash_flag_matches_dense_path():
    """Transformer NMT with use_flash_attention (causal decoder self-attn
    via the kernel's causal flag, padding via key-only biases) must match
    the dense-mask path's masked training loss on padded batches."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import transformer as tfm

    S, T, N = 8, 8, 4

    def run(flash):
        cfg = tfm.TransformerConfig(
            src_vocab=30, tgt_vocab=30, hidden_size=16, num_heads=2,
            num_layers=1, intermediate_size=32, dropout=0.0,
            label_smooth=0.0, use_flash_attention=flash,
        )
        with fluid.unique_name.guard():
            main, startup, feeds, loss = tfm.build_transformer_train(
                cfg, S, T, learning_rate=0.1
            )
        main.random_seed = startup.random_seed = 44
        scope = fluid.core.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        rs = np.random.RandomState(0)
        src_mask = np.ones((N, S, 1), "float32")
        src_mask[:, 6:] = 0.0
        tgt_mask = np.ones((N, T, 1), "float32")
        tgt_mask[:, 5:] = 0.0
        feed = {
            "src_ids": rs.randint(2, 30, (N, S, 1)).astype("int64"),
            "src_pos": np.tile(np.arange(S)[None, :, None],
                               (N, 1, 1)).astype("int64"),
            "src_mask": src_mask,
            "tgt_ids": rs.randint(2, 30, (N, T, 1)).astype("int64"),
            "tgt_pos": np.tile(np.arange(T)[None, :, None],
                               (N, 1, 1)).astype("int64"),
            "tgt_mask": tgt_mask,
            "labels": rs.randint(2, 30, (N, T, 1)).astype("int64"),
        }
        out = []
        for _ in range(3):
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            out.append(float(np.asarray(lv).ravel()[0]))
        return out

    dense = run(False)
    flash = run(True)
    np.testing.assert_allclose(flash, dense, rtol=1e-4, atol=1e-5)


def test_flash_cross_attention_different_kv_length():
    """Cross attention (decoder->encoder): S_q != S_kv, with a key-side
    padding mask on the encoder length."""
    B, N, Sq, Sk, D = 2, 2, 24, 40, 16
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(B, N, Sq, D).astype("float32") * 0.5)
    k = jnp.asarray(rs.randn(B, N, Sk, D).astype("float32") * 0.5)
    v = jnp.asarray(rs.randn(B, N, Sk, D).astype("float32") * 0.5)
    kb = np.zeros((B, Sk), np.float32)
    kb[:, 30:] = -1e9
    out = flash_attention(q, k, v, key_bias=jnp.asarray(kb), interpret=True)
    ref = reference_attention(
        q, k, v,
        bias=jnp.broadcast_to(jnp.asarray(kb)[:, None, None, :],
                              (B, N, 1, Sk)),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_causal_with_key_bias_and_odd_length():
    """The decoder-self configuration: causal flag combined with a key
    padding bias, at a non-multiple-of-8 length (exercising the internal
    pad path), through the KERNEL (interpret mode)."""
    B, N, S, D = 2, 2, 21, 16
    rs = np.random.RandomState(11)
    q = jnp.asarray(rs.randn(B, N, S, D).astype("float32") * 0.5)
    k = jnp.asarray(rs.randn(B, N, S, D).astype("float32") * 0.5)
    v = jnp.asarray(rs.randn(B, N, S, D).astype("float32") * 0.5)
    kb = np.zeros((B, S), np.float32)
    kb[:, 15:] = -1e9
    out = flash_attention(q, k, v, key_bias=jnp.asarray(kb), causal=True,
                          interpret=True)
    ref = reference_attention(
        q, k, v,
        bias=jnp.broadcast_to(jnp.asarray(kb)[:, None, None, :],
                              (B, N, 1, S)),
        causal=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # causal cross-length must refuse loudly on every backend
    with pytest.raises(ValueError):
        flash_attention(q[:, :, :8], k, v, causal=True)


# ---------------------------------------------------------------------------
# Pallas backward (VERDICT r4 task 3): dq/dk/dv via the two-kernel
# recompute backward, dbias via blockwise accumulation — gradient parity
# against jax.grad through the dense reference for every bias mode.
# ---------------------------------------------------------------------------


def _grad_parity(flash_fn, ref_fn, args, rtol=2e-4, atol=2e-5):
    gf = jax.grad(lambda *a: jnp.sum(flash_fn(*a) ** 2),
                  argnums=tuple(range(len(args))))(*args)
    gr = jax.grad(lambda *a: jnp.sum(ref_fn(*a) ** 2),
                  argnums=tuple(range(len(args))))(*args)
    for i, (a, b) in enumerate(zip(gf, gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol,
                                   err_msg="grad argnum %d" % i)


def test_flash_grad_key_bias():
    """dkey_bias accumulates in the dkv kernel ([BK] colsum per block)."""
    q, k, v = _inputs(S=48, seed=7)
    B, N, S = q.shape[0], q.shape[1], q.shape[2]
    rs = np.random.RandomState(8)
    kb = jnp.asarray(rs.randn(B * N, S).astype("float32"))

    _grad_parity(
        lambda q, k, v, kb: flash_attention(q, k, v, key_bias=kb,
                                            interpret=True),
        lambda q, k, v, kb: reference_attention(
            q, k, v, bias=kb.reshape(B, N, 1, S)),
        (q, k, v, kb),
    )


@pytest.mark.parametrize("bias_shape", [
    "2d",        # [S, S]            -> G=1 (accumulated across ALL heads)
    "full",      # [B, N, S, S]      -> G=B*N (no cross-program accumulation)
    "batch",     # [B, 1, S, S]      -> G=B (accumulated across heads of a batch)
    "head",      # [1, N, S, S]      -> head-major role swap
])
def test_flash_grad_general_bias(bias_shape):
    q, k, v = _inputs(B=2, N=3, S=32, D=8, seed=11)
    B, N, S = q.shape[0], q.shape[1], q.shape[2]
    rs = np.random.RandomState(12)
    shape = {
        "2d": (S, S),
        "full": (B, N, S, S),
        "batch": (B, 1, S, S),
        "head": (1, N, S, S),
    }[bias_shape]
    bias = jnp.asarray(rs.randn(*shape).astype("float32") * 0.3)

    _grad_parity(
        lambda q, k, v, b: flash_attention(q, k, v, bias=b, interpret=True),
        lambda q, k, v, b: reference_attention(
            q, k, v, bias=jnp.broadcast_to(
                b.reshape((1,) * (4 - b.ndim) + b.shape), (B, N, S, S))),
        (q, k, v, bias),
    )


def test_flash_forward_general_bias_matches_reference():
    q, k, v = _inputs(B=2, N=2, S=40, seed=13)
    B, N, S = q.shape[0], q.shape[1], q.shape[2]
    rs = np.random.RandomState(14)
    bias = jnp.asarray(rs.randn(S, S).astype("float32"))
    out = flash_attention(q, k, v, bias=bias, interpret=True)
    ref = reference_attention(q, k, v, bias=bias[None, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_flash_grad_causal_with_bias_and_key_bias():
    """All masking paths at once + odd (padded) length: causal + general
    bias + key padding mask, S not a block multiple."""
    q, k, v = _inputs(B=1, N=2, S=37, D=8, seed=15)
    B, N, S = q.shape[0], q.shape[1], q.shape[2]
    rs = np.random.RandomState(16)
    bias = jnp.asarray(rs.randn(S, S).astype("float32") * 0.2)
    mask = (np.arange(S) < 30).astype("float32")   # last 7 keys padded
    kb = jnp.asarray(np.tile((mask - 1.0) * 1e4, (B * N, 1)))

    _grad_parity(
        lambda q, k, v, b: flash_attention(q, k, v, key_bias=kb, bias=b,
                                           causal=True, interpret=True),
        lambda q, k, v, b: reference_attention(
            q, k, v,
            bias=kb.reshape(B, N, 1, S) + jnp.broadcast_to(
                b[None, None], (B, N, S, S)),
            causal=True),
        (q, k, v, bias),
    )


def test_flash_grad_cross_attention():
    """Sq != Sk, both padded to different block multiples."""
    rs = np.random.RandomState(17)
    B, N, Sq, Sk, D = 2, 2, 21, 50, 8
    q = jnp.asarray(rs.randn(B, N, Sq, D).astype("float32") * 0.5)
    k = jnp.asarray(rs.randn(B, N, Sk, D).astype("float32") * 0.5)
    v = jnp.asarray(rs.randn(B, N, Sk, D).astype("float32") * 0.5)

    _grad_parity(
        lambda q, k, v: flash_attention(q, k, v, interpret=True),
        lambda q, k, v: reference_attention(q, k, v),
        (q, k, v),
    )


def test_flash_grad_bf16_runs():
    """bf16 inputs: kernels accumulate fp32; loose parity vs the bf16
    dense reference."""
    q, k, v = _inputs(S=32, seed=18)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))

    _grad_parity(
        lambda q, k, v: flash_attention(q, k, v, interpret=True),
        lambda q, k, v: reference_attention(q, k, v),
        (q, k, v), rtol=5e-2, atol=5e-2,
    )


def test_flash_backward_never_materializes_scores():
    """Structural: the jaxpr of the flash grad must contain no [S, S]
    intermediate outside the Pallas calls (the whole point of task 3)."""
    q, k, v = _inputs(B=1, N=1, S=256, D=16, seed=19)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=True) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    S = 256
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert not (len(shape) >= 2 and shape[-1] == S and
                        shape[-2] == S), (
                "non-Pallas [S,S] intermediate: %s -> %s" % (eqn.primitive,
                                                             shape))


@pytest.mark.slow  # ~8 s; fast in-file equivalents: flash_grad_matches_reference + the flash_dropout_kernel_matches_fallback grid prove the same forward/backward kernels; gpt_flash_matches_dense (test_gpt) keeps a fast model-level flag-path check
def test_bert_trains_through_flash_kernel():
    """End-to-end: a tiny BERT fine-tune step runs THROUGH the Pallas
    kernels (interpret mode) — forward and the new two-kernel backward —
    and the loss decreases (VERDICT r4 task 3 acceptance)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0,
                               use_flash_attention=True)
    cfg.flash_interpret = True
    S, B = 24, 4
    main, startup, feeds, loss, acc = bert.build_bert_classifier(
        cfg, S, learning_rate=1e-3)
    assert any(op.type == "flash_attention" for b in main.blocks
               for op in b.ops), "kernel path not taken"
    rs = np.random.RandomState(0)
    feed = {
        "src_ids": rs.randint(0, cfg.vocab_size, (B, S, 1)).astype("int64"),
        "pos_ids": np.tile(np.arange(S)[None, :, None], (B, 1, 1)).astype("int64"),
        "sent_ids": np.zeros((B, S, 1), "int64"),
        "input_mask": np.ones((B, S, 1), "float32"),
        "label": rs.randint(0, 2, (B, 1)).astype("int64"),
    }
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    losses = []
    for _ in range(4):
        (l,) = exe.run(main, feed=feed, fetch_list=[loss])
        losses.append(float(np.asarray(l).ravel()[0]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses


def test_flash_engages_with_dropout_and_warns_without_mask():
    """Round 5: attention dropout runs INSIDE the kernel, so a default
    training config (dropout 0.1) engages flash; the fallback warning
    (ADVICE r4) remains only for the genuinely unsupported no-mask case."""
    import warnings
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny(use_flash_attention=True)  # dropout 0.1
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        main, _, _, _, _ = bert.build_bert_classifier(
            cfg, 16, learning_rate=1e-3
        )
    assert not [x for x in w if "falling back" in str(x.message)]
    ops = [op.type for op in main.global_block().ops]
    assert "flash_attention" in ops  # dropout config rides the kernel
    fa = [op for op in main.global_block().ops
          if op.type == "flash_attention"][0]
    assert abs(fa.attr("dropout_rate") - 0.1) < 1e-9

    # no key_bias -> dense fallback with ONE warning
    cfg2 = bert.BertConfig.tiny(use_flash_attention=True)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        main2 = fluid.Program()
        with fluid.program_guard(main2, fluid.Program()):
            x = fluid.layers.data(
                "x", shape=[-1, 16, cfg2.hidden_size], dtype="float32"
            )
            bert.multi_head_attention(x, x, None, cfg2, "att", key_bias=None)
    msgs = [x for x in w if "falling back to dense" in str(x.message)]
    assert len(msgs) == 1


def _dropout_case(bias=False, causal=False, S=160, rate=0.25, seed=11):
    q, k, v = _inputs(B=1, N=2, S=S, D=16, seed=3)
    kw = dict(dropout_rate=rate, dropout_seed=seed, causal=causal)
    if bias:
        rs = np.random.RandomState(5)
        kw["bias"] = jnp.asarray(
            rs.randn(1, 2, S, S).astype("float32") * 0.2
        )
    rs = np.random.RandomState(6)
    kw["key_bias"] = jnp.asarray(rs.randn(2, S).astype("float32") * 0.1)
    return q, k, v, kw


@pytest.mark.parametrize("bias,causal", [(False, False), (True, False),
                                         (False, True), (True, True)])
def test_flash_dropout_kernel_matches_fallback(bias, causal):
    """The stateless hash mask must be BIT-IDENTICAL between the Pallas
    kernels (interpret) and the dense fallback — forward and gradients."""
    q, k, v, kw = _dropout_case(bias=bias, causal=causal)

    def run(interpret):
        return flash_attention(
            q, k, v, interpret=interpret, **kw
        )

    np.testing.assert_allclose(run(True), run(None), rtol=2e-4, atol=2e-4)

    # key_bias rides the grad argnums too: the dkb-under-dropout
    # accumulation in the dkv kernel is otherwise unverified against the
    # fallback (a missing inv_keep there would pass every other check)
    args = (q, k, v) + ((kw["bias"],) if bias else ()) + (kw["key_bias"],)

    def loss(interpret):
        def f(*a):
            kw2 = dict(kw)
            if bias:
                kw2["bias"] = a[3]
            kw2["key_bias"] = a[-1]
            return (flash_attention(
                a[0], a[1], a[2], interpret=interpret, **kw2) ** 2).sum()
        return f

    gk = jax.grad(loss(True), argnums=tuple(range(len(args))))(*args)
    gf = jax.grad(loss(None), argnums=tuple(range(len(args))))(*args)
    for a, b in zip(gk, gf):
        np.testing.assert_allclose(a, b, rtol=4e-3, atol=4e-3)


def test_flash_dropout_per_head_bias_swap_parity():
    """A per-head bias shared across the batch ([1, N, Sq, Sk]) triggers
    the head-major role swap; with dropout the hash head-ids are remapped
    inside the kernels (no B-fold bias expansion), so kernel and fallback
    must still drop the exact same entries — forward and grads."""
    B, N, S, D = 3, 2, 64, 16
    q, k, v = _inputs(B=B, N=N, S=S, D=D, seed=12)
    rs = np.random.RandomState(13)
    bias = jnp.asarray(rs.randn(1, N, S, S).astype("float32") * 0.2)
    kw = dict(bias=bias, dropout_rate=0.3, dropout_seed=21)

    ok = flash_attention(q, k, v, interpret=True, **kw)
    of = flash_attention(q, k, v, **kw)  # dense fallback
    np.testing.assert_allclose(ok, of, rtol=2e-4, atol=2e-4)

    def loss(interpret):
        def f(q, k, v, b):
            return (flash_attention(
                q, k, v, bias=b, dropout_rate=0.3, dropout_seed=21,
                interpret=interpret) ** 2).sum()
        return f

    gk = jax.grad(loss(True), argnums=(0, 1, 2, 3))(q, k, v, bias)
    gf = jax.grad(loss(None), argnums=(0, 1, 2, 3))(q, k, v, bias)
    for name, a, b in zip("q k v bias".split(), gk, gf):
        assert a.shape == b.shape, (name, a.shape, b.shape)
        np.testing.assert_allclose(a, b, rtol=4e-3, atol=4e-3,
                                   err_msg=name)


def test_flash_dropout_statistics_and_seed():
    """Drop fraction ~= rate; same seed reproduces; seeds decorrelate;
    rate=0 equals the dense reference exactly."""
    q, k, v, kw = _dropout_case(rate=0.5, seed=1)
    kw.pop("key_bias")
    o1 = flash_attention(q, k, v, interpret=True, **kw)
    o1b = flash_attention(q, k, v, interpret=True, **kw)
    np.testing.assert_array_equal(o1, o1b)  # deterministic per seed
    kw["dropout_seed"] = 2
    o2 = flash_attention(q, k, v, interpret=True, **kw)
    assert not np.allclose(o1, o2)

    # fraction of dropped attention entries ~= rate (hash uniformity):
    # count via the fallback mask helper the kernels share
    keep = _fallback_keep(
        4, 4, 128, 128, jnp.asarray(9.0, jnp.float32), 0.5
    )
    frac = float(jnp.mean(keep))
    assert abs(frac - 0.5) < 0.01, frac

    o0 = flash_attention(
        q, k, v, dropout_rate=0.0, interpret=True
    )
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(o0, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # ~8 s; fast equivalents: flash_dropout_statistics_and_seed + the flash_dropout_kernel_matches_fallback grid
def test_flash_dropout_keeps_expectation():
    """1/keep upscaling is unbiased: E_seed[mask/keep] -> 1 per entry, and
    the seed-averaged attention output converges toward the dense one
    (1/sqrt(n) — checked as 16-seed error < 2-seed error)."""
    rate, keep = 0.3, 0.7
    masks = jnp.stack([
        _fallback_keep(2, 2, 64, 64, jnp.asarray(float(s), jnp.float32),
                       rate).astype(jnp.float32)
        for s in range(32)
    ])
    per_entry = masks.mean(0) / keep   # E[mask]/keep ~= 1
    assert abs(float(per_entry.mean()) - 1.0) < 0.01
    assert float(jnp.abs(per_entry - 1.0).mean()) < 0.12  # 32-draw noise

    q, k, v = _inputs(B=2, N=2, S=64, D=16, seed=8)
    dense = reference_attention(q, k, v)

    def err(n):
        mean = jnp.stack([
            flash_attention(q, k, v, dropout_rate=rate, dropout_seed=s,
                            interpret=True)
            for s in range(n)
        ]).mean(0)
        return float(jnp.abs(mean - dense).mean() / jnp.abs(dense).mean())

    assert err(16) < err(2) * 0.75  # converging toward the dense output


@pytest.mark.slow  # ~9 s; fast equivalents: the flash_dropout_kernel_matches_fallback grid + flash_grad_matches_reference (bert_trains_through_flash_kernel is slow-tier now too)
def test_bert_trains_through_flash_with_dropout():
    """End-to-end: default-dropout BERT config trains THROUGH the kernel
    (interpret mode) with finite, decreasing loss."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny(use_flash_attention=True)
    cfg.flash_interpret = True  # force Pallas interpreter off-TPU
    assert cfg.attention_dropout > 0.0
    main, startup, feeds, loss, acc = bert.build_bert_classifier(
        cfg, 16, learning_rate=1e-2
    )
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rs = np.random.RandomState(0)
    feed = {
        "src_ids": rs.randint(0, cfg.vocab_size, (4, 16, 1)).astype("int64"),
        "pos_ids": np.tile(np.arange(16)[None, :, None], (4, 1, 1)).astype("int64"),
        "sent_ids": np.zeros((4, 16, 1), "int64"),
        "input_mask": np.ones((4, 16, 1), "float32"),
        "label": rs.randint(0, 2, (4, 1)).astype("int64"),
    }
    losses = []
    for _ in range(8):
        out = exe.run(main, feed=feed, fetch_list=[loss])
        losses.append(float(np.asarray(out[0]).ravel()[0]))
    assert all(np.isfinite(losses)), losses
    assert min(losses[4:]) < losses[0], losses


@pytest.mark.parametrize("shape", [
    (1, 2, 1, 64, 33),     # Sq=1 decode step vs long KV (pad 1 -> 8)
    (2, 2, 9, 9, 20),      # odd head dim, tiny odd seqs
    (1, 1, 300, 260, 16),  # multi-block on BOTH axes with ragged tails
])
def test_flash_edge_shapes(shape):
    """Kernel-path parity on awkward geometries: the single-query decode
    shape GPT-style generation hits, non-multiple-of-8 head dims, and
    multi-block padding on both seq axes."""
    B, N, Sq, Sk, D = shape
    rs = np.random.RandomState(hash(shape) % 2**31)
    q = jnp.asarray(rs.rand(B, N, Sq, D).astype("float32") * 0.5)
    k = jnp.asarray(rs.rand(B, N, Sk, D).astype("float32") * 0.5)
    v = jnp.asarray(rs.rand(B, N, Sk, D).astype("float32") * 0.5)
    out = flash_attention(q, k, v, interpret=True)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    # gradients too on the decode shape (the generation-time case)
    if Sq == 1:
        g = jax.grad(lambda a, b, c: jnp.sum(
            flash_attention(a, b, c, interpret=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: jnp.sum(
            reference_attention(a, b, c) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


def test_residual_backward_matches_vjp_with_dropout():
    """flash_attention_bwd_from_residuals (the fluid grad-op fast path:
    backward from SAVED out/lse, no forward replay) must produce grads
    IDENTICAL to differentiating through the kernel entry — including
    with live dropout, where both sides must hash the same keep-mask
    from the same RAW seed (the residual path re-normalizes it through
    _norm_seed exactly as the forward did)."""
    from paddle_tpu.kernels.flash_attention import (
        flash_attention_bwd_from_residuals, flash_attention_lse)

    rs = np.random.RandomState(5)
    B, N, S, D = 2, 3, 16, 8
    q = jnp.asarray(rs.randn(B, N, S, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, N, S, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, N, S, D), jnp.float32)
    key_bias = jnp.asarray(
        np.where(rs.rand(B, S) > 0.2, 0.0, -1e4), jnp.float32)
    g = jnp.asarray(rs.randn(B, N, S, D), jnp.float32)
    raw_seed = jnp.asarray([[12345.0]], jnp.float32)

    def fwd(q, k, v, kb):
        out, _lse = flash_attention_lse(
            q, k, v, key_bias=kb, causal=True, dropout_rate=0.3,
            dropout_seed=raw_seed, interpret=True)
        return out

    out, vjp = jax.vjp(fwd, q, k, v, key_bias)
    dq0, dk0, dv0, dkb0 = vjp(g)
    _out2, lse = flash_attention_lse(
        q, k, v, key_bias=key_bias, causal=True, dropout_rate=0.3,
        dropout_seed=raw_seed, interpret=True)
    dq1, dk1, dv1, dkb1 = flash_attention_bwd_from_residuals(
        q, k, v, key_bias, raw_seed, out, lse, g,
        causal=True, dropout_rate=0.3, interpret=True)
    np.testing.assert_allclose(dq1, dq0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dk1, dk0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dv1, dv0, rtol=1e-5, atol=1e-5)
    # vjp reduces dkey_bias to the raw [B, S] shape; the residual entry
    # returns the kernels' canonical [B*N, S] — same after head-summing
    np.testing.assert_allclose(
        np.asarray(dkb1).reshape(B, N, S).sum(1), dkb0, rtol=1e-5, atol=1e-5)


# -- the sweeps visit only the blocks a causal mask leaves --------------------


def flash_attention_module():
    """The module itself: the package exports the function under the
    module's name."""
    import importlib

    return importlib.import_module("paddle_tpu.kernels.flash_attention")


def _causal_as_bias(S):
    """The lower-triangular mask as a general bias: the ``causal=False``
    call that carries it visits every block."""
    return jnp.asarray(np.where(np.tril(np.ones((S, S), bool)), 0.0, -1e30),
                       jnp.float32)


@pytest.mark.parametrize("dropout", [0.0, 0.25], ids=["nodrop", "drop"])
@pytest.mark.parametrize("with_key_bias", [False, True],
                         ids=["nokb", "keybias"])
@pytest.mark.parametrize("S", [256, 384, 1024])
def test_causal_sweep_matches_every_block_sweep(S, with_key_bias, dropout):
    """``causal=True`` (one program a head: dead blocks skipped, interior
    blocks unmasked) against the same attention with the mask passed as a
    bias, which visits all (S / 128)^2 blocks, a program a block: out,
    lse and every gradient."""
    fa = flash_attention_module()
    q, k, v = _inputs(B=1, N=2, S=S, D=16, seed=21)
    rs = np.random.RandomState(22)
    kb = jnp.asarray(rs.randn(2, S).astype("float32") * 0.3
                     if with_key_bias else np.zeros((2, S), "float32"))
    w_out = jnp.asarray(rs.randn(1, 2, S, 16).astype("float32"))
    w_lse = jnp.asarray(rs.randn(2, S).astype("float32"))
    kw = dict(dropout_rate=dropout, dropout_seed=5 if dropout else None,
              interpret=True)
    tril = _causal_as_bias(S)

    def both(causal):
        def f(q, k, v, kb):
            out, lse = fa.flash_attention_lse(
                q, k, v, key_bias=kb, causal=causal,
                bias=None if causal else tril, **kw)
            return (out * w_out).sum() + (lse * w_lse).sum(), (out, lse)
        grads, outs = jax.jit(jax.grad(
            f, argnums=(0, 1, 2, 3), has_aux=True))(q, k, v, kb)
        return outs + grads

    names = ("out", "lse", "dq", "dk", "dv", "dkey_bias")
    for name, a, b in zip(names, both(True), both(False)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("bias_rows", ["2d", "full"])
def test_causal_dbias_is_zero_on_dead_blocks(bias_rows):
    """Causal + general bias: the dkv kernel writes the bias gradient's
    column block whole, exact zeros on the rows of the q blocks above the
    diagonal, and the rest is the reference's gradient."""
    S, block = 384, 128
    q, k, v = _inputs(B=1, N=2, S=S, D=16, seed=23)
    rs = np.random.RandomState(24)
    shape = (S, S) if bias_rows == "2d" else (1, 2, S, S)
    bias = jnp.asarray(rs.randn(*shape).astype("float32") * 0.3)

    def wide(b):
        return jnp.broadcast_to(b.reshape((1,) * (4 - b.ndim) + b.shape),
                                (1, 2, S, S))

    got = jax.jit(jax.grad(lambda b: jnp.sum(flash_attention(
        q, k, v, bias=b, causal=True, interpret=True) ** 2)))(bias)
    want = jax.grad(lambda b: jnp.sum(reference_attention(
        q, k, v, bias=wide(b), causal=True) ** 2))(bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    got = np.asarray(got).reshape(-1, S, S)
    for ib in range(S // block):
        dead = got[:, ib * block:(ib + 1) * block, (ib + 1) * block:]
        assert not dead.any(), ib
    assert got[:, block:, :block].any()


@pytest.mark.parametrize("causal,q_len,kv_len,block_q,block_k,want", [
    (True, 1024, 1024, 128, 128, (28, 28, 8)),
    (False, 1024, 1024, 128, 128, (0, 64, 0)),
    (True, 4096, 4096, 128, 128, (496, 496, 32)),
    (True, 512, 512, 128, 64, None),
    (True, 512, 512, 64, 128, None),
    (True, 256, 512, 128, 128, None),
    (True, 40, 40, 40, 40, (0, 0, 1)),
])
def test_block_classes(causal, q_len, kv_len, block_q, block_k, want):
    """dead / interior / diagonal counts of the (q-block, kv-block) pairs,
    each pair checked against the dense lower-triangular mask; a pair one
    of whose indices is a program id is computed with the mask."""
    fa = flash_attention_module()
    got = fa.block_classes(causal, q_len, kv_len, block_q, block_k)
    if want is not None:
        assert (got["dead"], got["interior"], got["diagonal"]) == want
    mask = np.tril(np.ones((q_len, kv_len), bool)) if causal else \
        np.ones((q_len, kv_len), bool)
    dense = {"dead": 0, "interior": 0, "diagonal": 0}
    for ib in range(q_len // block_q):
        for kb in range(kv_len // block_k):
            blk = mask[ib * block_q:(ib + 1) * block_q,
                       kb * block_k:(kb + 1) * block_k]
            cls = ("interior" if blk.all() else
                   "diagonal" if blk.any() else "dead")
            dense[cls] += 1
            assert fa._block_class(causal, ib, kb, block_q, block_k) == cls
    assert got == dense
    traced = jnp.int32(0)
    assert fa._block_class(causal, traced, 0, block_q, block_k) == (
        "diagonal" if causal else "interior")


def _equations(jaxpr):
    """Every equation of ``jaxpr``, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _traced_pallas_calls(fn, *args):
    """The ``pallas_call`` equations of ``fn`` traced on ``args``."""
    return [eqn for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("causal,S,visited,total", [
    (True, 1024, 3 * 36, 3 * 64),
    (True, 384, 3 * 6, 3 * 9),
    (False, 384, 3 * 9, 3 * 9),
    # more blocks than a program unrolls: every pair is visited, masked
    (True, 2048, 3 * 256, 3 * 256),
])
def test_flash_blocks_counters(causal, S, visited, total):
    """One traced forward + backward bumps the two counters by the block
    pairs a head the three kernels visit / would visit unskipped."""
    from paddle_tpu.observability import registry

    seen = registry.counter("flash_blocks_visited")
    whole = registry.counter("flash_blocks_total")
    before = seen.value(), whole.value()
    q = jnp.zeros((1, 2, S, 16), jnp.float32)
    calls = _traced_pallas_calls(
        jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, interpret=True).sum(),
            argnums=(0, 1, 2)), q, q, q)
    assert [c.params["name"] for c in calls] == [
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    assert seen.value() - before[0] == visited
    assert whole.value() - before[1] == total


@pytest.mark.parametrize("causal,S,grids", [
    # BERT-shaped: key bias, no causal mask: a program a block
    (False, 384, [(4, 3), (4, 3), (4, 3)]),
    # one block: the diagonal one
    (True, 128, [(4, 1), (4, 1), (4, 1)]),
    # causal: a program holds its head's three blocks, classes are static
    (True, 384, [(4, 1), (4, 1), (4, 1)]),
    # more blocks than a program unrolls: a program a block, as before
    (True, 2048, [(4, 16), (4, 16), (4, 16)]),
])
def test_sweeps_are_static(causal, S, grids):
    """Every sweep's shape is fixed at trace time: no kernel holds a
    ``cond`` or a loop, none takes a scratch operand. Without ``causal``
    that is the op stream the kernels had before blocks were
    classified."""
    q = jnp.zeros((2, 2, S, 16), jnp.bfloat16)
    kb = jnp.zeros((2, S), jnp.float32)
    calls = _traced_pallas_calls(
        jax.grad(lambda q, k, v, kb: flash_attention(
            q, k, v, key_bias=kb, causal=causal,
            interpret=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3)), q, q, q, kb)
    assert [tuple(c.params["grid_mapping"].grid) for c in calls] == grids
    for call in calls:
        prims = {eqn.primitive.name
                 for eqn in _equations(call.params["jaxpr"])}
        assert not prims & {"cond", "while", "scan"}, call.params["name"]
        assert call.params["grid_mapping"].num_scratch_operands == 0


@pytest.mark.parametrize("causal,bias,S,d,itemsize,want", [
    (True, None, 1024, 64, 2, True),        # GPT-2: 8 x 8 blocks of bf16
    (True, None, 256, 16, 4, True),
    (False, None, 1024, 64, 2, False),      # nothing to skip
    (True, "bias", 1024, 64, 2, False),     # the bias rides in a block a program
    (True, None, 128, 64, 2, False),        # one block: one diagonal block
    (True, None, 2048, 64, 2, False),       # 136 live pairs: too many to unroll
    (True, None, 1024, 512, 4, False),      # a head's rows outgrow a program's VMEM
])
def test_whole_head_decision(causal, bias, S, d, itemsize, want):
    """One program a head only where every pair's class can be static, the
    head's triangle is small enough to unroll and its rows fit VMEM."""
    fa = flash_attention_module()
    block = min(S, fa.BLOCK_Q)
    geom = (1, 1, S, S, S, S, block, block)
    assert fa._whole_head(causal, bias, geom, d, itemsize) is want
