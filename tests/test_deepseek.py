"""The latent-attention / routed-expert decoder (``models/deepseek.py``,
``fluid/ops/decoder_ops.py``, the ``mla_decode_paged`` kernel) against
plain ``jax.numpy`` and against the benchmark's plain reference
(``benchmark/references/deepseek.py``), at toy widths on the CPU with
seeded weights, and the cache interface the decode engine asks a model
module for.

Tolerances. A float32 program against the float32 reference at toy
widths differs by rounding order only: logits of size ~1 agree to 1e-5
(asserted at 1e-4 on logits, 1e-5 on single ops). A bfloat16 program is
compared with the SAME program in float32: bfloat16 keeps 8 bits, one
step is 2^-8 = 0.0039 of a value, and a logit of size ~0.7 after three
blocks of ~10 roundings each reads within 8 steps of its own size.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from benchmark.families import deepseek as family
from benchmark.references import deepseek as ref
from paddle_tpu.fluid.ops import decoder_ops as ops
from paddle_tpu.models import cache_kinds, deepseek, gpt
from paddle_tpu.serving import decode
from conftest import record_picked_rows

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

CFG = dict(family.TOY, first_k_dense_replace=1, routed_scaling_factor=2.448,
           rope_theta=1e4, rope_interleave=True, rms_norm_eps=1e-6)
BF16_STEP = 2.0 ** -8


def _rng(seed=0):
    return np.random.default_rng(seed)


# -- (a) each new op against jax.numpy ----------------------------------------

def _run_op(build, feed):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        outs = build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    exe.run(startup, scope=scope)
    outs = outs if isinstance(outs, (list, tuple)) else [outs]
    return [np.asarray(v) for v in exe.run(
        main, feed=feed, fetch_list=list(outs), scope=scope)]


def test_rms_norm_op():
    x = _rng(1).normal(size=(2, 5, 16)).astype("float32")
    w = _rng(2).normal(size=(16,)).astype("float32")

    def build():
        xv = fluid.layers.data(name="x", shape=[5, 16], dtype="float32")
        wv = fluid.layers.data(name="w", shape=[16], dtype="float32",
                               append_batch_size=False)
        return fluid.layers.rms_norm(xv, wv, epsilon=1e-6)

    (got,) = _run_op(build, {"x": x, "w": w})
    want = w * x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("interleaved", [True, False])
def test_rotary_embedding_op(interleaved):
    """Against the complex-number form: pair (x_i, y_i) times
    exp(1j * pos * theta^(-i / half)); interleaved input holds the pairs
    side by side, the other form holds all x then all y."""
    n, t, heads, hd, rd = 2, 6, 3, 12, 8
    x = _rng(3).normal(size=(n, t, heads * hd)).astype("float32")
    pos = _rng(4).integers(0, 50, (n, t, 1)).astype("int64")

    def build():
        xv = fluid.layers.data(name="x", shape=[t, heads * hd],
                               dtype="float32")
        pv = fluid.layers.data(name="pos", shape=[t, 1], dtype="int64")
        return fluid.layers.rotary_embedding(
            xv, pv, head_dim=hd, rope_dim=rd, theta=100.0,
            interleaved=interleaved)

    (got,) = _run_op(build, {"x": x, "pos": pos})
    xh = x.reshape(n, t, heads, hd)
    r = xh[..., hd - rd:]
    half = rd // 2
    if interleaved:
        z = r[..., 0::2] + 1j * r[..., 1::2]
    else:
        z = r[..., :half] + 1j * r[..., half:]
    ang = pos.reshape(n, t, 1, 1) * 100.0 ** (-np.arange(half) / half)
    z = z * np.exp(1j * ang)
    want = np.concatenate([xh[..., :hd - rd], z.real, z.imag], -1)
    np.testing.assert_allclose(got, want.reshape(n, t, -1), rtol=1e-5,
                               atol=1e-5)


def test_swiglu_op():
    g = _rng(5).normal(size=(3, 7)).astype("float32")
    u = _rng(6).normal(size=(3, 7)).astype("float32")

    def build():
        gv = fluid.layers.data(name="g", shape=[7], dtype="float32")
        uv = fluid.layers.data(name="u", shape=[7], dtype="float32")
        return fluid.layers.swiglu(gv, uv)

    (got,) = _run_op(build, {"g": g, "u": u})
    np.testing.assert_allclose(got, g / (1 + np.exp(-g)) * u, rtol=1e-5,
                               atol=1e-6)


def test_mul_keeps_the_accumulators_dtype_when_asked():
    x = jnp.asarray(_rng(7).normal(size=(4, 32)), jnp.bfloat16)
    w = jnp.asarray(_rng(8).normal(size=(32, 6)), jnp.bfloat16)

    def build():
        from paddle_tpu.fluid.layer_helper import LayerHelper

        xv = fluid.layers.data(name="x", shape=[32], dtype="bfloat16")
        wv = fluid.layers.data(name="w", shape=[32, 6], dtype="bfloat16",
                               append_batch_size=False)
        helper = LayerHelper("head")
        out = helper.create_variable_for_type_inference("float32")
        helper.append_op(
            type="mul", inputs={"X": [xv], "Y": [wv]},
            outputs={"Out": [out]},
            attrs={"x_num_col_dims": 1, "y_num_col_dims": 1,
                   "out_dtype": fluid.core.np_to_dtype("float32")})
        return out

    (got,) = _run_op(build, {"x": x, "w": w})
    assert got.dtype == np.float32
    want = np.asarray(x, np.float32) @ np.asarray(w, np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _moe_inputs(seed, tokens=24, h=16, e=8, i=12, dtype="float32"):
    r = _rng(seed)
    return dict(
        x=r.normal(size=(tokens, h)).astype(dtype),
        wg=(0.5 * r.normal(size=(h, e))).astype(dtype),
        bias=(0.1 * r.normal(size=(e,))).astype("float32"),
        w1=(0.3 * r.normal(size=(e, h, i))).astype(dtype),
        w3=(0.3 * r.normal(size=(e, h, i))).astype(dtype),
        w2=(0.3 * r.normal(size=(e, i, h))).astype(dtype))


def _moe_naive(p, k, scaling):
    """Every expert on every token, numpy, float64."""
    x = p["x"].astype("float64")
    s = 1 / (1 + np.exp(-(x @ p["wg"].astype("float64"))))
    pick = np.argsort(-(s + p["bias"]), axis=1, kind="stable")[:, :k]
    out = np.zeros_like(x)
    counts = np.zeros(p["wg"].shape[1], int)
    for t in range(x.shape[0]):
        chosen = s[t, pick[t]]
        for e, g in zip(pick[t], scaling * chosen / (chosen.sum() + 1e-20)):
            a = x[t] @ p["w1"][e].astype("float64")
            b = x[t] @ p["w3"][e].astype("float64")
            out[t] += g * ((a / (1 + np.exp(-a)) * b)
                           @ p["w2"][e].astype("float64"))
            counts[e] += 1
    return out, counts


def _moe_op(p, k, scaling, offset=0, held=None):
    e = p["wg"].shape[1]
    held = e if held is None else held
    sl = slice(offset, offset + held)
    feed = dict(p, w1=p["w1"][sl], w3=p["w3"][sl], w2=p["w2"][sl])

    def build():
        vs = {n: fluid.layers.data(name=n, shape=list(v.shape),
                                   dtype=str(v.dtype),
                                   append_batch_size=False)
              for n, v in feed.items()}
        return fluid.layers.moe_ffn(
            vs["x"], vs["wg"], vs["bias"], vs["w1"], vs["w3"], vs["w2"],
            num_experts=e, experts_per_token=k, expert_offset=offset,
            scaling=scaling)

    return _run_op(build, feed)


def test_moe_ffn_op_is_dropless_and_counts_assignments():
    p = _moe_inputs(11)
    got, counts = _moe_op(p, k=2, scaling=2.448)
    want, want_counts = _moe_naive(p, 2, 2.448)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert counts.dtype == np.int32
    assert counts.tolist() == want_counts.tolist()
    assert counts.sum() == 24 * 2          # no token dropped


# -- (f) the share test ---------------------------------------------------------

def test_expert_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """With 2 experts held at offsets 0, 2, 4, 6 the four partial results
    plus the shared expert ONCE are the uncut layer of the reference."""
    cfg = dict(CFG)
    params = ref.init_params(5, cfg)
    p = ref.common.nest(params)["l1"]
    x = jnp.asarray(_rng(12).normal(size=(20, cfg["hidden_size"])),
                    jnp.float32)
    whole = np.asarray(ref.moe(x, p, cfg, ref._mm("highest"), "highest"))
    feed = dict(
        x=np.asarray(x), wg=np.asarray(p["moe"]["wg"], "float32"),
        bias=np.asarray(p["moe"]["bias"]),
        w1=np.asarray(p["moe"]["w1"], "float32"),
        w3=np.asarray(p["moe"]["w3"], "float32"),
        w2=np.asarray(p["moe"]["w2"], "float32"))
    parts, counts = [], []
    for offset in (0, 2, 4, 6):
        y, c = _moe_op(feed, cfg["num_experts_per_tok"],
                       cfg["routed_scaling_factor"], offset=offset, held=2)
        parts.append(y)
        counts.append(c)
    shared = np.asarray(ref.gated_mlp(x, p["shared"], ref._mm("highest")))
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=1e-4,
                               atol=1e-5)
    assert np.concatenate(counts).sum() == 20 * cfg["num_experts_per_tok"]


def test_reference_grouping_is_the_naive_masked_form():
    cfg = dict(CFG)
    p = ref.common.nest(ref.init_params(6, cfg))["l2"]["moe"]
    x = jnp.asarray(_rng(13).normal(size=(33, cfg["hidden_size"])),
                    jnp.float32)
    experts, gates = ref.route(x, p, cfg)
    np.testing.assert_allclose(
        np.asarray(ref.experts_grouped(x, experts, gates, p)),
        np.asarray(ref.experts_naive(x, experts, gates, p)),
        rtol=1e-4, atol=1e-6)


# -- (d) absorbed against up-projected attention, (e) the kernel -----------------

def _latent_case(seed, dtype=jnp.float32):
    """3 slots over a pool of 11 blocks of 4 rows: slot 0 and 1 share
    block 2, slot 1 fills its table, slot 2 holds one live row; entries
    past the live blocks name blocks that must not be read (block 9 holds
    NaN)."""
    r = _rng(seed)
    heads, nope, rope, vd, lat, width, blk = 4, 16, 8, 16, 32, 128, 4
    pool = r.normal(size=(11, 1, blk, width)).astype("float32")
    pool[..., lat + rope:] = 0.0
    pool[9] = np.nan
    tables = np.array([[1, 2, 3, 9, 9], [4, 2, 5, 6, 7], [8, 9, 9, 9, 9]])
    lengths = np.array([9, 20, 1])
    q = r.normal(size=(3, 1, heads * (nope + rope))).astype("float32")
    wkvb = (0.2 * r.normal(size=(lat, heads * (nope + vd)))).astype("float32")
    dims = dict(heads=heads, nope=nope, rope=rope, vdim=vd)
    arrs = [jnp.asarray(a, dtype) for a in (q, pool, wkvb)]
    return arrs, jnp.asarray(tables), jnp.asarray(lengths), dims


def test_absorbed_attention_is_up_projected_attention():
    (q, pool, wkvb), tables, lengths, dims = _latent_case(21)
    got = ops.mla_absorbed(q, pool, tables, lengths, wkvb, **dims)
    live = jnp.where(jnp.isnan(pool), 0.0, pool)
    rows = live[tables][:, :, 0].reshape(3, -1, pool.shape[-1])
    want = ops.mla_window(q, rows, wkvb, (lengths - 1).reshape(3, 1), **dims)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("offset,block", [(0, 4), (8, 4), (5, 6), (0, 512)])
def test_window_attention_in_blocks_is_the_dense_softmax(
        offset, block, monkeypatch):
    """A window of 12 queries at ``offset`` of a 24-key row, in blocks of
    queries over chunks of keys (``block`` 4: 3 x up to 5; 6: 2 x up to 3,
    a block's first query inside a chunk; 512, the default: the largest
    divisors, 4 and 8), against the
    softmax over the whole masked row written out. Keys past the window's
    last position are never read: they hold NaN."""
    r = _rng(31)
    heads, nope, rope, vd, lat, t, s = 4, 16, 8, 16, 32, 12, 24
    q = jnp.asarray(r.normal(size=(2, t, heads * (nope + rope))), "float32")
    rows = r.normal(size=(2, s, 128)).astype("float32")
    rows[:, -(-(offset + t) // block) * block:] = np.nan
    wkvb = jnp.asarray(0.2 * r.normal(size=(lat, heads * (nope + vd))),
                       "float32")
    qpos = jnp.asarray(offset + np.arange(t))[None].repeat(2, 0)
    monkeypatch.setattr(ops, "_WINDOW_BLOCK", block)
    got = ops.mla_window(q, jnp.asarray(rows), wkvb, qpos, heads, nope,
                         rope, vd)
    live = jnp.asarray(np.nan_to_num(rows))
    kv = (live[..., :lat] @ wkvb).reshape(2, s, heads, nope + vd)
    qh = q.reshape(2, t, heads, nope + rope)
    sc = (jnp.einsum("nthd,nshd->nhts", qh[..., :nope], kv[..., :nope])
          + jnp.einsum("nthr,nsr->nhts", qh[..., nope:],
                       live[..., lat:lat + rope])) / (nope + rope) ** 0.5
    seen = jnp.arange(s)[None, None, None] <= qpos[:, None, :, None]
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    want = jnp.einsum("nhts,nshv->nthv", p, kv[..., nope:]).reshape(2, t, -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("block", [4, 2])
def test_mla_kernel_under_the_interpreter_is_the_dense_form(block):
    """Dead table entries (a NaN block) are neither fetched nor computed,
    a block shared by two slots is read by both; ``block`` 2 gives a
    program two pool operands... (PAGED_KEYS / block pages, capped by
    the table)."""
    (q, pool, wkvb), tables, lengths, dims = _latent_case(22)
    if block == 2:   # the same rows as twice as many half blocks
        pool = pool.reshape(22, 1, 2, -1)
        tables = jnp.stack([2 * tables, 2 * tables + 1], -1).reshape(3, -1)
    heads, width = dims["heads"], pool.shape[-1]
    qf = jnp.asarray(_rng(23).normal(size=(3, heads, width)), jnp.float32)
    flat = pool.reshape(pool.shape[0], block, width)
    dense = fa.mla_decode_paged_attention(qf, flat, tables, lengths, 32, 0.2)
    kernel = fa.mla_decode_paged_attention(qf, flat, tables, lengths, 32,
                                           0.2, interpret=True)
    assert np.isfinite(np.asarray(kernel)).all()
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


# -- (b) the exported program against the reference -------------------------------

def _served_scope(cfg, params):
    """Scope holding the seeded weights under the program's names."""
    scope = fluid.core.Scope()
    for leaf, var in family.leaf_to_var(CFG).items():
        value = params[leaf]
        if leaf.endswith("moe/bias"):
            scope.set(var, value)
        else:
            scope.set(var, value.astype(
                fluid.core.dtype_to_np(cfg.dtype)))
    return scope


def _exported_logits(dtype, params, ids, tmp_path):
    cfg = deepseek.DeepseekConfig.from_config(CFG, dtype=dtype)
    with fluid.unique_name.guard():
        infer, _s, feeds, logits = deepseek.build_infer(cfg, ids.shape[1])
    scope = _served_scope(cfg, params)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        fluid.io.save_inference_model(str(tmp_path), feeds, [logits], exe,
                                      main_program=infer)
    from paddle_tpu import inference

    pred = inference.create_paddle_predictor(
        inference.AnalysisConfig(str(tmp_path)))
    n, t = ids.shape
    (out,) = pred.run([
        ids.reshape(n, t, 1).astype("int64"),
        np.tile(np.arange(t).reshape(1, t, 1), (n, 1, 1)).astype("int64")])
    return np.asarray(out.as_ndarray() if hasattr(out, "as_ndarray")
                      else out)


@pytest.fixture(scope="module")
def seeded():
    params = ref.init_params(7, dict(CFG))
    ids = _rng(31).integers(0, CFG["vocab_size"], (2, 16))
    return params, ids, np.asarray(ref.logits(dict(CFG), params, ids))


def test_exported_float32_program_is_the_reference(seeded, tmp_path):
    params, ids, want = seeded
    got = _exported_logits("float32", params, ids, tmp_path)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_exported_bfloat16_program_within_bf16_steps(seeded, tmp_path):
    """bfloat16 parameters survive export and load as bfloat16; float32
    logits within 8 bfloat16 steps of the largest logit, all but the few
    positions where the lower precision routed a token differently."""
    params, ids, want = seeded
    got = _exported_logits("bfloat16", params, ids, tmp_path)
    assert got.dtype == np.float32
    off = np.abs(got - want).max(-1)
    near = off <= 8 * BF16_STEP * np.abs(want).max()
    assert near.mean() >= 0.9, off
    assert off.max() < 0.2 * np.abs(want).max()   # a flip, not a fault


# -- (c) windows then T = 1 steps through the paged engine --------------------------

def _engine(cfg, params, **kw):
    with fluid.unique_name.guard():
        infer, _s, _f, _l = deepseek.build_infer(cfg, 8)
    args = dict(slots=2, max_len=64, block_size=4, prefill_buckets=[8, 16],
                prefill_chunk=16, param_program=infer, model=deepseek)
    args.update(kw)
    return decode.DecodeEngine(cfg, place=fluid.CPUPlace(),
                               scope=_served_scope(cfg, params), **args)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["dense_fallback", "interpreted_kernel"])
def test_engine_windows_then_steps_are_the_reference_forward(
        seeded, monkeypatch, kernel):
    """A 37-token prompt is prefilled in three windows (16, 16, 5) over
    ten blocks of 4, then 9 tokens are decoded by T = 1 steps next to a
    second, shorter stream. Every logits row a token is picked from (on
    the host for a window, on the device for a step) is compared with the
    reference's full forward over prompt + tokens."""
    params = seeded[0]
    cfg = deepseek.DeepseekConfig.from_config(
        CFG, dtype="float32", flash_interpret=kernel)
    eng = _engine(cfg, params).start(loop=False)
    seen = record_picked_rows(monkeypatch, eng)
    try:
        prompts = [list(_rng(41).integers(0, 211, 37)),
                   list(_rng(42).integers(0, 211, 6))]
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, (10, 4))]
        for _ in range(40):
            eng._tick()
            if all(s.done for s in streams):
                break
        assert streams[0].admit_windows == 3
        for prompt, stream in zip(prompts, streams):
            tokens = stream.tokens(timeout=1)
            rows = np.stack(seen[id(stream)])
            assert len(tokens) == len(rows)
            ids = np.array([prompt + tokens])
            want = np.asarray(ref.logits(dict(CFG), params, ids))[0]
            first = len(prompt) - 1
            np.testing.assert_allclose(
                rows, want[first:first + len(rows)], atol=1e-4, rtol=0)
    finally:
        eng.stop()


def test_step_span_carries_the_expert_counts(seeded):
    from paddle_tpu.observability import trace

    cfg = deepseek.DeepseekConfig.from_config(CFG, dtype="float32")
    eng = _engine(cfg, seeded[0]).start(loop=False)
    try:
        stream = eng.submit([1, 2, 3, 4, 5], max_new_tokens=3)
        for _ in range(8):
            eng._tick()
        assert stream.done
        steps = [s for s in trace.get_spans()
                 if s["name"] == "decode_paged_step"
                 and "experts_hit" in (s.get("args") or {})]
        args = steps[-1]["args"]
        # one live stream, two expert layers, top 2 of 8
        assert args["assignments"] == 2 * 2 * 2   # 2 slots: one inactive
        assert 2 <= args["experts_hit"] <= 8
        assert args["expert_load_max"] >= 1
        assert args["latent_rows_live"] >= 5
        ticks = [s for s in trace.get_spans() if s["name"] == "engine_tick"]
        assert ticks[-1]["args"]["kv_bytes_per_token"] == 3 * 128 * 4
    finally:
        eng.stop()


# -- (g) the cache interface of models/gpt.py ----------------------------------------

def test_gpt_cache_kinds_are_todays_pools():
    """K and V a layer, a token's heads side by side in one row: the
    names, bytes and allocator arithmetic of the ``[heads, d_head]`` row
    these pools had before PR 30, another shape."""
    cfg = gpt.GPTConfig.tiny()
    kinds = gpt.cache_kinds(cfg)
    blocks, block = 9, 4
    assert [tuple(p.name(blocks, block) for p in layer) for layer in kinds] \
        == gpt.paged_pool_names(cfg, blocks, block) \
        == [("gpt_paged_k_%d_n9x4" % i, "gpt_paged_v_%d_n9x4" % i)
            for i in range(cfg.num_layers)]
    for layer in kinds:
        for pool in layer:
            assert pool.shape(blocks, block) == gpt.paged_pool_shape(
                cfg, blocks, block) == [blocks, 1, block, cfg.hidden_size]
            assert pool.dtype == "float32"
    assert gpt.paged_block_bytes(cfg, block) \
        == cfg.num_layers * 2 * block * cfg.hidden_size * 4
    assert cache_kinds.bytes_per_token(kinds) * block \
        == gpt.paged_block_bytes(cfg, block)
    real = gpt.GPTConfig()
    assert cache_kinds.bytes_per_token(gpt.cache_kinds(real)) \
        == 12 * 2 * 768 * 4


def test_latent_cache_kind_is_one_640_lane_pool_a_layer():
    cfg = deepseek.DeepseekConfig(num_hidden_layers=6)
    kinds = deepseek.cache_kinds(cfg)
    assert [len(layer) for layer in kinds] == [1] * 6
    pool = kinds[0][0]
    assert pool.shape(2177, 128) == [2177, 1, 128, 640]
    assert pool.dtype == "bfloat16"
    assert cache_kinds.bytes_per_token(kinds) == 6 * 640 * 2


# -- (h) modes that are not built for a latent cache -----------------------------------

@pytest.mark.parametrize("mode,kwargs", [
    ("spec_tokens", dict(block_size=4, spec_tokens=3)),
    ("tp", dict(block_size=4, tp=2)),
])
def test_latent_session_refuses_a_mode_by_name(mode, kwargs):
    cfg = deepseek.DeepseekConfig.tiny()
    with pytest.raises(NotImplementedError) as err:
        decode.DecodeSession(cfg, place=fluid.CPUPlace(), slots=2,
                             max_len=32, model=deepseek, **kwargs)
    assert deepseek.UNSUPPORTED[mode] in str(err.value)


def test_latent_engine_refuses_the_host_kv_tier(seeded):
    from paddle_tpu.fluid import flags

    cfg = deepseek.DeepseekConfig.from_config(CFG, dtype="float32")
    flags.set_flags({"FLAGS_kv_tier_host_mb": 1.0})
    try:
        eng = _engine(cfg, seeded[0], prefix_cache_mb=1.0)
        with pytest.raises(NotImplementedError) as err:
            eng.start(loop=False)
        assert deepseek.UNSUPPORTED["kv_host_tier"] in str(err.value)
    finally:
        flags.set_flags({"FLAGS_kv_tier_host_mb": 0.0})


def test_latent_engine_prefix_index_shares_blocks(seeded):
    """The paged prefix INDEX is a block-table operation and works: a
    second request with the same 12-token head reuses three blocks and
    decodes the same tokens."""
    cfg = deepseek.DeepseekConfig.from_config(CFG, dtype="float32")
    eng = _engine(cfg, seeded[0], prefix_cache_mb=1.0).start(loop=False)
    try:
        prompt = list(_rng(51).integers(0, 211, 14))
        outs = []
        for _ in range(2):
            stream = eng.submit(prompt, max_new_tokens=4)
            for _tick in range(12):
                eng._tick()
            outs.append((stream.tokens(timeout=1),
                         stream.cached_prefix_tokens))
        assert outs[0][0] == outs[1][0]
        assert outs[0][1] == 0 and outs[1][1] == 12
    finally:
        eng.stop()


def test_one_live_key_gives_its_own_value():
    """A slot with one live key: the softmax is 1 and the result is that
    row's up-projected value, whatever the scores' scale."""
    (q, pool, wkvb), tables, lengths, dims = _latent_case(24)
    rows = jnp.where(jnp.isnan(pool), 0.0, pool)[tables][:, :, 0].reshape(
        3, -1, pool.shape[-1])
    got = ops.mla_window(q, rows, wkvb, (lengths - 1).reshape(3, 1), **dims)
    # by hand for slot 2 (one live key): softmax over one key is 1
    lat = wkvb.shape[0]
    v = (rows[2, 0, :lat] @ wkvb).reshape(dims["heads"], -1)[:, 16:]
    np.testing.assert_allclose(np.asarray(got[2, 0]),
                               np.asarray(v).reshape(-1), rtol=1e-5,
                               atol=1e-6)
