"""The gated delta-rule / grouped-query decoder (``models/solar_open2.py``,
the ``kda_*`` and ``gqa_window_attention`` ops, the ``kda_decode`` kernel
and the grouped heads of ``flash_decode_paged_attention``) against plain
``jax.numpy`` and against the benchmark's plain reference
(``benchmark/references/solar_open2.py``), at toy widths on the CPU with
seeded float32 weights.

Tolerances. The chunked delta rule and the token-by-token recurrence are
the same sums in another order: float32 at the highest matmul precision
agrees to 1e-5 of values of size ~1 (asserted at 2e-5 on ops, 1e-4 on
logits after eight blocks).
"""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from benchmark.families import solar_open2 as family
from benchmark.references import solar_open2 as ref
from paddle_tpu.fluid.ops import decoder_ops as ops
from paddle_tpu.kernels import kda as kda_kernel
from paddle_tpu.models import cache_kinds, solar_open2
from paddle_tpu.serving import decode
from conftest import record_picked_rows

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

CFG = dict(family.TOY, gqa_layers=[0, 4, 8], n_shared_experts=1,
           routed_scaling_factor=1.0, rms_norm_eps=1e-5,
           first_k_dense_replace=0, expert_offset=0)


def _rng(seed=0):
    return np.random.default_rng(seed)


# -- (a) the delta rule: chunks, windows, the step ----------------------------

def _kda_case(seed, t, heads=3, d=16, decay=1.0):
    """q, k unit rows, v, per-channel decays a = e^g and write strengths
    b in (0, 2), a state; ``decay`` scales g (8: a chunk's running product
    underflows float32)."""
    r = _rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(r.normal(size=(t, heads, d))) * d ** -0.5
    k = unit(r.normal(size=(t, heads, d)))
    v = r.normal(size=(t, heads, d))
    g = -decay * r.uniform(0.001, 1.6, size=(t, heads, d))
    b = r.uniform(0.05, 1.95, size=(t, heads))
    s0 = r.normal(size=(heads, d, d))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, b, s0)]


@pytest.mark.parametrize("t,decay", [(128, 1.0), (64, 8.0), (16, 1.0),
                                     (192, 0.05)])
def test_chunked_delta_rule_is_the_recurrence(t, decay):
    q, k, v, g, b, s0 = _kda_case(t, t, decay=decay)
    want_o, want_s = ref.delta_rule(q, k, v, jnp.exp(g), b, s0)
    got_o, got_s = ops.kda_chunked(q, k, v, g, b, s0)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5, rtol=0)
    assert np.isfinite(np.asarray(got_o)).all()


def _window_case(seed, t, heads=2, d=16, taps=4):
    r = _rng(seed)
    hd = heads * d
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return dict(
        qkv=f32(r.normal(size=(t, 3 * hd))), f=f32(r.normal(size=(t, hd))),
        bt=f32(r.normal(size=(t, heads))),
        conv_w=f32(r.normal(size=(taps, 3 * hd)) * 0.5),
        a_log=f32(np.log(r.uniform(1, 16, heads))),
        dt_bias=f32(r.uniform(-5, -1, hd)), heads=heads, head_dim=d)


def _window_by_recurrence(c, length):
    """``kda_window`` over the first ``length`` tokens, token by token
    from zeros: (o, S, the last 3 rows before the convolution)."""
    hd = c["heads"] * c["head_dim"]
    p = {"a_log": c["a_log"], "dt_bias": c["dt_bias"], "wb": jnp.eye(
        c["heads"]), "f_down": jnp.eye(hd), "f_up": jnp.eye(hd)}
    for i, n in enumerate("qkv"):
        p["w" + n] = jnp.eye(3 * hd)[:, i * hd:(i + 1) * hd]
        p["conv_" + n] = c["conv_w"][:, i * hd:(i + 1) * hd]
    z = dict(kh=c["heads"], kd=c["head_dim"])
    mm = lambda a, w: a @ w  # noqa: E731
    x = c["qkv"][:length]
    # the identity "projections" pick q~ k~ v~ out of the row; f and b are
    # fed as they are
    q, k, v, _a, _b = ref.kda_inputs(
        x, dict(p, f_down=jnp.zeros((3 * hd, hd)), f_up=jnp.eye(hd),
                wb=jnp.zeros((3 * hd, c["heads"]))), z, mm)
    g = -jnp.exp(c["a_log"])[None, :, None] * jax.nn.softplus(
        (c["f"][:length] + c["dt_bias"]).reshape(length, c["heads"], -1))
    b = 2 * jax.nn.sigmoid(c["bt"][:length])
    s0 = jnp.zeros((c["heads"], c["head_dim"], c["head_dim"]))
    o, s = ref.delta_rule(q, k, v, jnp.exp(g), b, s0)
    taps = c["conv_w"].shape[0]
    tail = jnp.concatenate([jnp.zeros((taps - 1, 3 * hd)), x])[-(taps - 1):]
    return o.reshape(length, hd), s, tail


def _states(c, rows=3):
    hd = c["heads"] * c["head_dim"]
    r = _rng(99)
    return (jnp.asarray(r.normal(size=(rows, c["heads"], c["head_dim"],
                                       c["head_dim"])), jnp.float32),
            jnp.asarray(r.normal(size=(rows, 3, 3 * hd)), jnp.float32))


def test_a_window_from_offset_zero_starts_from_zeros_and_rewrites_its_row():
    c = _window_case(1, 32)
    want_o, want_s, want_tail = _window_by_recurrence(c, 32)
    state, conv = _states(c)
    o, s1, c1 = ops.kda_window(**c, state=state, conv=conv, row=2, start=0,
                               length=32)
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(s1[2], want_s, atol=2e-5, rtol=0)
    np.testing.assert_allclose(c1[2], want_tail, atol=0, rtol=0)
    # the other rows are as they were
    np.testing.assert_array_equal(s1[:2], state[:2])
    np.testing.assert_array_equal(c1[:2], conv[:2])


@pytest.mark.parametrize("first", [16, 13, 2])
def test_a_state_carried_across_two_windows_is_one_window(first):
    """32 tokens as windows of ``first`` (padded to 16) and the rest
    (padded to 32): the second starts from the row the first left."""
    c = _window_case(2, 32)
    want_o, want_s, want_tail = _window_by_recurrence(c, 32)
    state, conv = _states(c)
    cut = lambda a, lo, hi, pad: jnp.concatenate(  # noqa: E731
        [a[lo:hi], jnp.ones((pad - (hi - lo),) + a.shape[1:], a.dtype)])
    w1 = dict(c, **{n: cut(c[n], 0, first, 16) for n in ("qkv", "f", "bt")})
    o1, s1, c1 = ops.kda_window(**w1, state=state, conv=conv, row=1,
                                start=0, length=first)
    w2 = dict(c, **{n: cut(c[n], first, 32, 32)
                    for n in ("qkv", "f", "bt")})
    o2, s2, c2 = ops.kda_window(**w2, state=s1, conv=c1, row=1,
                                start=first, length=32 - first)
    got = jnp.concatenate([o1[:first], o2[:32 - first]])
    np.testing.assert_allclose(got, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(s2[1], want_s, atol=2e-5, rtol=0)
    np.testing.assert_allclose(c2[1], want_tail, atol=0, rtol=0)


def test_a_window_padded_past_its_last_real_token_stops_there():
    """21 real tokens in a bucket of 32 whose padding is NOT zeros: the
    state and the tail are those of the 21."""
    c = _window_case(3, 32)
    _o, want_s, want_tail = _window_by_recurrence(c, 21)
    state, conv = _states(c)
    _o, s1, c1 = ops.kda_window(**c, state=state, conv=conv, row=1, start=0,
                                length=21)
    np.testing.assert_allclose(s1[1], want_s, atol=2e-5, rtol=0)
    np.testing.assert_allclose(c1[1], want_tail, atol=0, rtol=0)


def _step_case(seed, slots=5, heads=4, d=16):
    r = _rng(seed)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return dict(
        state=f32(r.normal(size=(slots + 1, heads, d, d))),
        q=f32(unit(r.normal(size=(slots, heads, d)))),
        k=f32(unit(r.normal(size=(slots, heads, d)))),
        v=f32(r.normal(size=(slots, heads, d))),
        a=f32(r.uniform(0.2, 1.0, size=(slots, heads, d))),
        b=f32(r.uniform(0.05, 1.95, size=(slots, heads))))


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["dense_fallback", "interpreted_kernel"])
def test_kda_decode_steps_the_fed_rows_and_no_other(interpret):
    """Slots 0, 2, 3 on rows 1, 3, 4; slots 1 and 4 idle on the sink: the
    fed rows take one step of the recurrence, rows 2 and 5 are as they
    were, the sink holds garbage nobody reads."""
    c = _step_case(5)
    rows = jnp.asarray([1, 0, 3, 4, 0], jnp.int32)
    o, s1 = kda_kernel.kda_decode(c["state"], rows, c["q"], c["k"], c["v"],
                                  c["a"], c["b"], interpret=interpret)
    for slot, row in ((0, 1), (2, 3), (3, 4)):
        want_o, want_s = ref.delta_rule(
            c["q"][slot][None], c["k"][slot][None], c["v"][slot][None],
            c["a"][slot][None], c["b"][slot][None], c["state"][row])
        np.testing.assert_allclose(o[slot], want_o[0], atol=1e-6, rtol=0)
        np.testing.assert_allclose(s1[row], want_s, atol=1e-6, rtol=0)
    np.testing.assert_array_equal(s1[2], c["state"][2])
    np.testing.assert_array_equal(s1[5], c["state"][5])


def test_kda_step_op_is_the_window_op_one_token_at_a_time():
    """Four tokens through ``kda_step`` (the kernel interpreted), the
    convolution's tail carried in its state, against one window."""
    c = _window_case(4, 4)
    want_o, want_s, want_tail = _window_by_recurrence(c, 4)
    hd = c["heads"] * c["head_dim"]
    state = jnp.zeros((2, c["heads"], c["head_dim"], c["head_dim"]))
    conv = jnp.zeros((2, 3, 3 * hd))
    rows = jnp.asarray([1], jnp.int32)
    outs = []
    for t in range(4):
        window = jnp.concatenate([conv[rows], c["qkv"][None, t:t + 1]], 1)
        y = (c["conv_w"][None] * window).sum(1)
        q, k, v, g, b = ops.kda_inputs(
            y, c["f"][t:t + 1], c["bt"][t:t + 1], c["a_log"], c["dt_bias"],
            c["heads"], c["head_dim"])
        o, state = kda_kernel.kda_decode(state, rows, q, k, v, jnp.exp(g), b,
                                         interpret=True)
        conv = conv.at[rows].set(window[:, 1:])
        outs.append(o.reshape(1, hd))
    np.testing.assert_allclose(jnp.concatenate(outs), want_o, atol=2e-5,
                               rtol=0)
    np.testing.assert_allclose(state[1], want_s, atol=2e-5, rtol=0)
    np.testing.assert_allclose(conv[1], want_tail, atol=0, rtol=0)


# -- (b) grouped-query attention ----------------------------------------------

def _dense_gqa(q, k, v, length):
    """q [N, D], k/v [S, G*D]: softmax over the first ``length`` keys,
    query head h on key head h // (N / G)."""
    n, d = q.shape
    g = k.shape[1] // d
    k = np.asarray(k, np.float64).reshape(-1, g, d)[:length]
    v = np.asarray(v, np.float64).reshape(-1, g, d)[:length]
    out = np.zeros((n, d))
    for h in range(n):
        s = k[:, h // (n // g)] @ np.asarray(q[h], np.float64) * d ** -0.5
        p = np.exp(s - s.max())
        out[h] = (p / p.sum()) @ v[:, h // (n // g)]
    return out


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["dense_fallback", "interpreted_kernel"])
@pytest.mark.parametrize("heads,kv_heads,block", [(8, 2, 4), (16, 2, 8),
                                                  (6, 1, 2)])
def test_grouped_paged_decode_is_dense_grouped_attention(
        heads, kv_heads, block, interpret):
    r = _rng(heads * 10 + block)
    d, slots, mb = 16, 3, 6
    blocks = slots * mb + 1
    kp = jnp.asarray(r.normal(size=(blocks, block, kv_heads * d)), jnp.float32)
    vp = jnp.asarray(r.normal(size=(blocks, block, kv_heads * d)), jnp.float32)
    q = jnp.asarray(r.normal(size=(slots, heads, 1, d)), jnp.float32)
    tables = r.permutation(np.arange(1, blocks))[:slots * mb].reshape(
        slots, mb)
    lengths = np.array([1, mb * block, 2 * block + 1])
    # entries past a slot's live blocks may point anywhere: poison them
    for s in range(slots):
        tables[s, -(-lengths[s] // block):] = 0
    got = fa.flash_decode_paged_attention(
        q, kp, vp, jnp.asarray(tables), lengths=jnp.asarray(lengths),
        interpret=interpret)
    assert got.shape == (slots, heads, 1, d)
    for s in range(slots):
        want = _dense_gqa(q[s, :, 0], np.asarray(kp)[tables[s]].reshape(
            -1, kv_heads * d), np.asarray(vp)[tables[s]].reshape(
                -1, kv_heads * d), lengths[s])
        np.testing.assert_allclose(got[s, :, 0], want, atol=2e-5, rtol=0)


def test_grouped_paged_decode_refuses_a_key_bias_and_a_missing_length():
    q = jnp.zeros((1, 4, 1, 16))
    pool = jnp.zeros((3, 4, 32))
    tables = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(ValueError, match="lengths"):
        fa.flash_decode_paged_attention(q, pool, pool, tables)
    with pytest.raises(ValueError, match="lengths"):
        fa.flash_decode_paged_attention(
            q, pool, pool, tables, key_bias=jnp.zeros((1, 8)),
            lengths=jnp.ones((1,), jnp.int32))


# the traced program (jaxpr, the kernel's body in it) of GPT-2-small's
# T = 1 call, 12 heads of 64 over float32 768-lane rows, as the commit
# before grouped heads traced it
GPT_CALL_SHA256 = ("1d38d541a552229cbcb3755395734bdc"
                   "0d70ce17fbbf6ec2c409af252b98ce64")


def test_gpt_call_of_the_paged_kernel_is_traced_bit_for_bit_as_before():
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    slots, heads, d, block, mb = 8, 12, 64, 16, 64
    sd = jax.ShapeDtypeStruct
    pool = sd((slots * mb + 1, block, heads * d), jnp.float32)

    def call(q, k, v, tables, bias, lengths):
        return fa.flash_decode_paged_attention(
            q, k, v, tables, key_bias=bias, lengths=lengths, interpret=False)

    text = str(jax.make_jaxpr(call)(
        sd((slots, heads, 1, d), jnp.float32), pool, pool,
        sd((slots, mb), jnp.int32), sd((slots, mb * block), jnp.float32),
        sd((slots,), jnp.int32)))
    assert "flash_decode_paged_gqa" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == GPT_CALL_SHA256


@pytest.mark.parametrize("offset,t,s", [(0, 16, 16), (8, 8, 24), (5, 16, 1024)])
def test_gqa_window_in_blocks_is_the_dense_softmax(offset, t, s):
    r = _rng(offset + t)
    heads, kvh, d = 4, 2, 16
    q = jnp.asarray(r.normal(size=(1, t, heads * d)), jnp.float32)
    k = jnp.asarray(r.normal(size=(1, s, kvh * d)), jnp.float32)
    v = jnp.asarray(r.normal(size=(1, s, kvh * d)), jnp.float32)
    qpos = jnp.asarray(offset + np.arange(t))[None]
    got = ops.gqa_window(q, k, v, qpos, kvh, d)
    for i in range(t):
        want = _dense_gqa(q[0, i].reshape(heads, d), k[0], v[0],
                          offset + i + 1)
        np.testing.assert_allclose(got[0, i].reshape(heads, d), want,
                                   atol=2e-5, rtol=0)


# -- (c) the share of the experts held ----------------------------------------

def test_four_shares_and_everything_else_once_add_up_to_the_uncut_layer():
    """One softmax block and one delta-rule block, each with 8 experts
    top 2: the op's routed part over each of 4 shares of 2 (``moe_ffn``'s
    ``expert_offset``), summed, plus the mixing layer and the shared
    expert counted ONCE, is the uncut reference block."""
    whole = dict(CFG, num_hidden_layers=2, gqa_layers=[0],
                 n_routed_experts=8, published={"n_routed_experts": 8})
    params = {k: jnp.asarray(v, jnp.float32)
              for k, v in ref.init_params(11, whole).items()}
    x = jnp.asarray(_rng(12).normal(size=(2, 16, whole["hidden_size"])),
                    jnp.float32)
    z, eps = ref.sizes(whole), whole["rms_norm_eps"]
    mm = ref._mm("highest")
    for layer, is_gqa in ((0, True), (1, False)):
        p = ref.common.nest(params)["l%d" % layer]
        want = ref._layer(x, p, z=ref._freeze(whole), is_gqa=is_gqa,
                          kind="highest", eps=eps, scaling=1.0)
        if is_gqa:
            mix = lambda r: ref.gqa(ref.rms_norm(r, p["ln1"], eps),  # noqa: E731
                                    p["attn"], z, mm)
        else:
            mix = lambda r: ref.kda(ref.rms_norm(r, p["ln1"], eps),  # noqa: E731
                                    p["kda"], z, mm, eps)
        mixed = x + jax.lax.map(mix, x)
        y = ref.rms_norm(mixed, p["ln2"], eps).reshape(32, -1)
        experts, gates = ops.route(y, p["moe"]["wg"], p["moe"]["bias"], 2, 1.0)
        total = mixed.reshape(32, -1) + ref.gated_mlp(y, p["shared"], mm)
        held = 0
        for offset in (0, 2, 4, 6):
            part, counts = ops.grouped_experts(
                y, experts, gates, *(p["moe"][w][offset:offset + 2]
                                     for w in ("w1", "w3", "w2")), offset)
            total = total + part
            held += int(counts.sum())
        assert held == 32 * 2        # every assignment is some share's
        np.testing.assert_allclose(total.reshape(x.shape), want, atol=2e-5,
                                   rtol=0)


def test_moe_ffn_op_over_a_held_share_is_the_references_share():
    """The op told it holds experts 2..3 of 8 gives what the reference's
    share gives, naive and grouped, and counts its own assignments."""
    r = _rng(21)
    t, h, i, e, k = 24, 16, 12, 8, 2
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    x = f32(r.normal(size=(t, h)))
    p = dict(wg=f32(r.normal(size=(h, e))), bias=f32(r.normal(size=(e,)) * .1),
             w1=f32(r.normal(size=(2, h, i)) * .3),
             w3=f32(r.normal(size=(2, h, i)) * .3),
             w2=f32(r.normal(size=(2, i, h)) * .3))
    z = dict(topk=k, offset=2)
    experts, gates = ref.route(x, p, z, 1.0)
    want = ref.experts_naive(x, experts, gates, p, z)
    np.testing.assert_allclose(
        ref.experts_held(x, experts, gates, p, z), want, atol=2e-5, rtol=0)
    oe, og = ops.route(x, p["wg"], p["bias"], k, 1.0)
    got, counts = ops.grouped_experts(x, oe, og, p["w1"], p["w3"], p["w2"], 2)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    np.testing.assert_array_equal(
        counts, [(np.asarray(experts) == g).sum() for g in (2, 3)])


# -- (d) the model: export, then windows and steps through the engine ---------

def _served_scope(cfg, params):
    scope = fluid.core.Scope()
    for leaf, var in family.leaf_to_var(CFG).items():
        scope.set(var, np.asarray(params[leaf], np.float32))
    return scope


@pytest.fixture(scope="module")
def seeded():
    params = ref.init_params(7, dict(CFG))
    ids = _rng(31).integers(0, CFG["vocab_size"], (2, 16))
    return params, ids, np.asarray(ref.logits(dict(CFG), params, ids))


def _config(**kw):
    return solar_open2.SolarOpen2Config.from_config(
        CFG, **dict(dict(dtype="float32"), **kw))


def test_the_toy_keeps_two_of_eight_experts_and_the_router_all_eight():
    cfg = _config()
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_offset) == (
        8, 2, 0)
    assert cfg.gqa_layers == (0, 4)
    assert [cfg.is_gqa(i) for i in range(8)] == [True, False, False, False,
                                                 True, False, False, False]


def test_exported_float32_program_is_the_reference(seeded, tmp_path):
    params, ids, want = seeded
    cfg = _config()
    with fluid.unique_name.guard():
        infer, _s, feeds, logits = solar_open2.build_infer(cfg, ids.shape[1])
    declared = {v.name: tuple(v.shape) for v in infer.list_vars()
                if getattr(v, "is_parameter", False)}
    assert declared == {var: tuple(params[leaf].shape) for leaf, var in
                        family.leaf_to_var(CFG).items()}
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
    got = np.asarray(out.as_ndarray() if hasattr(out, "as_ndarray") else out)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def _engine(cfg, params, **kw):
    with fluid.unique_name.guard():
        infer, _s, _f, _l = solar_open2.build_infer(cfg, 8)
    args = dict(slots=2, max_len=64, block_size=4, prefill_buckets=[8, 16],
                prefill_chunk=16, param_program=infer, model=solar_open2)
    args.update(kw)
    return decode.DecodeEngine(cfg, place=fluid.CPUPlace(),
                               scope=_served_scope(cfg, params), **args)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["dense_fallback", "interpreted_kernels"])
def test_engine_windows_then_steps_are_the_reference_forward(
        seeded, monkeypatch, kernel):
    """A 37-token prompt is prefilled in three windows (16, 16, 5: the
    state handed from window to window, the last one padded to 8), then 9
    tokens are decoded by T = 1 steps next to a second, shorter stream
    admitted in one window of 6 in 8. Every logits row a token is picked
    from (on the host for a window, on the device for a step) is compared
    with the reference's full forward over prompt + tokens: logits, not
    tokens."""
    params = seeded[0]
    cfg = _config(flash_interpret=kernel)
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


def test_spans_and_counters_carry_states_rows_and_experts(seeded):
    from paddle_tpu.fluid import profiler
    from paddle_tpu.observability import trace

    cfg = _config()
    eng = _engine(cfg, seeded[0]).start(loop=False)
    before = profiler.get_counters()      # after the warm-up's windows
    try:
        stream = eng.submit([1, 2, 3, 4, 5], max_new_tokens=3)
        for _ in range(8):
            eng._tick()
        assert stream.done
        spans = trace.get_spans()
        step = [s for s in spans if s["name"] == "decode_paged_step"
                and "state_slots_live" in (s.get("args") or {})][-1]["args"]
        state = 6 * 2 * 16 * 16 * 4          # S of 6 layers, 2 heads of 16
        assert step["state_slots_live"] == 1
        assert step["state_bytes"] == 2 * state
        assert step["kv_rows_live"] >= 5
        # one live stream and one idle slot, 8 layers, top 2 of 8, the
        # counts of the 2 held
        assert 0 <= step["assignments"] <= 2 * 8 * 2
        assert step["experts_hit"] <= 8 * 2
        window = [s for s in spans if s["name"] == "decode_paged_window"
                  and "kda_chunks" in (s.get("args") or {})][-1]["args"]
        assert (window["window_tokens_real"],
                window["window_tokens_padded"], window["kda_chunks"]) == (
                    5, 8, 1)
        after = profiler.get_counters()
        assert after["kda_state_resets"] - before.get(
            "kda_state_resets", 0) == 1
        assert after["kda_state_bytes"] > before.get("kda_state_bytes", 0)
        assert after["moe_assignments"] >= before.get("moe_assignments", 0)
        tick = [s for s in spans if s["name"] == "engine_tick"][-1]["args"]
        assert tick["kv_bytes_per_token"] == 2 * 2 * 32 * 4
        conv = 6 * 3 * 3 * 32 * 4
        assert tick["state_bytes_per_slot"] == state + conv
        assert eng.stats()["state_bytes_per_slot"] == state + conv
    finally:
        eng.stop()


# -- (e) what the model tells the engine --------------------------------------

def test_cache_kinds_answer_pools_or_states_by_layer():
    cfg = _config()
    kinds = solar_open2.cache_kinds(cfg)
    assert len(kinds) == 8
    for i, layer in enumerate(kinds):
        if cfg.is_gqa(i):
            assert [type(k) for k in layer] == [cache_kinds.CachePool] * 2
            assert layer[0].row == [1, 32] and layer[0].shape(9, 4) == [
                9, 1, 4, 32]
        else:
            assert [type(k) for k in layer] == [cache_kinds.CacheState] * 2
            assert layer[0].shape(3) == [4, 2, 16, 16]
            assert layer[0].dtype == "float32"
            assert layer[1].shape(3) == [4, 3, 96]
    assert cache_kinds.bytes_per_token(kinds) == 2 * 2 * 32 * 4
    assert cache_kinds.state_bytes_per_slot(kinds) == 6 * (
        2 * 16 * 16 + 3 * 96) * 4
    with pytest.raises(TypeError, match="layer 1"):
        cache_kinds.kv_pools(kinds)


def test_published_widths_cost_what_the_issue_reckons():
    """Solar-Open2-250B as published: 4 kB of K and V a token a softmax
    layer, 4.19 MB + 147 kB a slot a delta-rule layer."""
    cfg = solar_open2.SolarOpen2Config(num_hidden_layers=8)
    kinds = solar_open2.cache_kinds(cfg)
    assert cache_kinds.bytes_per_token(kinds) == 2 * 2 * 1024 * 2
    assert cache_kinds.state_bytes_per_slot(kinds) == 6 * (
        64 * 128 * 128 * 4 + 3 * 24576 * 2)
