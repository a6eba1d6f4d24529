"""The shortcut-connected expert decoder (``models/longcat_flash.py``:
two latent attentions and two dense feed-forwards a double layer, a
routed branch with identity experts carried past three of them; the
``moe_ffn`` op's ``scoring`` / ``norm_topk`` / ``zero_experts`` and
``mla_attention``'s query LoRA and LoRA scales) against plain
``jax.numpy`` and against the benchmark's plain reference
(``benchmark/references/longcat_flash.py``), at toy widths on the CPU
with seeded float32 weights.

Tolerances. The program and the reference are the same float32 sums in
another order (grouped against naive products, absorbed against
up-projected attention, a window's blocks against a whole row): 2e-5 on
ops of values of size ~1, 1e-4 on logits after two double layers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from benchmark.families import longcat_flash as family
from benchmark.references import longcat_flash as ref
from paddle_tpu.fluid.ops import decoder_ops as ops
from paddle_tpu.models import cache_kinds, deepseek, longcat_flash
from paddle_tpu.serving import decode
from conftest import record_picked_rows

CFG = dict(family.TOY, mla_scale_q_lora=True, mla_scale_kv_lora=True,
           routed_scaling_factor=6.0, rms_norm_eps=1e-5, expert_offset=0)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _f32(x):
    return jnp.asarray(x, jnp.float32)


# -- (a) the router and the expert op -----------------------------------------

def _moe_case(seed, t=24, h=16, i=12, held=3, experts=8, zeros=4, k=3,
              offset=2):
    """Tokens, a router ``experts + zeros`` wide and ``held`` experts from
    ``offset``; ``z`` as the reference's ``sizes`` gives it."""
    r = _rng(seed)
    p = dict(wg=_f32(r.normal(size=(h, experts + zeros))),
             bias=_f32(r.normal(size=(experts + zeros,)) * 0.02),
             w1=_f32(r.normal(size=(held, h, i)) * 0.3),
             w3=_f32(r.normal(size=(held, h, i)) * 0.3),
             w2=_f32(r.normal(size=(held, i, h)) * 0.3))
    z = dict(topk=k, offset=offset, experts=experts, zeros=zeros,
             scaling=6.0)
    return _f32(r.normal(size=(t, h))), p, z


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
@pytest.mark.parametrize("norm_topk", [True, False])
def test_route_scores_and_gates_written_out(scoring, norm_topk):
    x, p, z = _moe_case(1)
    experts, gates = ops.route(x, p["wg"], p["bias"], 3, 6.0,
                               scoring=scoring, norm_topk=norm_topk)
    logits = np.asarray(x, np.float64) @ np.asarray(p["wg"], np.float64)
    if scoring == "softmax":
        e = np.exp(logits - logits.max(-1, keepdims=True))
        s = e / e.sum(-1, keepdims=True)
    else:
        s = 1 / (1 + np.exp(-logits))
    want = np.argsort(-(s + np.asarray(p["bias"])), axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want, -1))
    chosen = np.take_along_axis(s, np.asarray(experts), 1)
    if norm_topk:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    np.testing.assert_allclose(gates, 6.0 * chosen, rtol=1e-5, atol=1e-7)


def test_route_defaults_are_the_sigmoid_renormalised_router():
    x, p, _z = _moe_case(2)
    a = ops.route(x, p["wg"], p["bias"], 3, 2.5)
    b = ops.route(x, p["wg"], p["bias"], 3, 2.5, scoring="sigmoid",
                  norm_topk=True)
    for got, want in zip(a, b):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(a[1].sum(-1), 2.5, rtol=1e-6)


def _run_moe_op(x, p, z, **attrs):
    """The ``moe_ffn`` op through a program. -> the fetched outputs."""
    held, h, i = p["w1"].shape
    wide = z["experts"] + z["zeros"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xv = fluid.layers.data(name="x", shape=[h], dtype="float32")
        make = fluid.layers.create_parameter
        outs = fluid.layers.moe_ffn(
            xv, make([h, wide], "float32", name="wg"),
            make([wide], "float32", name="bias"),
            make([held, h, i], "float32", name="w1"),
            make([held, h, i], "float32", name="w3"),
            make([held, i, h], "float32", name="w2"),
            num_experts=z["experts"], experts_per_token=z["topk"],
            expert_offset=z["offset"], scaling=z["scaling"], **attrs)
    scope = fluid.core.Scope()
    for name, value in p.items():
        scope.set(name, np.asarray(value))
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        got = exe.run(main, feed={"x": np.asarray(x)}, fetch_list=list(outs))
    return main, [np.asarray(g) for g in got]


def test_moe_ffn_op_with_identity_experts_is_the_naive_form():
    """softmax scores, gates as they are, 4 identity experts after the 8:
    the op holding experts 2..4 gives the reference's naive share plus
    ``gate * x`` of the identity assignments, and counts both."""
    x, p, z = _moe_case(3)
    experts, gates = ref.route(x, p, z)
    want = (ref.experts_naive(x, experts, gates, p, z)
            + ref.identity_experts(x, experts, gates, z))
    np.testing.assert_allclose(
        ref.experts_held(x, experts, gates, p, z)
        + ref.identity_experts(x, experts, gates, z), want, atol=2e-5,
        rtol=0)
    main, (out, counts, zero_count) = _run_moe_op(
        x, p, z, scoring="softmax", norm_topk=False, zero_experts=4)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=0)
    experts = np.asarray(experts)
    np.testing.assert_array_equal(
        counts, [(experts == g).sum() for g in (2, 3, 4)])
    assert zero_count.tolist() == [int((experts >= 8).sum())]
    assert 0 < zero_count[0] < experts.size
    op = [o for o in main.global_block().ops if o.type == "moe_ffn"][0]
    assert op.attr("zero_experts") == 4 and not op.has_attr("scope")


def test_moe_ffn_attributes_at_their_defaults_are_left_out_of_the_op():
    """A program that asks for none of the new attributes is op for op
    what it was: no new attribute, no third output."""
    x, p, z = _moe_case(4, zeros=0)
    main, got = _run_moe_op(x, p, z)
    assert len(got) == 2
    op = [o for o in main.global_block().ops if o.type == "moe_ffn"][0]
    assert sorted(op.attrs) == sorted(
        ["num_experts", "experts_per_token", "expert_offset", "scaling"]
        + [a for a in op.attrs if a.startswith("op_")])
    assert "ZeroCount" not in op.outputs
    oe, og = ops.route(x, p["wg"], p["bias"], 3, 6.0)
    want, _counts = ops.grouped_experts(x, oe, og, p["w1"], p["w3"],
                                        p["w2"], 2)
    np.testing.assert_allclose(got[0], want, atol=1e-6, rtol=0)


def test_a_token_whose_picks_are_all_identity_experts_costs_no_grouped_row():
    """The router's bias puts the 4 identity experts first for every
    token (top 3 of them): the result is ``6 sum(p) x``, no held expert
    counts an assignment, and every grouped product has zero rows."""
    x, p, z = _moe_case(5)
    p["bias"] = p["bias"].at[8:].add(10.0)
    experts, gates = ops.route(x, p["wg"], p["bias"], 3, 6.0,
                               scoring="softmax", norm_topk=False)
    assert (np.asarray(experts) >= 8).all()
    y, counts = ops.grouped_experts(x, experts, gates, p["w1"], p["w3"],
                                    p["w2"], 2)
    assert counts.tolist() == [0, 0, 0] and not np.asarray(y).any()
    same, n = ops.identity_experts(x, experts, gates, 8)
    assert int(n) == experts.size
    s = jax.nn.softmax(x @ p["wg"], axis=-1)
    picked = jnp.take_along_axis(s, experts, 1).sum(-1, keepdims=True)
    np.testing.assert_allclose(same, 6.0 * picked * x, rtol=1e-5, atol=1e-7)
    _main, (out, _c, zero_count) = _run_moe_op(
        x, p, z, scoring="softmax", norm_topk=False, zero_experts=4)
    np.testing.assert_allclose(out, same, rtol=1e-5, atol=1e-7)
    assert zero_count.tolist() == [experts.size]


def test_all_shares_and_the_identity_part_once_add_up_to_the_uncut_layer():
    """One double layer with 8 experts and 4 identity experts, top 3: the
    op's held part over each of 4 shares of 2 (``expert_offset``), summed,
    plus what every chip computes alike counted ONCE (both attentions,
    both feed-forwards, the identity experts), is the uncut reference
    layer."""
    whole = dict(CFG, num_layers=1, n_routed_experts=8,
                 published={"n_routed_experts": 8})
    params = {k: _f32(v) for k, v in ref.init_params(11, whole).items()}
    x = _f32(_rng(12).normal(size=(2, 16, whole["hidden_size"])))
    z = ref.sizes(whole)
    assert (z["held"], z["experts"], z["zeros"]) == (8, 8, 4)
    p = ref.common.nest(params)["l0"]
    want = ref._layer(x, p, z=ref._freeze(whole), kind="highest")
    mm = ref._mm("highest")
    att = lambda j, v: v + jax.lax.map(  # noqa: E731
        lambda row: ref.mla(ref.rms_norm(row, p["ln_att%d" % j], z["eps"]),
                            p["att%d" % j], z, mm), v)
    a1 = att(0, x)
    u1 = ref.rms_norm(a1, p["ln_ffn0"], z["eps"]).reshape(32, -1)
    experts, gates = ops.route(u1, p["moe"]["wg"], p["moe"]["bias"], 3, 6.0,
                               scoring="softmax", norm_topk=False)
    same, zero_count = ops.identity_experts(u1, experts, gates, 8)
    shortcut, held = same, 0
    for offset in (0, 2, 4, 6):
        part, counts = ops.grouped_experts(
            u1, experts, gates, *(p["moe"][w][offset:offset + 2]
                                  for w in ("w1", "w3", "w2")), offset)
        shortcut = shortcut + part
        held += int(counts.sum())
    assert held + int(zero_count) == 32 * 3   # every assignment is someone's
    a2 = att(1, a1 + ref.gated_mlp(u1, p["ffn0"], mm).reshape(x.shape))
    y = (a2 + ref.gated_mlp(ref.rms_norm(a2, p["ln_ffn1"], z["eps"]),
                            p["ffn1"], mm) + shortcut.reshape(x.shape))
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=0)


# -- (b) latent attention with the query LoRA and both scales -----------------

def _attention_program(cfg, t, cache_mode=None, blocks=7, block=4,
                       max_blocks=6):
    """``deepseek.mla_attention`` alone in a program: no cache (up-projected
    over the window's own rows) or the T = 1 step (absorbed, paged)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[t, cfg.hidden_size],
                              dtype="float32")
        pos = fluid.layers.data(name="pos", shape=[t, 1], dtype="int64")
        cache = None
        if cache_mode:
            tables = fluid.layers.data(name="tables", shape=[max_blocks],
                                       dtype="int64")
            write = fluid.layers.reshape(pos, shape=[-1])
            (pool,), = cache_kinds.declare_pools(
                longcat_flash.cache_kinds(cfg)[:1], blocks, block)
            cache = {"mode": cache_mode, "tables": tables, "pos": write,
                     "lengths": fluid.layers.scale(write, bias=1.0),
                     "pool": pool}
        out = deepseek.mla_attention(x, pos, cfg, "att", cache=cache)
    return main, out


def _attention_weights(cfg, seed):
    r = _rng(seed)
    heads, qk = cfg.num_attention_heads, (cfg.qk_nope_head_dim
                                          + cfg.qk_rope_head_dim)
    h, lat, rope = cfg.hidden_size, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    shapes = {"att_qa.w_0": (h, cfg.q_lora_rank),
              "att_qb.w_0": (cfg.q_lora_rank, heads * qk),
              "att_kva.w_0": (h, lat + rope),
              "att_kvb.w_0": (lat, heads * (cfg.qk_nope_head_dim
                                            + cfg.v_head_dim)),
              "att_o.w_0": (heads * cfg.v_head_dim, h)}
    w = {k: (0.2 * r.normal(size=s)).astype("float32")
         for k, s in shapes.items()}
    w["att_q_norm"] = (1 + 0.1 * r.normal(size=cfg.q_lora_rank)).astype(
        "float32")
    w["att_kv_norm"] = (1 + 0.1 * r.normal(size=lat)).astype("float32")
    return w


def test_query_lora_and_both_scales_are_the_references_attention():
    cfg = longcat_flash.LongcatFlashConfig.tiny()
    assert cfg.q_lora_scale == 2.0
    assert cfg.kv_lora_scale == pytest.approx(2 ** 0.5)
    t = 12
    main, out = _attention_program(cfg, t)
    w = _attention_weights(cfg, 21)
    scope = fluid.core.Scope()
    for name, value in w.items():
        scope.set(name, value)
    x = _rng(22).normal(size=(2, t, cfg.hidden_size)).astype("float32")
    pos = np.tile(np.arange(t).reshape(1, t, 1), (2, 1, 1)).astype("int64")
    with fluid.scope_guard(scope):
        (got,) = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"x": x, "pos": pos}, fetch_list=[out])
    p = {"wqa": w["att_qa.w_0"], "q_norm": w["att_q_norm"],
         "wqb": w["att_qb.w_0"], "wkva": w["att_kva.w_0"],
         "kv_norm": w["att_kv_norm"], "wkvb": w["att_kvb.w_0"],
         "wo": w["att_o.w_0"]}
    z = ref.sizes(dict(CFG))
    for row in range(2):
        want = ref.mla(_f32(x[row]), {k: _f32(v) for k, v in p.items()}, z,
                       ref._mm("highest"))
        np.testing.assert_allclose(got[row], want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["dense_fallback", "interpreted_kernel"])
def test_absorbed_is_up_projected_with_both_lora_scales(kernel):
    """Ten tokens, one a step through the ABSORBED form against the paged
    pool (which keeps the SCALED latent), against the same ten as one
    window in the UP-PROJECTED form."""
    cfg = longcat_flash.LongcatFlashConfig.tiny(flash_interpret=kernel)
    t, slots = 10, 2
    w = _attention_weights(cfg, 23)
    x = _rng(24).normal(size=(slots, t, cfg.hidden_size)).astype("float32")
    window, out = _attention_program(cfg, t)
    step, step_out = _attention_program(cfg, 1, cache_mode="paged_step")
    scope = fluid.core.Scope()
    for name, value in w.items():
        scope.set(name, value)
    (pool,) = longcat_flash.cache_kinds(cfg)[0]
    scope.set(pool.name(7, 4), np.zeros(pool.shape(7, 4), "float32"))
    exe = fluid.Executor(fluid.CPUPlace())
    tables = np.array([[1, 2, 3, 0, 0, 0], [4, 5, 6, 0, 0, 0]], "int64")
    with fluid.scope_guard(scope):
        (want,) = exe.run(window, feed={
            "x": x, "pos": np.tile(np.arange(t).reshape(1, t, 1),
                                   (slots, 1, 1)).astype("int64")},
            fetch_list=[out])
        for i in range(t):
            (got,) = exe.run(step, feed={
                "x": x[:, i:i + 1], "tables": tables,
                "pos": np.full((slots, 1, 1), i, "int64")},
                fetch_list=[step_out])
            np.testing.assert_allclose(got[:, 0], want[:, i], atol=2e-5,
                                       rtol=0)
        rows = np.asarray(scope.get(pool.name(7, 4)))
    # the pool keeps the scaled normed latent: rms sqrt(2) x the norm's gain
    latent = rows[1, 0, :, :cfg.kv_lora_rank] / w["att_kv_norm"]
    np.testing.assert_allclose(np.sqrt((latent ** 2).mean(-1)), 2 ** 0.5,
                               rtol=1e-3)


def test_deepseek_attention_without_a_query_lora_builds_what_it_built():
    """``q_lora_rank`` None and scales of 1: one query projection, no
    norm on it, no scale op."""
    cfg = deepseek.DeepseekConfig.tiny()
    assert (cfg.q_lora_rank, cfg.q_lora_scale, cfg.kv_lora_scale) == (
        None, 1.0, 1.0)
    main, _out = _attention_program(cfg, 8)
    names = {v.name for v in main.list_vars()}
    assert "att_q.w_0" in names and "att_qa.w_0" not in names
    assert [o.type for o in main.global_block().ops].count("scale") == 0
    assert deepseek.DeepseekConfig.from_config(
        {"q_lora_rank": None, "num_hidden_layers": 2}).q_lora_rank is None


# -- (c) the model: export, then windows and steps through the engine ---------

def _served_scope(params):
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
    return longcat_flash.LongcatFlashConfig.from_config(
        CFG, **dict(dict(dtype="float32"), **kw))


def test_the_toy_keeps_two_of_eight_experts_and_the_router_all_twelve():
    cfg = _config()
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_offset,
            cfg.zero_experts) == (8, 2, 0, 4)
    assert (cfg.router_scoring, cfg.norm_topk_prob) == ("softmax", False)
    with fluid.unique_name.guard():
        infer, _s, _f, _l = longcat_flash.build_infer(cfg, 8)
    shapes = {v.name: tuple(v.shape) for v in infer.list_vars()}
    assert shapes["lc_0_moe_router.w_0"] == (64, 12)
    assert shapes["lc_0_moe_experts_w1"] == (2, 64, 32)
    assert "lc_0_moe_shared_w1.w_0" not in shapes


def test_exported_float32_program_is_the_reference(seeded, tmp_path):
    params, ids, want = seeded
    cfg = _config()
    with fluid.unique_name.guard():
        infer, _s, feeds, logits = longcat_flash.build_infer(
            cfg, ids.shape[1])
    declared = {v.name: tuple(v.shape) for v in infer.list_vars()
                if getattr(v, "is_parameter", False)}
    assert declared == {var: tuple(params[leaf].shape) for leaf, var in
                        family.leaf_to_var(CFG).items()}
    scope = _served_scope(params)
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
        infer, _s, _f, _l = longcat_flash.build_infer(cfg, 8)
    args = dict(slots=2, max_len=64, block_size=4, prefill_buckets=[8, 16],
                prefill_chunk=16, param_program=infer, model=longcat_flash)
    args.update(kw)
    return decode.DecodeEngine(cfg, place=fluid.CPUPlace(),
                               scope=_served_scope(params), **args)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["dense_fallback", "interpreted_kernel"])
@pytest.mark.parametrize("prompt_len,windows", [(37, 3), (21, 2)])
def test_engine_windows_then_steps_are_the_reference_forward(
        seeded, monkeypatch, kernel, prompt_len, windows):
    """A prompt split over windows of at most 16 (37: 16, 16, 5; 21: 16
    and 5 padded to 8), each landing in all four latent pools through the
    slot's one table, then 9 tokens by T = 1 steps next to a second,
    shorter stream admitted in one window. Every logits row a token is
    picked from is compared with the reference's full forward over
    prompt + tokens: logits, not tokens."""
    params = seeded[0]
    cfg = _config(flash_interpret=kernel)
    eng = _engine(cfg, params).start(loop=False)
    seen = record_picked_rows(monkeypatch, eng)
    try:
        prompts = [list(_rng(41).integers(0, 211, prompt_len)),
                   list(_rng(42).integers(0, 211, 6))]
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, (10, 4))]
        for _ in range(40):
            eng._tick()
            if all(s.done for s in streams):
                break
        assert streams[0].admit_windows == windows
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


def test_spans_and_counters_carry_the_identity_assignments(seeded):
    from paddle_tpu.fluid import profiler
    from paddle_tpu.observability import registry, trace

    cfg = _config()
    eng = _engine(cfg, seeded[0]).start(loop=False)
    before = profiler.get_counters()
    try:
        stream = eng.submit([1, 2, 3, 4, 5], max_new_tokens=3)
        for _ in range(8):
            eng._tick()
        assert stream.done
        spans = trace.get_spans()
        step = [s for s in spans if s["name"] == "decode_paged_step"
                and "zero_assignments" in (s.get("args") or {})][-1]["args"]
        # 2 slots (one idle, fed token 0), 2 double layers, top 3 of 12
        assert 0 <= step["assignments"] <= 2 * 2 * 3
        assert step["assignments"] + step["zero_assignments"] <= 2 * 2 * 3
        assert step["experts_hit"] <= 2 * 2
        assert step["expert_load_max"] <= 2
        # a token's row in both pools of a double layer, as the kernel reads
        assert step["latent_rows_live"] >= 2 * 5
        assert step["latent_rows_live"] % 2 == 0
        after = profiler.get_counters()
        rose = lambda k: after.get(k, 0) - before.get(k, 0)  # noqa: E731
        assert rose("moe_zero_assignments") > 0
        assert rose("moe_assignments") >= 0
        assert "moe_zero_assignments" in registry.render_prometheus()
        tick = [s for s in spans if s["name"] == "engine_tick"][-1]["args"]
        assert tick["kv_bytes_per_token"] == 4 * 128 * 4
    finally:
        eng.stop()


def test_prefix_index_shares_a_block_in_every_pool(seeded):
    """A block is a block in all four pools: a second request with the
    same 12-token head reuses three blocks and decodes the same tokens."""
    eng = _engine(_config(), seeded[0], prefix_cache_mb=1.0).start(loop=False)
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


# -- (d) what the model tells the engine --------------------------------------

def test_cache_kinds_are_two_latent_pools_a_double_layer_and_no_pair():
    cfg = _config()
    kinds = longcat_flash.cache_kinds(cfg)
    assert len(kinds) == 2 * cfg.num_layers == 4
    for i, layer in enumerate(kinds):
        (pool,) = layer
        assert isinstance(pool, cache_kinds.CachePool)
        assert pool.prefix == "lc_paged_latent_%d" % i
        assert pool.shape(9, 4) == [9, 1, 4, 128]
    assert cache_kinds.bytes_per_token(kinds) == 4 * 128 * 4
    with pytest.raises(TypeError, match="layer 0"):
        cache_kinds.kv_pools(kinds)


@pytest.mark.parametrize("mode,kwargs", [
    ("spec_tokens", dict(block_size=4, spec_tokens=3)),
    ("tp", dict(block_size=4, tp=2)),
])
def test_session_refuses_a_mode_by_name(mode, kwargs):
    cfg = longcat_flash.LongcatFlashConfig.tiny()
    with pytest.raises(NotImplementedError) as err:
        decode.DecodeSession(cfg, place=fluid.CPUPlace(), slots=2,
                             max_len=32, model=longcat_flash, **kwargs)
    assert longcat_flash.UNSUPPORTED[mode] in str(err.value)


def test_step_program_refuses_a_speculative_width_by_name():
    with pytest.raises(NotImplementedError) as err:
        longcat_flash.build_paged_step(
            longcat_flash.LongcatFlashConfig.tiny(), 2, 9, 4, 8, step_w=3)
    assert longcat_flash.UNSUPPORTED["spec_tokens"] in str(err.value)


def test_engine_refuses_the_host_kv_tier_by_name(seeded):
    from paddle_tpu.fluid import flags

    flags.set_flags({"FLAGS_kv_tier_host_mb": 1.0})
    try:
        eng = _engine(_config(), seeded[0], prefix_cache_mb=1.0)
        with pytest.raises(NotImplementedError) as err:
            eng.start(loop=False)
        assert longcat_flash.UNSUPPORTED["kv_host_tier"] in str(err.value)
    finally:
        flags.set_flags({"FLAGS_kv_tier_host_mb": 0.0})


@pytest.mark.parametrize("call", [
    lambda eng: eng.block_row_shape(),
    lambda eng: eng.offer_blocks([]),
    lambda eng: eng.request_export([1, 2, 3]),
], ids=["block_row_shape", "offer_blocks", "request_export"])
def test_engine_refuses_block_export_by_name(seeded, call):
    eng = _engine(_config(), seeded[0])
    with pytest.raises(NotImplementedError) as err:
        call(eng)
    assert longcat_flash.UNSUPPORTED["block_export"] in str(err.value)


def test_published_widths_cost_what_the_issue_reckons():
    """LongCat-Flash-Omni as published, four double layers, 16 of 512
    experts, an eighth of the vocabulary: 638.87 M parameters a double
    layer outside its experts, 37.75 M an expert, 5.17 B on the chip
    (10.35 GB in bfloat16); a token costs 8 latent rows of 640 lanes,
    10,240 B, and 64 slots of 5632 positions 3.69 GB."""
    cfg = longcat_flash.LongcatFlashConfig(
        num_layers=4, vocab_size=16384, experts_held=16)
    assert (cfg.q_lora_scale, round(cfg.kv_lora_scale, 3)) == (2.0, 3.464)
    with fluid.unique_name.guard():
        infer, _s, _f, _l = longcat_flash.build_infer(cfg, 8)
    sizes = {v.name: int(np.prod(v.shape)) for v in infer.list_vars()
             if getattr(v, "is_parameter", False)}
    layer = {k: n for k, n in sizes.items() if k.startswith("lc_0_")}
    experts = sum(n for k, n in layer.items() if "_experts_" in k)
    assert experts == 16 * 3 * 6144 * 2048 == 16 * 37748736
    outside = sum(layer.values()) - experts
    assert outside == 638874368       # the issue's 638.8 M, cut not rounded
    attention = sum(n for k, n in layer.items() if k.startswith("lc_0_att0"))
    assert round(attention / 1e6, 1) == 90.6
    assert layer["lc_0_moe_router.w_0"] == 6144 * 768
    total = sum(sizes.values())
    assert round(total / 1e9, 2) == 5.17 and round(2 * total / 1e9, 2) == 10.35
    kinds = longcat_flash.cache_kinds(cfg)
    assert len(kinds) == 8 and cfg.latent_row == 640
    assert cache_kinds.bytes_per_token(kinds) == 8 * 640 * 2 == 10240
    assert round(64 * 5632 * 10240 / 1e9, 2) == 3.69
