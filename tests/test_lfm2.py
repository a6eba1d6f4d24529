"""``models/lfm2.py`` (a TRAINED ``lfm2_moe`` decoder) against the plain
reference ``benchmark/references/lfm2.py`` at toy widths on the CPU, the
flash kernels under the Pallas interpreter: the loss and every leaf's
gradient over three steps, the new gradients against finite differences,
the expert shares against the uncut layer, grouped-query dK/dV, and that
registering gradients left the served models' programs as they were.
"""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from benchmark.references import common as ref_common
from benchmark.references import lfm2 as ref
from op_test import OpTest
from paddle_tpu.models import decoder_common, deepseek, lfm2
from paddle_tpu.models import longcat_flash, solar_open2

SEQ, ROWS, LR = 16, 2, 1e-3
CONFIG = dict(
    vocab_size=211, hidden_size=32, intermediate_size=48,
    num_hidden_layers=5, num_dense_layers=2,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
    layers_kept=[1, 2, 3, 4, 5],
    num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
    conv_bias=False, moe_intermediate_size=16, num_experts=2,
    expert_offset=2, num_experts_per_tok=2, norm_topk_prob=True,
    use_expert_bias=True, routed_scaling_factor=1, norm_eps=1e-5,
    rope_theta=1000000, max_position_embeddings=64,
    published={"num_experts": 8})


def _leaf_vars():
    from benchmark.families import lfm2 as family

    return family.leaf_to_var(CONFIG), family.buffers(CONFIG)


LEAVES = sorted(_leaf_vars()[0])


def _batches(steps):
    rng = np.random.default_rng(7)
    return [rng.integers(0, CONFIG["vocab_size"], (ROWS, SEQ))
            for _ in range(steps)]


def _feed(ids):
    return {"ids": ids.reshape(ROWS, SEQ, 1).astype("int64"),
            "pos_ids": np.tile(np.arange(SEQ)[None, :, None],
                               (ROWS, 1, 1)).astype("int64")}


def _program(use_amp=False):
    cfg = lfm2.LFM2Config.from_config(CONFIG, flash_interpret=True)
    with fluid.unique_name.guard():
        built = lfm2.build_lfm2_train(cfg, SEQ, LR, use_amp=use_amp)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    exe.run(built[1], scope=scope)
    leaves, buffers = _leaf_vars()
    params = ref.init_params(11, CONFIG)
    for leaf, var in dict(leaves, **buffers).items():
        assert tuple(scope.get(var).shape) == tuple(params[leaf].shape), leaf
        scope.set(var, jnp.copy(params[leaf]))
    return built, exe, scope, params


@pytest.fixture(scope="module")
def three_steps():
    """Three Adam steps of the float32 program, every gradient fetched,
    beside three of the reference. -> per step (program loss, reference
    loss, {leaf: (program gradient, reference gradient)})."""
    (main, _startup, _feeds, loss, counts), exe, scope, params = _program()
    leaves, _buffers = _leaf_vars()
    grad_fn = jax.jit(jax.value_and_grad(ref.loss_fn(CONFIG)))
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    v = {k: jnp.zeros_like(x) for k, x in params.items()}
    out = []
    for t, ids in enumerate(_batches(3), 1):
        fetched = exe.run(
            main, feed=_feed(ids), scope=scope,
            fetch_list=[loss, counts] + [leaves[k] + "@GRAD"
                                         for k in LEAVES])
        want, grads = grad_fn(params, {"ids": jnp.asarray(ids, jnp.int32)})
        out.append((float(np.asarray(fetched[0]).reshape(-1)[0]),
                    float(want), np.asarray(fetched[1]),
                    {k: (np.asarray(g), np.asarray(grads[k]))
                     for k, g in zip(LEAVES, fetched[2:])}))
        params, m, v = ref_common.adam_step(params, grads, m, v, t, LR)
    return out


@pytest.mark.parametrize("step", [0, 1, 2])
def test_loss_matches_reference(three_steps, step):
    got, want, counts, _grads = three_steps[step]
    assert got == pytest.approx(want, abs=2e-5)
    # 2 of 8 experts held, top 2: a quarter of the assignments on average
    assert counts.shape == (4, 2) and 0 < counts.sum() < 4 * ROWS * SEQ * 2


@pytest.mark.parametrize("leaf", LEAVES)
def test_leaf_gradient_matches_reference(three_steps, leaf):
    for _got, _want, _counts, grads in three_steps:
        got, want = grads[leaf]
        scale = max(float(np.abs(want).max()), 1e-6)
        np.testing.assert_allclose(got.reshape(want.shape) / scale,
                                   want / scale, atol=2e-3)


def test_router_bias_is_a_buffer_nobody_updates(three_steps):
    (main, _s, _f, _loss, _counts), _exe, _scope, _params = _program()
    _leaves, buffers = _leaf_vars()
    block = main.global_block()
    trained = {op.inputs["Param"][0] for op in block.ops
               if op.type == "adam"}
    trained = {getattr(n, "name", n) for n in trained}
    for var in buffers.values():
        assert var not in trained
        assert not block.has_var(var + "@GRAD")
        assert not block.var(var).trainable


def test_no_shared_expert_parameter_is_built():
    (main, _s, _f, _loss, _counts), _exe, _scope, _params = _program()
    names = [p.name for p in main.global_block().all_parameters()]
    assert names and not [n for n in names if "shared" in n]
    assert set(names) == set(_leaf_vars()[0].values()) | set(
        _leaf_vars()[1].values())


def test_amp_program_follows_the_reference():
    """bf16 AMP through the same step call as the benchmark's: losses
    within bf16's rounding of the reference's."""
    (main, _s, _f, loss, counts), exe, scope, params = _program(
        use_amp=True)
    batches = _batches(3)
    got = [lfm2.run_train_step(exe, main, _feed(ids), loss, counts,
                               scope)[0] for ids in batches]
    want, _g, _d = ref.train(CONFIG, params,
                             [{"ids": b} for b in batches], LR)
    np.testing.assert_allclose(got, want, atol=5e-3)
    # the router and the norms' gains stay float32 under the rewrite
    casts = {n for op in main.global_block().ops if op.type == "cast"
             for n in op.input_arg_names}
    assert not [n for n in casts if "router" in n or n.endswith("_norm")]


def test_train_step_span_and_counters():
    from paddle_tpu.fluid import profiler
    from paddle_tpu.observability import trace

    (main, _s, _f, loss, counts), exe, scope, _params = _program()
    before = profiler.get_counters()
    _loss, held = lfm2.run_train_step(exe, main, _feed(_batches(1)[0]), loss,
                                      counts, scope)
    after = profiler.get_counters()
    assert (after["moe_train_assignments"]
            - before.get("moe_train_assignments", 0)) == held.sum()
    assert (after["moe_train_experts_hit"]
            - before.get("moe_train_experts_hit", 0)) == (held > 0).sum()
    span = [s for s in trace.get_spans() if s["name"] == "train_step"][-1]
    assert span["args"]["assignments"] == held.sum()
    assert span["args"]["expert_load_max"] == held.max()
    inside = [s for s in trace.get_spans()
              if s["name"] == "executor_run" and s["start"] >= span["start"]
              and s["end"] <= span["end"]]
    assert len(inside) == 1     # ONE Executor.run a step


# -- the new gradients against finite differences ----------------------------

def _op_case(op_type, inputs, attrs, outputs):
    case = OpTest()
    case.op_type, case.inputs, case.attrs = op_type, inputs, attrs
    case.outputs = outputs
    return case


def _rand(rng, *shape):
    return rng.uniform(-1, 1, shape).astype(np.float32)


def _moe_inputs(rng, tokens=6, hidden=8, width=5, experts=6, held=3):
    # a bias far apart fixes the choice: a finite difference must not
    # cross a top-k boundary (experts 1, 2 and 4 always win; 1 and 2 are
    # held with offset 1, 4 is held elsewhere)
    bias = np.array([0.0, 30.0, 20.0, 0.5, 10.0, 0.2], np.float32)
    return {"X": _rand(rng, 2, tokens // 2, hidden),
            "RouterW": _rand(rng, hidden, experts), "RouterBias": bias,
            "W1": _rand(rng, held, hidden, width),
            "W3": _rand(rng, held, hidden, width),
            "W2": _rand(rng, held, width, hidden)}


MOE_ATTRS = {"num_experts": 6, "experts_per_token": 3, "expert_offset": 1,
             "scaling": 1.5, "norm_eps": 1e-6}


def _numeric_cases():
    rng = np.random.RandomState(3)
    x = _rand(rng, 2, 5, 12)
    pos = np.tile(np.arange(5)[None], (2, 1)).astype(np.int64)
    zeros = np.zeros_like(x)
    yield "rms_norm", {"X": x, "Scale": 1 + 0.1 * _rand(rng, 12)}, {
        "epsilon": 1e-5}, {"Out": zeros}, ["X", "Scale"]
    for form in (False, True):
        yield "rotary_embedding", {"X": x, "Pos": pos}, {
            "head_dim": 6, "rope_dim": 4, "theta": 100.0,
            "interleaved": form}, {"Out": zeros}, ["X"]
    yield "swiglu", {"Gate": x, "Up": _rand(rng, 2, 5, 12)}, {}, {
        "Out": zeros}, ["Gate", "Up"]
    yield "gated_short_conv", {"X": x, "ConvW": _rand(rng, 3, 4)}, {}, {
        "Out": np.zeros((2, 5, 4), np.float32)}, ["X", "ConvW"]
    inputs = _moe_inputs(rng)
    yield "moe_ffn", inputs, MOE_ATTRS, {
        "Out": np.zeros_like(inputs["X"]),
        "Counts": np.zeros(3, np.int32)}, ["X", "RouterW", "W1", "W3", "W2"]


NUMERIC = [pytest.param(op_type, inputs, attrs, outputs, slot,
                        id="%s%s-%s" % (op_type, "-interleaved" if attrs.get(
                            "interleaved") else "", slot))
           for op_type, inputs, attrs, outputs, slots in _numeric_cases()
           for slot in slots]


@pytest.mark.parametrize("op_type,inputs,attrs,outputs,slot", NUMERIC)
def test_gradient_against_finite_differences(op_type, inputs, attrs, outputs,
                                             slot):
    _op_case(op_type, inputs, attrs, outputs).check_grad(
        [slot], "Out", max_relative_error=0.01)


def test_gated_short_conv_forward():
    rng = np.random.RandomState(5)
    x, w = _rand(rng, 2, 7, 9), _rand(rng, 3, 3)
    b, c, v = np.split(x, 3, axis=-1)
    bx = np.concatenate([np.zeros((2, 2, 3), np.float32), b * v], axis=1)
    want = c * sum(w[j][None, None] * bx[:, j:j + 7] for j in range(3))
    _op_case("gated_short_conv", {"X": x, "ConvW": w}, {},
             {"Out": want}).check_output(atol=1e-6)


# -- the shares add up --------------------------------------------------------

def _expert_layer(x, cot, p, offset, held, experts=32, k=4):
    """(out, dX, dRouterW) of ``moe_ffn`` over the experts ``offset ..
    offset + held - 1`` with the cotangent ``cot`` of its output."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds = {}
        for name, value in (("x", x), ("cot", cot), ("wg", p["wg"]),
                            ("bias", p["bias"])):
            feeds[name] = fluid.layers.data(
                name=name, shape=list(value.shape), dtype="float32",
                append_batch_size=False)
            feeds[name].stop_gradient = name in ("cot", "bias")
        stacks = []
        for name in ("w1", "w3", "w2"):
            value = p[name][offset:offset + held]
            stacks.append(fluid.layers.assign(np.asarray(value)))
        out, _counts = fluid.layers.moe_ffn(
            feeds["x"], feeds["wg"], feeds["bias"], *stacks,
            num_experts=experts, experts_per_token=k, expert_offset=offset,
            norm_eps=1e-6)
        loss = fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(out, feeds["cot"]))
        fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    return [np.asarray(a) for a in exe.run(
        main, feed={"x": x, "cot": cot, "wg": np.asarray(p["wg"]),
                    "bias": np.asarray(p["bias"])},
        fetch_list=[out, "x@GRAD", "wg@GRAD"], scope=fluid.core.Scope())]


@pytest.fixture(scope="module")
def shares():
    """Four shares of 8 of 32 experts, summed, beside the uncut
    reference's expert layer: (out, dX, dRouterW) of each."""
    rng = np.random.RandomState(9)
    hidden, width, tokens = 16, 8, 24
    p = {"wg": _rand(rng, hidden, 32), "bias": 0.1 * _rand(rng, 32),
         "w1": _rand(rng, 32, hidden, width),
         "w3": _rand(rng, 32, hidden, width),
         "w2": _rand(rng, 32, width, hidden)}
    x, cot = _rand(rng, tokens, hidden), _rand(rng, tokens, hidden)
    parts = [_expert_layer(x, cot, p, offset, 8)
             for offset in (0, 8, 16, 24)]
    summed = [sum(part[i] for part in parts) for i in range(3)]
    z = dict(held=32, offset=0, topk=4, scaling=1.0, norm_topk=True)
    p = {k: jnp.asarray(v) for k, v in p.items()}

    def whole(x, wg):
        out = ref.experts_held(x, dict(p, wg=wg), z, ref_common.mm_highest)
        return (out * cot).sum(), out

    (_loss, out), (dx, dwg) = jax.value_and_grad(
        whole, argnums=(0, 1), has_aux=True)(jnp.asarray(x), p["wg"])
    return summed, [np.asarray(a) for a in (out, dx, dwg)]


@pytest.mark.parametrize("what", ["forward", "dX", "dRouterW"])
def test_four_shares_add_up_to_the_uncut_layer(shares, what):
    at = ["forward", "dX", "dRouterW"].index(what)
    summed, whole = shares
    np.testing.assert_allclose(summed[at], whole[at], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("expert", [1, 2])
def test_no_token_dropped_when_all_pick_one_held_expert(expert):
    """A bias that makes every token choose one held expert: it receives
    all of them (no capacity), and each token's result is that expert's."""
    rng = np.random.RandomState(13)
    inputs = _moe_inputs(rng, tokens=12)
    bias = np.zeros(6, np.float32)
    bias[expert] = 50.0
    inputs["RouterBias"] = bias
    attrs = dict(MOE_ATTRS, experts_per_token=1)
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        feeds = {k: fluid.layers.data(name=k, shape=list(v.shape),
                                      dtype="float32",
                                      append_batch_size=False)
                 for k, v in inputs.items()}
        out, counts = fluid.layers.moe_ffn(
            feeds["X"], feeds["RouterW"], feeds["RouterBias"], feeds["W1"],
            feeds["W3"], feeds["W2"], num_experts=6, experts_per_token=1,
            expert_offset=1, scaling=attrs["scaling"], norm_eps=1e-6)
    got, held = fluid.Executor(fluid.CPUPlace()).run(
        main, feed=inputs, fetch_list=[out, counts],
        scope=fluid.core.Scope())
    want_counts = np.zeros(3, np.int32)
    want_counts[expert - 1] = 12
    np.testing.assert_array_equal(np.asarray(held), want_counts)
    x = inputs["X"].reshape(12, -1)
    s = 1 / (1 + np.exp(-(x @ inputs["RouterW"])[:, expert]))
    gate = 1.5 * s / (s + 1e-6)
    w1, w3, w2 = (inputs[n][expert - 1] for n in ("W1", "W3", "W2"))
    a = x @ w1
    want = gate[:, None] * (((a / (1 + np.exp(-a))) * (x @ w3)) @ w2)
    np.testing.assert_allclose(np.asarray(got).reshape(12, -1), want,
                               rtol=1e-4, atol=1e-5)


# -- grouped queries through the training flash kernels ------------------------

def _plain_gqa(q, k, v, cot):
    """sum(cot * attention) with 4 query heads a key head, no repeat: the
    group is an axis of the einsum."""
    b, heads, s, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(b, kvh, heads // kvh, s, d)
    sc = jnp.einsum("bgrsd,bgtd->bgrst", qg, k) * d ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
    o = jnp.einsum("bgrst,bgtd->bgrsd", jax.nn.softmax(sc, -1), v)
    return (o.reshape(q.shape) * cot).sum()


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernels", "dense"])
def test_grouped_query_gradients_add_up_over_the_group(interpret):
    rng = np.random.RandomState(17)
    q, cot = _rand(rng, 2, 8, 128, 16), _rand(rng, 2, 8, 128, 16)
    k, v = _rand(rng, 2, 2, 128, 16), _rand(rng, 2, 2, 128, 16)
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        feeds = {}
        for name, value in (("q", q), ("k", k), ("v", v), ("cot", cot)):
            feeds[name] = fluid.layers.data(
                name=name, shape=list(value.shape), dtype="float32",
                append_batch_size=False)
            feeds[name].stop_gradient = name == "cot"
        out = fluid.layers.flash_attention(
            feeds["q"], feeds["k"], feeds["v"], causal=True,
            interpret=interpret)
        fluid.backward.append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(out, feeds["cot"])))
    got = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"q": q, "k": k, "v": v, "cot": cot},
        fetch_list=["q@GRAD", "k@GRAD", "v@GRAD"], scope=fluid.core.Scope())
    want = jax.grad(_plain_gqa, argnums=(0, 1, 2))(
        *(jnp.asarray(a) for a in (q, k, v, cot)))
    for name, a, b in zip("qkv", got, want):
        assert np.asarray(a).shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4, err_msg="d" + name)


# -- the served models' programs are what they were ---------------------------

def _fingerprint(program):
    rows = []
    for op in program.global_block().ops:
        attrs = {k: v for k, v in op.attrs.items()
                 if k not in ("op_callstack", "op_namescope")}
        rows.append([op.type, sorted(op.inputs), sorted(op.outputs),
                     json.dumps(attrs, sort_keys=True, default=str)])
    for p in program.global_block().all_parameters():
        rows.append([p.name, list(p.shape), str(p.dtype), bool(p.trainable)])
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()[:16]


# taken at the parent commit (4699a42), before any of these ops
# registered a gradient or took ``norm_eps``: op types, slots, attributes
# and parameters of the T = 1 step and a prefill window at toy widths
SERVED = {
    "deepseek": ("153728b44a180034", "2b459f56d0d2ab34"),
    "solar_open2": ("962f8349c4ae4288", "c8550ce1bf0ca3b6"),
    "longcat_flash": ("9ce3e939ee91a4fe", "db0f8f7448e5af02"),
}


@pytest.mark.parametrize("model", sorted(SERVED))
def test_served_programs_are_what_they_were(model):
    if model == "deepseek":
        cfg = deepseek.DeepseekConfig.tiny()
        step, window = (deepseek.build_deepseek_paged_step,
                        deepseek.build_deepseek_paged_window)
        window_kw = {}
    else:
        mod = {"solar_open2": solar_open2, "longcat_flash": longcat_flash}[
            model]
        cfg = [getattr(mod, n) for n in dir(mod)
               if n.endswith("Config")][0].tiny()
        step, window, window_kw = (mod.build_paged_step,
                                   mod.build_paged_window, {"slots": 2})
    with fluid.unique_name.guard():
        got_step = _fingerprint(step(cfg, 2, 9, 4, 4)[0])
    with fluid.unique_name.guard():
        got_window = _fingerprint(window(cfg, 9, 4, 4, 8, **window_kw)[0])
    assert (got_step, got_window) == SERVED[model]
    assert decoder_common.expert_train_stats  # the trained twin is beside
