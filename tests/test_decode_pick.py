"""The T = 1 step picks its greedy tokens on the device
(``serving/decode.py::step_tail``): every family's step program ends in an
argmax over the vocabulary and leaves its logits in the scope, the engine
fetches ``[slots, w]`` ids, and only a stream that samples has its rows
read back. Toy widths on the CPU.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from benchmark.families import deepseek as latent_family
from benchmark.families import solar_open2 as state_family
from benchmark.references import deepseek as latent_ref
from benchmark.references import solar_open2 as state_ref
from conftest import engine_free_oracle
from paddle_tpu.fluid import profiler
from paddle_tpu.models import deepseek, gpt, solar_open2
from paddle_tpu.observability import trace
from paddle_tpu.serving import decode

SLOTS, MAX_LEN, BLOCK = 3, 24, 4
LATENT = dict(latent_family.TOY, first_k_dense_replace=1,
              routed_scaling_factor=2.448, rope_theta=1e4,
              rope_interleave=True, rms_norm_eps=1e-6)
STATE = dict(state_family.TOY, gqa_layers=[0, 4], n_shared_experts=1,
             routed_scaling_factor=1.0, rms_norm_eps=1e-5,
             first_k_dense_replace=0, expert_offset=0)


def _gpt_model(vocab=211):
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0,
                             vocab_size=vocab)
    with fluid.unique_name.guard():
        infer, startup, _names, logits = gpt.build_gpt_infer(cfg, MAX_LEN)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    return {"cfg": cfg, "infer": infer, "exe": exe, "scope": scope,
            "logits": logits, "module": gpt,
            "head": ("lm_head.w_0", "lm_head.b_0")}


def _seeded_model(config_cls, family, ref, config, seed):
    cfg = config_cls.from_config(config, dtype="float32")
    scope = fluid.core.Scope()
    params = ref.init_params(seed, dict(config))
    for leaf, var in family.leaf_to_var(config).items():
        scope.set(var, np.asarray(params[leaf], np.float32))
    return {"cfg": cfg, "scope": scope,
            "head": (family.leaf_to_var(config)["head"],)}


@pytest.fixture(scope="module")
def models():
    latent = _seeded_model(deepseek.DeepseekConfig, latent_family,
                           latent_ref, LATENT, 7)
    state = _seeded_model(solar_open2.SolarOpen2Config, state_family,
                          state_ref, STATE, 5)
    return {"gpt": _gpt_model(), "latent": dict(latent, module=deepseek),
            "state": dict(state, module=solar_open2)}


def _session(model, width):
    cfg = model["cfg"]
    if model["module"] is gpt:
        cfg.max_position_embeddings = MAX_LEN + width
    return decode.DecodeSession(
        cfg, place=fluid.CPUPlace(), scope=model["scope"], slots=SLOTS,
        max_len=MAX_LEN, prefill_buckets=[8], block_size=BLOCK,
        spec_tokens=width, model=model["module"])


def _stepped(sess, width, form="paged_step_ids"):
    """Two of the three slots prefilled, then one step of ``width``
    through ``form``: -> what it returned."""
    tables = [[1, 2, 3], [4, 5, 6], ()]
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(0, 97, n)] for n in (5, 3)]
    for slot, prompt in enumerate(prompts):
        sess.paged_window(tables[slot], prompt, 0, slot=slot)
    return getattr(sess, form)(
        rng.integers(0, 97, (SLOTS, width)), [5, 3, 0], tables,
        [True, True, False], width=width)


# the speculative verify is refused by name for the latent and state models
STEPS = [("gpt", 1), ("gpt", 3), ("latent", 1), ("state", 1)]


@pytest.mark.parametrize("family,width", STEPS)
def test_step_ids_are_the_argmax_of_the_logits_it_leaves(models, family,
                                                         width):
    sess = _session(models[family], width)
    ids = _stepped(sess, width)
    assert ids.shape == (SLOTS, width)
    assert np.issubdtype(ids.dtype, np.integer)
    logits = sess.step_logits(width=width)
    assert logits.dtype == np.float32
    assert logits.shape == (SLOTS, width, models[family]["cfg"].vocab_size)
    np.testing.assert_array_equal(ids, logits.argmax(-1))
    # a slot's rows alone, and the form that returns every logit (a new
    # session starts from zeroed caches: a step moves a recurrent state)
    np.testing.assert_array_equal(sess.step_logits(1, width=width),
                                  logits[1])
    np.testing.assert_array_equal(
        _stepped(_session(models[family], width), width, "paged_step"),
        logits)


@pytest.mark.parametrize("family,width", STEPS)
def test_equal_logits_go_to_the_lowest_id(models, family, width):
    """A head of zeros makes every logit of a row equal: id 0. With GPT's
    head bias two ids stand equal above the rest: the lower one."""
    model = models[family]
    scope, names = model["scope"], model["head"]
    kept = {n: np.array(scope.get(n)) for n in names}
    want = 0
    try:
        for n in names:
            scope.set(n, np.zeros_like(kept[n]))
        if len(names) > 1:
            bias = np.zeros_like(kept[names[1]])
            bias[[9, 5]] = 1.0
            scope.set(names[1], bias)
            want = 5
        sess = _session(model, width)
        ids = _stepped(sess, width)
        logits = sess.step_logits(width=width)
        assert (logits == logits.max(-1, keepdims=True)).sum(-1).min() >= 2
        np.testing.assert_array_equal(logits.argmax(-1), want)
        np.testing.assert_array_equal(ids, want)
    finally:
        for n in names:
            scope.set(n, kept[n])


# -- the engine ---------------------------------------------------------------
PROMPTS = ([2, 9, 4], [7, 1, 8, 2, 8], [3, 1, 4, 1, 5, 9, 2])
SEEDED = {"temperature": 0.9, "top_k": 24, "seed": 4242}
N = 9


def _engine(model, **kw):
    model["cfg"].max_position_embeddings = MAX_LEN + 2
    return decode.DecodeEngine(
        model["cfg"], scope=model["scope"], slots=4, max_len=MAX_LEN,
        param_program=model["infer"], block_size=BLOCK, **kw)


@pytest.mark.parametrize("spec", [0, 2], ids=["plain", "spec2"])
def test_one_sampled_stream_among_greedy_ones(models, spec):
    """The sampled stream is handed the parent's tokens for the parent's
    draws (one uniform a token, the engine-free oracle's), from its own
    rows read back; the greedy streams' step tokens are ids."""
    model = models["gpt"]
    engine = _engine(model, spec_tokens=spec).start()
    try:
        before = profiler.get_counters()
        knobs = [None, SEEDED, None]
        streams = [engine.submit(p, max_new_tokens=N, **(k or {}))
                   for p, k in zip(PROMPTS, knobs)]
        for p, k, s in zip(PROMPTS, knobs, streams):
            assert s.tokens(timeout=120) == engine_free_oracle(
                model, p, N, MAX_LEN, k)
        rng = decode.fast_forward_rng(
            np.random.RandomState(SEEDED["seed"]), N)
        assert streams[1]._rng.random_sample() == rng.random_sample()
        after = profiler.get_counters()
        rose = {k: after.get(k, 0) - before.get(k, 0)
                for k in ("decode_picks_on_device", "decode_picks_on_host",
                          "decode_tokens", "serving_steady_recompiles")}
        # a stream's first token is picked from its window's row
        assert rose["decode_picks_on_host"] == N - 1
        assert rose["decode_picks_on_device"] == 2 * (N - 1)
        assert rose["decode_tokens"] == 3 * N
        stats = engine.stats()
        assert stats["picks_on_host"] == N - 1
        assert stats["picks_on_device"] == 2 * (N - 1)
        assert rose["serving_steady_recompiles"] == 0
    finally:
        engine.stop()


def _step_fetches(spans):
    """Bytes of the ``executor_fetch`` inside each ``decode_paged_step``."""
    steps = [s for s in spans if s["name"] == "decode_paged_step"]
    return [f["args"]["bytes"] for f in spans
            if f["name"] == "executor_fetch"
            and any(s["tid"] == f["tid"] and s["start"] <= f["start"]
                    and f["end"] <= s["end"] for s in steps)]


@pytest.mark.parametrize("vocab", [211, 1031])
@pytest.mark.parametrize("spec", [0, 2], ids=["plain", "spec2"])
def test_a_greedy_tick_fetches_ids_whatever_the_vocabulary(vocab, spec):
    model = _gpt_model(vocab)
    engine = _engine(model, spec_tokens=spec).start(loop=False)
    try:
        trace.reset()
        streams = [engine.submit(p, max_new_tokens=5) for p in PROMPTS]
        for _ in range(12):
            engine._tick()
        assert all(s.done for s in streams)
        fetched = _step_fetches(trace.get_spans())
        assert len(fetched) >= 2
        # GPT's step names no stats: the ids are all a step brings back
        width = max(spec, 1)
        assert 0 < max(fetched) <= engine.session.slots * width * 8
        assert len(set(fetched)) == 1
    finally:
        engine.stop()


def test_a_sampled_request_after_greedy_ones_compiles_nothing(models):
    """Warm-up ran the step and the slice a sampled stream's rows are read
    through, so a first sampled request finds both compiled."""
    import jax

    from paddle_tpu.observability import xla_stats

    model = models["gpt"]
    engine = _engine(model).start()
    try:
        assert engine.submit(PROMPTS[0], max_new_tokens=4).tokens(
            timeout=120) == engine_free_oracle(model, PROMPTS[0], 4, MAX_LEN)
        before = profiler.get_counters().get("serving_steady_recompiles", 0)
        records = len(xla_stats.get_records())
        compiled = []

        def on_event(name, *_a, **_k):
            if name == "/jax/core/compile/backend_compile_duration":
                compiled.append(name)

        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            got = engine.submit(PROMPTS[1], max_new_tokens=6,
                                **SEEDED).tokens(timeout=120)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
        assert got == engine_free_oracle(model, PROMPTS[1], 6, MAX_LEN,
                                         SEEDED)
        assert profiler.get_counters().get(
            "serving_steady_recompiles", 0) == before
        assert len(xla_stats.get_records()) == records
        assert compiled == []
    finally:
        engine.stop()
