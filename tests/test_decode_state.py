"""A per-slot recurrent state beside the paged KV cache in the decode
engine (``serving/decode.py``, ``models/cache_kinds.py``), driven with
``models/solar_open2.py`` at toy widths on the CPU: which state row a
device call may touch, what a slot starts from, and the modes that are
refused by name.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from benchmark.families import solar_open2 as family
from benchmark.references import solar_open2 as ref
from paddle_tpu.fluid import flags
from paddle_tpu.models import cache_kinds, deepseek, gpt, solar_open2
from paddle_tpu.serving import decode

CFG = dict(family.TOY, gqa_layers=[0, 4], n_shared_experts=1,
           routed_scaling_factor=1.0, rms_norm_eps=1e-5,
           first_k_dense_replace=0, expert_offset=0)


@pytest.fixture(scope="module")
def params():
    return {k: np.asarray(v, np.float32)
            for k, v in ref.init_params(5, dict(CFG)).items()}


def _engine(params, **kw):
    cfg = solar_open2.SolarOpen2Config.from_config(CFG, dtype="float32")
    with fluid.unique_name.guard():
        infer, _s, _f, _l = solar_open2.build_infer(cfg, 8)
    scope = fluid.core.Scope()
    for leaf, var in family.leaf_to_var(CFG).items():
        scope.set(var, params[leaf])
    args = dict(slots=3, max_len=48, block_size=4, prefill_buckets=[8, 16],
                prefill_chunk=16, param_program=infer, model=solar_open2)
    args.update(kw)
    return decode.DecodeEngine(cfg, place=fluid.CPUPlace(), scope=scope,
                               **args)


def _state_rows(sess):
    """{var name: host copy} of every per-slot state of the session."""
    kinds = sess.model.cache_kinds(sess.cfg)
    return {name: np.array(sess.scope.get(name))
            for layer, names in zip(kinds, sess.cache_names())
            for kind, name in zip(layer, names)
            if isinstance(kind, cache_kinds.CacheState)}


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 211, n)]


def _run(eng, streams, ticks=60):
    for _ in range(ticks):
        eng._tick()
        if all(s.done for s in streams):
            return
    raise AssertionError("streams did not finish")


def test_session_declares_zeroes_and_sizes_pools_and_states(params):
    eng = _engine(params).start(loop=False)
    try:
        sess = eng.session
        names = sess.cache_names()
        assert [len(n) for n in names] == [2] * 8
        assert [len(n) for n in sess.pool_names()] == [2, 0, 0, 0] * 2
        states = _state_rows(sess)
        assert len(states) == 12
        for name, value in states.items():
            assert value.shape[0] == sess.slots + 1 and not value.any(), name
        assert eng.kv_bytes_per_token == 2 * 2 * 32 * 4
        assert eng.state_bytes_per_slot == 6 * (2 * 16 * 16 + 3 * 96) * 4
        with pytest.raises(TypeError, match="layer 1"):
            sess.kv_pool_names()
    finally:
        eng.stop()


def test_a_prefilling_slots_state_survives_other_slots_steps(params):
    """Slot 1 has run the first window of its prompt; a fused step in
    which only slot 0 is active changes row 1 (slot 0) and the sink, and
    leaves row 2 (slot 1) bit for bit."""
    eng = _engine(params).start(loop=False)
    try:
        sess = eng.session
        sess.paged_window([1, 2, 3, 4], _prompt(1, 13), 0, slot=1)
        sess.paged_window([5, 6], _prompt(2, 5), 0, slot=0)
        before = _state_rows(sess)
        sess.paged_step(np.array([[7], [0], [0]]), [5, 0, 0],
                        [[5, 6], (), ()], [True, False, False])
        after = _state_rows(sess)
        for name in before:
            np.testing.assert_array_equal(after[name][2], before[name][2])
            np.testing.assert_array_equal(after[name][3], before[name][3])
            assert (after[name][1] != before[name][1]).any(), name
    finally:
        eng.stop()


def test_an_idle_slots_feed_touches_only_the_sink_row(params):
    eng = _engine(params).start(loop=False)
    try:
        sess = eng.session
        for slot in range(3):
            sess.paged_window([1 + slot], _prompt(slot, 3), 0, slot=slot)
        before = _state_rows(sess)
        sess.paged_step(np.zeros((3, 1), "int64"), [0] * 3, [()] * 3,
                        [False] * 3)
        after = _state_rows(sess)
        for name in before:
            np.testing.assert_array_equal(after[name][1:], before[name][1:])
    finally:
        eng.stop()


def test_the_warm_up_writes_the_sink_row_alone(params):
    eng = _engine(params).start(loop=False)
    try:
        # start() zeroes after warming; warm again and look
        sess = eng.session
        for t in sess.buckets:
            sess.paged_window([0] * sess.max_blocks, [0] * t, 0, slot=-1)
        for name, value in _state_rows(sess).items():
            assert not value[1:].any(), name
    finally:
        eng.stop()


def _tokens(eng, prompt, n, **kw):
    stream = eng.submit(prompt, max_new_tokens=n, **kw)
    _run(eng, [stream])
    return stream.tokens(timeout=1)


def test_admission_after_retirement_starts_from_zero_state(params):
    """One slot: a second request in the slot a first one left gives the
    tokens a fresh engine gives it."""
    prompt = _prompt(11, 19)
    fresh = _engine(params, slots=1).start(loop=False)
    try:
        want = _tokens(fresh, prompt, 6)
    finally:
        fresh.stop()
    eng = _engine(params, slots=1).start(loop=False)
    try:
        _tokens(eng, _prompt(12, 23), 5)
        assert any(v[1].any() for v in _state_rows(eng.session).values())
        assert _tokens(eng, prompt, 6) == want
    finally:
        eng.stop()


def test_preemption_and_readmission_are_token_exact(params):
    """One slot: a batch stream is evicted mid-generation by an
    interactive request and re-admitted; re-prefilling prompt + emitted
    tokens rebuilds the state, so it ends with the tokens of an
    uninterrupted run."""
    long_prompt, short = _prompt(21, 17), _prompt(22, 4)
    alone = _engine(params, slots=1).start(loop=False)
    try:
        want = _tokens(alone, long_prompt, 9)
        want_short = _tokens(alone, short, 3)
    finally:
        alone.stop()
    eng = _engine(params, slots=1).start(loop=False)
    try:
        batch = eng.submit(long_prompt, max_new_tokens=9, priority="batch")
        for _ in range(5):
            eng._tick()
        assert 0 < batch.emitted_count < 9
        urgent = eng.submit(short, max_new_tokens=3)
        _run(eng, [batch, urgent])
        assert batch.preemptions == 1
        assert urgent.tokens(timeout=1) == want_short
        assert batch.tokens(timeout=1) == want
    finally:
        eng.stop()


@pytest.mark.parametrize("mode,kwargs,flag", [
    ("prefix cache", dict(prefix_cache_mb=1.0), None),
    ("host KV tier", dict(prefix_cache_mb=1.0),
     {"FLAGS_kv_tier_host_mb": 1.0}),
    ("tensor-parallel", dict(tp=2), None),
    ("speculative", dict(spec_tokens=4), None),
])
def test_engine_refuses_a_mode_by_name(params, mode, kwargs, flag):
    flags.set_flags(flag or {})
    try:
        with pytest.raises(NotImplementedError, match=mode):
            _engine(params, **kwargs).start(loop=False)
    finally:
        flags.set_flags({k: 0.0 for k in flag or {}})


@pytest.mark.parametrize("call", [
    lambda e: e.block_row_shape(),
    lambda e: e.offer_blocks([]),
    lambda e: e.request_export([1, 2, 3, 4]),
], ids=["block_row_shape", "offer_blocks", "request_export"])
def test_engine_refuses_block_export_by_name(params, call):
    eng = _engine(params).start(loop=False)
    try:
        with pytest.raises(NotImplementedError, match="block export"):
            call(eng)
    finally:
        eng.stop()


def test_every_unsupported_mode_of_every_model_is_one_the_engine_asks():
    """A key the engine never passes to ``_require`` would be a silent
    skip."""
    import inspect

    asked = set()
    src = inspect.getsource(decode)
    for model in (solar_open2, deepseek):
        for mode in model.UNSUPPORTED:
            assert '"%s"' % mode in src, mode
            asked.add(mode)
    assert asked == {"prefix_cache", "kv_host_tier", "tp", "spec_tokens",
                     "block_export"}
    assert gpt.UNSUPPORTED == {}


def test_block_row_shape_asks_the_kind_not_the_first_layer():
    """GPT's pools answer as before; a model whose layers keep pools of
    different rows, or anything but a (K, V) pair, is refused."""
    kinds = gpt.cache_kinds(gpt.GPTConfig.tiny())
    assert all(len(p) == 2 for p in cache_kinds.kv_pools(kinds))
    mixed = [(cache_kinds.CachePool("k", [1, 8], "float32"),
              cache_kinds.CachePool("v", [1, 16], "float32"))]
    with pytest.raises(TypeError, match="layer 0"):
        cache_kinds.kv_pools(mixed)
    latent = deepseek.cache_kinds(deepseek.DeepseekConfig.tiny())
    with pytest.raises(TypeError, match="layer 0"):
        cache_kinds.kv_pools(latent)
