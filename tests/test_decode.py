"""Autoregressive decode runtime (ISSUE 8): KV-cache prefill/decode
parity vs the full-forward oracle, continuous-batching scheduler
behavior, prefix reuse and chunked prefill, streaming API, resume, and
the closed-loop probe acceptance."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import profiler
from paddle_tpu.models import gpt
from paddle_tpu.observability import registry as obs_registry
from paddle_tpu.serving import decode as sdecode
from paddle_tpu.serving.batcher import ServerOverloadedError, ServingError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")

MAX_LEN = 20
SLOTS = 4


@pytest.fixture(scope="module")
def rig():
    """One shared model + oracle + engine for the module: params in one
    scope, the [1, MAX_LEN] full-forward program as the parity oracle,
    and a started 4-slot engine attached to the same scope."""
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = MAX_LEN
    with fluid.unique_name.guard():
        infer, startup, _names, logits = gpt.build_gpt_infer(cfg, MAX_LEN)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    engine = sdecode.DecodeEngine(
        cfg, scope=scope, slots=SLOTS, max_len=MAX_LEN,
        prefill_buckets=[8, MAX_LEN], param_program=infer,
    ).start()

    def oracle(prompt):
        return gpt._reference_generate(
            exe, infer, logits, cfg, prompt, MAX_LEN, scope=scope
        )

    yield {"cfg": cfg, "infer": infer, "exe": exe, "scope": scope,
           "engine": engine, "oracle": oracle, "logits": logits}
    engine.stop()


def test_greedy_generate_matches_reference(rig):
    """The rebased greedy_generate (KV-cache session) must be token-exact
    vs the kept full-forward oracle across prompt lengths, including a
    1-token prompt and a prompt one shy of max_len."""
    rs = np.random.RandomState(0)
    for n in (1, 3, 9, MAX_LEN - 1):
        p = list(rs.randint(0, rig["cfg"].vocab_size, n))
        got = gpt.greedy_generate(
            rig["exe"], rig["infer"], rig["logits"], rig["cfg"], p,
            MAX_LEN, scope=rig["scope"],
        )
        assert got == rig["oracle"](p), "prompt len %d" % n
        assert got[:n] == p


def _seeded_model(max_len):
    """-> (cfg, exe, scope, infer, logits): a tiny GPT with initialized
    params and its [1, max_len] full-forward program."""
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = max_len
    with fluid.unique_name.guard():
        infer, startup, _names, logits = gpt.build_gpt_infer(cfg, max_len)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    return cfg, exe, scope, infer, logits


@pytest.mark.parametrize("block", [4, 16])
@pytest.mark.parametrize("edge", ["block-1", "block", "block+1",
                                  "2*block+3"])
def test_greedy_generate_exact_at_block_edges(block, edge):
    """greedy_generate drives a 1-slot session through one window over an
    identity table and then the fused step: a prompt that ends just short
    of, on and just past a block boundary, and one that spans two blocks
    and a bit, each token-exact vs the oracle, at the two block sizes the
    repo runs (the flag is what a caller of greedy_generate can set)."""
    n = {"block-1": block - 1, "block": block, "block+1": block + 1,
         "2*block+3": 2 * block + 3}[edge]
    max_len = 2 * block + 8
    cfg, exe, scope, infer, logits = _seeded_model(max_len)
    p = list(np.random.RandomState(40 + n).randint(0, cfg.vocab_size, n))
    old = fluid.get_flags(["FLAGS_decode_block_size"])
    fluid.set_flags({"FLAGS_decode_block_size": block})
    try:
        got = gpt.greedy_generate(exe, infer, logits, cfg, p, max_len,
                                  scope=scope)
        (sess,) = scope._decode_gen_sessions["sessions"].values()
        assert sess.block_size == block
    finally:
        fluid.set_flags(old)
    assert got == gpt._reference_generate(exe, infer, logits, cfg, p,
                                          max_len, scope=scope)


def test_greedy_generate_sessions_are_keyed_by_block_size():
    """The cached 1-slot session's pools and tables have the geometry of
    the block it was built at: a caller that changes the flag between two
    calls on one scope gets a second session, not the first one's
    programs over the wrong table, and both stay exact."""
    max_len = 24
    cfg, exe, scope, infer, logits = _seeded_model(max_len)
    p = [5, 3, 8, 1, 9, 2, 6]
    want = gpt._reference_generate(exe, infer, logits, cfg, p, max_len,
                                   scope=scope)
    old = fluid.get_flags(["FLAGS_decode_block_size"])
    try:
        for block in (4, 8, 4):
            fluid.set_flags({"FLAGS_decode_block_size": block})
            assert gpt.greedy_generate(exe, infer, logits, cfg, p, max_len,
                                       scope=scope) == want
    finally:
        fluid.set_flags(old)
    sessions = scope._decode_gen_sessions["sessions"].values()
    assert sorted(s.block_size for s in sessions) == [4, 8]


@pytest.mark.parametrize("source", ["argument", "flag"])
@pytest.mark.parametrize("family", ["gpt", "deepseek"])
def test_block_size_below_one_raises(family, source):
    """There is one cache layout: ``block_size`` is the tokens a block
    holds, and 0 no longer selects another engine for any model, whether
    it comes by argument or by ``FLAGS_decode_block_size``."""
    if family == "gpt":
        cfg, model = gpt.GPTConfig.tiny(), None
    else:
        from paddle_tpu.models import deepseek as model

        cfg = model.DeepseekConfig.tiny()
    kw = dict(block_size=0) if source == "argument" else {}
    old = fluid.get_flags(["FLAGS_decode_block_size"])
    if source == "flag":
        fluid.set_flags({"FLAGS_decode_block_size": 0})
    try:
        with pytest.raises(ValueError, match="block_size"):
            sdecode.DecodeSession(cfg, slots=1, max_len=8, model=model,
                                  **kw)
        with pytest.raises(ValueError, match="block_size"):
            sdecode.DecodeEngine(cfg, model=model, **kw)
    finally:
        fluid.set_flags(old)


@pytest.mark.parametrize("call", ["empty window", "window past the table",
                                  "width not built"])
def test_session_refuses_what_it_has_no_program_for(rig, call):
    """The session's two device calls check what a fed table cannot
    express before anything runs: a window of no tokens, a window whose
    bucket would land past the slot's last table entry (the scatter
    would clamp and overwrite), a step width no program was built for."""
    sess = rig["engine"].session
    table = [0] * sess.max_blocks
    span = sess.max_blocks * sess.block_size
    with pytest.raises(ValueError):
        if call == "empty window":
            sess.paged_window(table, [], 0)
        elif call == "window past the table":
            sess.paged_window(table, [1, 2], span - 1)
        else:
            sess.paged_step([[0, 0]] * SLOTS, [0] * SLOTS, [()] * SLOTS,
                            [False] * SLOTS, width=2)


def test_default_engine_runs_block_tables_at_16(rig):
    """``DecodeEngine(cfg)`` with no ``block_size`` and the flag unset is
    the engine the serve cells run, at the block of gpt2s-serve-chat."""
    assert fluid.get_flags(["FLAGS_decode_block_size"]) == {
        "FLAGS_decode_block_size": 16}
    engine = rig["engine"]
    assert engine.block_size == engine.session.block_size == 16
    assert engine.stats()["paged"]["block_size"] == 16


def test_engine_parity_across_churned_slots(rig):
    """More requests than slots, all in flight: every stream's full
    completion is token-exact vs the oracle — admission and slot reuse
    after retirement never leak another stream's cache."""
    rs = np.random.RandomState(1)
    prompts = [list(rs.randint(0, rig["cfg"].vocab_size, n))
               for n in (2, 5, 9, 3, 7, 4, 1, 6)]  # 8 requests, 4 slots
    streams = [rig["engine"].generate(p) for p in prompts]
    for p, s in zip(prompts, streams):
        assert s.result(timeout=120) == rig["oracle"](p)
        assert s.finish_reason == "length"


def test_engine_eos_midstream(rig):
    """An eos_id the greedy stream emits mid-way stops the request right
    after that token (included), token-exact up to the stop."""
    rs = np.random.RandomState(2)
    p = list(rs.randint(0, rig["cfg"].vocab_size, 4))
    gen = rig["oracle"](p)[len(p):]
    eos = gen[2]
    s = rig["engine"].generate(p, eos_id=eos)
    assert s.tokens(timeout=120) == gen[: gen.index(eos) + 1]
    assert s.finish_reason == "eos"


def test_engine_max_new_truncation(rig):
    rs = np.random.RandomState(3)
    p = list(rs.randint(0, rig["cfg"].vocab_size, 3))
    gen = rig["oracle"](p)[len(p):]
    s = rig["engine"].generate(p, max_new_tokens=4)
    assert s.tokens(timeout=120) == gen[:4]
    assert s.finish_reason == "length"


def test_late_arrival_joins_inflight_batch(rig):
    """Scheduler contract: a request submitted while a decode batch is in
    flight is admitted into it mid-stream — active streams keep their
    slots (no eviction) and the late stream decodes concurrently with
    them, not after them."""
    engine = rig["engine"]
    rs = np.random.RandomState(4)
    p1 = list(rs.randint(0, rig["cfg"].vocab_size, 2))
    p2 = list(rs.randint(0, rig["cfg"].vocab_size, 3))
    p3 = list(rs.randint(0, rig["cfg"].vocab_size, 5))
    s1 = engine.generate(p1)  # runs to max_len: 18 tokens
    s2 = engine.generate(p2)
    # wait until the first streams are demonstrably mid-decode
    deadline = time.monotonic() + 60
    while len(s1._tokens) < 3 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(s1._tokens) >= 3 and not s1.done
    s3 = engine.generate(p3)
    out3 = s3.result(timeout=120)
    out1 = s1.result(timeout=120)
    out2 = s2.result(timeout=120)
    # parity first: joining mid-flight never corrupts anyone's stream
    assert out1 == rig["oracle"](p1)
    assert out2 == rig["oracle"](p2)
    assert out3 == rig["oracle"](p3)
    # overlap: the late stream started before the early ones finished
    # (ticks are engine decode-step indices)
    assert s3.first_tick is not None
    assert s1.last_tick > s3.first_tick
    assert s2.last_tick > s3.first_tick


def test_zero_steady_recompiles_and_gauges(rig):
    """Churning admissions/retirements through the warmed engine causes
    ZERO steady-state compiles (the bucketed-slot design's invariant),
    and the occupancy/queue gauges are live while the engine runs."""
    c0 = profiler.get_counters()
    rs = np.random.RandomState(5)
    streams = [
        rig["engine"].generate(
            list(rs.randint(0, rig["cfg"].vocab_size, 1 + i % 7)),
            max_new_tokens=2 + i % 5,
        )
        for i in range(3 * SLOTS)
    ]
    for s in streams:
        s.tokens(timeout=120)
    c1 = profiler.get_counters()
    assert c1.get("serving_steady_recompiles", 0) == c0.get(
        "serving_steady_recompiles", 0
    )
    assert c1.get("xla_compiles", 0) == c0.get("xla_compiles", 0)
    gauges = obs_registry.gauge_values()
    assert "serving_slot_occupancy" in gauges
    assert "decode_queue_depth" in gauges
    assert c1.get("serving_slot_retirements", 0) >= c0.get(
        "serving_slot_retirements", 0
    ) + 3 * SLOTS


def test_generation_stream_iterates_live(rig):
    """The iterator API yields tokens as they are generated (streaming),
    not after completion."""
    rs = np.random.RandomState(6)
    p = list(rs.randint(0, rig["cfg"].vocab_size, 2))
    s = rig["engine"].generate(p)
    seen = []
    for tok in s:
        seen.append(tok)
        if len(seen) == 2:
            # mid-iteration the request is still in flight
            assert not s.done or len(s._tokens) > 2
    assert seen == rig["oracle"](p)[len(p):]
    assert s.finish_reason == "length"


def test_submit_validation_and_overload(rig):
    engine = rig["engine"]
    with pytest.raises(ValueError):
        engine.submit([])
    with pytest.raises(ValueError):
        engine.submit(list(range(MAX_LEN)))  # no room to generate
    with pytest.raises(ValueError):
        engine.submit([1], max_new_tokens=0)
    # bounded admission: shrink the queue bound and flood
    old = engine.queue_depth
    engine.queue_depth = 2
    try:
        streams = []
        with pytest.raises(ServerOverloadedError):
            for _ in range(64):
                streams.append(engine.submit([1], max_new_tokens=1))
    finally:
        engine.queue_depth = old
        for s in streams:
            try:
                s.tokens(timeout=120)
            except ServingError:
                pass


@pytest.mark.slow  # ~9 s; fast equivalents: greedy_generate_matches_reference (dense-engine token parity) + the kernel-level parity tests in test_flash_attention
def test_flash_decode_engine_matches_dense():
    """A flash-attention engine (interpret kernel: the table-chasing
    single-query decode kernel) reproduces the dense engine's tokens
    exactly."""
    outs = {}
    for flash in (False, True):
        cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0,
                                 use_flash_attention=flash)
        cfg.max_position_embeddings = 16
        cfg.flash_interpret = True
        with fluid.unique_name.guard():
            infer, startup, _n, _logits = gpt.build_gpt_infer(cfg, 16)
        infer.random_seed = startup.random_seed = 11
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.core.Scope()
        with fluid.executor.scope_guard(scope):
            exe.run(startup)
        engine = sdecode.DecodeEngine(
            cfg, scope=scope, slots=2, max_len=16,
            prefill_buckets=[16], param_program=infer,
        ).start()
        try:
            outs[flash] = [
                engine.generate([3, 7]).result(timeout=120),
                engine.generate([5], max_new_tokens=6).tokens(timeout=120),
            ]
        finally:
            engine.stop()
    assert outs[True] == outs[False]


def test_prefill_ladder_shapes():
    import warnings

    assert sdecode.prefill_ladder(48) == [8, 16, 32, 48]
    assert sdecode.prefill_ladder(8) == [8]
    assert sdecode.prefill_ladder(6) == [6]
    assert sdecode.prefill_ladder(64, "16,64") == [16, 64]
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert sdecode.prefill_ladder(64, [100, 16]) == [16, 64]
        assert sdecode.prefill_ladder(64, [128]) == [64]
    dropped = [x for x in w if "exceed max_len" in str(x.message)]
    assert len(dropped) == 2
    assert "full-length program" in str(dropped[1].message)
    with pytest.raises(ValueError):
        sdecode.prefill_ladder(64, [0, 16])


def test_server_generate_wiring():
    """InferenceServer.generate() fronts an attached engine; a server
    without one raises; the server's stop() stops an engine it started."""

    class _FakePredictor(object):
        def run(self, arrays):
            return [np.asarray(arrays[0])]

        def clone(self):
            return self

    from paddle_tpu.serving import InferenceServer

    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = 12
    with fluid.unique_name.guard():
        infer, startup, _n, _l = gpt.build_gpt_infer(cfg, 12)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    engine = sdecode.DecodeEngine(
        cfg, scope=scope, slots=2, max_len=12, prefill_buckets=[12],
        param_program=infer,
    )
    server = InferenceServer(
        _FakePredictor(), max_batch_size=2, num_workers=1,
        decode_engine=engine,
    ).start(warmup_inputs=[np.ones((1, 4), "float32")])
    try:
        assert engine.started
        s = server.generate([3, 5], max_new_tokens=3)
        toks = s.tokens(timeout=120)
        assert len(toks) == 3
        assert all(0 <= t < cfg.vocab_size for t in toks)
    finally:
        server.stop()
    assert not engine.started  # server-started engine stops with it

    bare = InferenceServer(_FakePredictor(), max_batch_size=2,
                           num_workers=1)
    bare.start(warmup_inputs=[np.ones((1, 4), "float32")])
    try:
        with pytest.raises(ServingError):
            bare.generate([1])
    finally:
        bare.stop()


def test_rng_run_index_skipped_for_random_free_programs():
    """The executor's per-run fold_in skip: a program with no random ops
    neither pays the PRNG derivation nor bumps the scope run index; a
    program WITH random ops keeps the exact legacy behavior."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.fc(x, size=4)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    feed = {"x": np.ones((2, 8), "float32")}
    with fluid.executor.scope_guard(scope):
        exe.run(startup, scope=scope)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[y], scope=scope)
    counters = main.__dict__.get("_rng_run_counters")
    assert counters is None or counters.get(scope, 0) == 0

    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2):
        x2 = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h2 = fluid.layers.dropout(x2, dropout_prob=0.5)
    scope2 = fluid.core.Scope()
    with fluid.executor.scope_guard(scope2):
        exe.run(startup2, scope=scope2)
        for _ in range(3):
            exe.run(main2, feed=feed, fetch_list=[h2], scope=scope2)
    assert main2.__dict__["_rng_run_counters"].get(scope2) == 3


def test_needs_rng_sees_random_ops_inside_sub_blocks():
    """Review regression: a random op living only inside a control-flow
    sub-block (conditional_block / while body) must still mark the
    compiled block needs_rng — the segment's top level only shows the
    control-flow op type, and a fixed key would freeze the body's
    randomness across steps."""
    from paddle_tpu.fluid import executor as ex_mod

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        one = fluid.layers.fill_constant(shape=[1], dtype="float32",
                                         value=1.0)
        zero = fluid.layers.fill_constant(shape=[1], dtype="float32",
                                          value=0.0)
        pred = fluid.layers.greater_than(one, zero)
        out = fluid.layers.cond(
            pred,
            lambda: fluid.layers.dropout(x, dropout_prob=0.5),
            lambda: x,
        )
    compiled = ex_mod._CompiledBlock(
        main, 0, ["x"], [out.name], fluid.CPUPlace()
    )
    assert compiled.needs_rng
    # and the real run path bumps the per-scope run index accordingly
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup, scope=scope)
        for _ in range(2):
            exe.run(main, feed={"x": np.ones((2, 8), "float32")},
                    fetch_list=[out], scope=scope)
    assert main.__dict__["_rng_run_counters"].get(scope) == 2


def test_needs_rng_flash_attention_attr_aware():
    """flash_attention consumes a key only with LIVE dropout: an is_test
    flash program (the decode step on TPU) keeps the rng skip, a flash
    TRAINING program with attention dropout does not."""
    from paddle_tpu.fluid import executor as ex_mod

    def build(is_test, rate):
        cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0,
                                 attention_dropout=rate,
                                 use_flash_attention=True,
                                 is_test=is_test)
        cfg.flash_interpret = True
        with fluid.unique_name.guard():
            if is_test:
                main, _s, _n, out = gpt.build_gpt_infer(cfg, 12)
                return main, ["ids", "pos_ids", "input_mask"], out.name
            main, _s, _f, loss = gpt.build_gpt_lm_train(cfg, 12)
            return main, ["ids", "pos_ids", "input_mask"], loss.name

    main, feeds, fetch = build(is_test=True, rate=0.5)
    assert not ex_mod._CompiledBlock(
        main, 0, feeds, [fetch], fluid.CPUPlace()
    ).needs_rng
    main, feeds, fetch = build(is_test=False, rate=0.5)
    assert ex_mod._CompiledBlock(
        main, 0, feeds, [fetch], fluid.CPUPlace()
    ).needs_rng


def test_greedy_session_cache_dies_with_scope():
    """Review regression: the per-scope greedy session cache lives ON the
    scope (a module registry — even weak-keyed — would pin the scope via
    the session's strong back-reference). Dropping the scope must free
    the whole session graph."""
    import gc
    import weakref

    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = 10
    with fluid.unique_name.guard():
        infer, startup, _n, logits = gpt.build_gpt_infer(cfg, 10)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup, scope=scope)
        out = gpt.greedy_generate(exe, infer, logits, cfg, [1, 2], 10,
                                  scope=scope)
    assert len(out) == 10
    assert getattr(scope, "_decode_gen_sessions", None)
    ref = weakref.ref(scope)
    del scope
    gc.collect()
    assert ref() is None, "scope (and its cached decode session) leaked"


def test_server_unwinds_when_engine_start_fails():
    """Review regression: a failing DecodeEngine.start() inside
    InferenceServer.start() must stop the half-started server — batcher
    down, counted strict gate disarmed — since the caller never gets a
    handle to stop."""
    from paddle_tpu.observability import xla_stats as _xla_stats
    from paddle_tpu.serving import InferenceServer

    class _FakePredictor(object):
        def run(self, arrays):
            return [np.asarray(arrays[0])]

        def clone(self):
            return self

    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = 12
    # max_len beyond the model's positions: DecodeSession raises at start
    engine = sdecode.DecodeEngine(cfg, scope=fluid.core.Scope(), slots=1,
                                  max_len=64)
    server = InferenceServer(_FakePredictor(), max_batch_size=2,
                             num_workers=1, decode_engine=engine)
    armed_before = _xla_stats._steady_count
    with pytest.raises(ValueError, match="max_position_embeddings"):
        server.start(warmup_inputs=[np.ones((1, 4), "float32")])
    assert _xla_stats._steady_count == armed_before, "gate left armed"
    assert not server._started
    assert not engine.started


def test_greedy_generate_concurrent_callers_stay_exact(rig):
    """Review regression: greedy_generate funnels every caller thread
    into ONE cached session per (scope, geometry); calls must serialize
    on the session lock — interleaved window/step calls would read
    each other's blocks and return silently wrong tokens."""
    rs = np.random.RandomState(9)
    prompts = [list(rs.randint(0, rig["cfg"].vocab_size, n))
               for n in (2, 4, 6, 3)]
    want = {tuple(p): rig["oracle"](p) for p in prompts}
    results, errors = {}, []

    def worker(p):
        try:
            results[tuple(p)] = gpt.greedy_generate(
                rig["exe"], rig["infer"], rig["logits"], rig["cfg"], p,
                MAX_LEN, scope=rig["scope"],
            )
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(p,))
               for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    for p in prompts:
        assert results[tuple(p)] == want[tuple(p)], p


def test_engine_step_failure_retires_slots_and_recovers(rig):
    """Review regression: a failing decode step fails the streams it was
    serving, COUNTS their slots as retirements (admissions ==
    retirements + occupancy must survive recovered failures), and leaves
    the engine serving subsequent requests."""
    engine = rig["engine"]
    session = engine.session
    real_step = session.paged_step_ids
    boom = {"armed": True}

    def failing_step(*a, **kw):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected step failure")
        return real_step(*a, **kw)

    c0 = profiler.get_counters()
    session.paged_step_ids = failing_step
    try:
        s = engine.generate([1, 2], max_new_tokens=4)
        with pytest.raises(RuntimeError, match="injected step failure"):
            s.tokens(timeout=120)
    finally:
        session.paged_step_ids = real_step
    c1 = profiler.get_counters()
    assert c1.get("serving_slot_retirements", 0) >= c0.get(
        "serving_slot_retirements", 0
    ) + 1
    # engine recovered: the freed slot serves the next request
    rs = np.random.RandomState(8)
    p = list(rs.randint(0, rig["cfg"].vocab_size, 3))
    assert engine.generate(p).result(timeout=120) == rig["oracle"](p)
    assert len(engine._free) + len(engine._active) == SLOTS


def test_submit_after_stop_raises_not_hangs():
    """Review regression: submit racing stop must never strand a stream —
    after stop() every path raises ServingError instead of queueing."""
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = 12
    with fluid.unique_name.guard():
        infer, startup, _n, _l = gpt.build_gpt_infer(cfg, 12)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup, scope=scope)
    engine = sdecode.DecodeEngine(
        cfg, scope=scope, slots=1, max_len=12, prefill_buckets=[12],
        param_program=infer,
    ).start()
    engine.stop()
    with pytest.raises(ServingError):
        engine.submit([1, 2])


@pytest.mark.slow  # ~8 s; fast equivalents: needs_rng_flash_attention_attr_aware + rng_run_index_skipped_for_random_free_programs pin the same rng-skip analysis from both sides
def test_flash_attention_dropout_mask_varies_per_step():
    """Regression for the rng-skip analysis: flash_attention consumes a
    PRNG key for in-kernel dropout, so a training program whose ONLY
    random op is the flash kernel must still draw a fresh key per step —
    a frozen mask would silently bias training."""
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.5,
                             use_flash_attention=True)
    cfg.flash_interpret = True
    with fluid.unique_name.guard():
        main, startup, _feeds, loss = gpt.build_gpt_lm_train(
            cfg, 12, learning_rate=0.0)
    types = [op.type for b in main.blocks for op in b.ops]
    assert "dropout" not in types and "flash_attention" in types
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    rs = np.random.RandomState(0)
    feed = {
        "ids": rs.randint(0, cfg.vocab_size, (2, 12, 1)).astype("int64"),
        "pos_ids": np.tile(np.arange(12)[None, :, None],
                           (2, 1, 1)).astype("int64"),
        "input_mask": np.ones((2, 12, 1), "float32"),
    }
    with fluid.executor.scope_guard(scope):
        exe.run(startup, scope=scope)
        losses = []
        for _ in range(4):
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)
            losses.append(float(np.asarray(lv).ravel()[0]))
    # lr=0 + identical feed: only the dropout mask can move the loss
    assert len(set(losses)) > 1, losses


def test_decode_probe_fast_acceptance():
    """ISSUE 8 + ISSUE 12 closed loop: token-exact parity vs the
    full-forward oracle (including prefix-cache hit/miss and chunked
    admission paths), >= 10x tokens/sec over the per-token-recompute
    baseline at 8 streams,
    bounded inter-token p99 while a max-length prompt admits chunked,
    LRU evictions under store overflow, and 0 steady-state recompiles
    under the armed strict gate across the whole churn. Runs via the
    shared conftest subprocess helper; the retry prefixes are the
    LOAD-SENSITIVE bars only (throughput, inter-token p99 —
    the 2-core driver box throttles under external load) — parity /
    recompile / metrics / eviction failures fail immediately."""
    from conftest import run_probe_subprocess

    p, report = run_probe_subprocess(
        "decode_probe.py",
        retry_prefix=("speedup", "intertoken"),
    )
    assert p.returncode == 0, "probe failed:\n%s\n%s" % (
        p.stdout[-3000:], p.stderr[-2000:]
    )
    assert "PROBE PASS" in p.stdout
    assert report["schema_version"] == 3
    assert all(report["parity"].values()), report["parity"]
    assert report["strict"]["steady_recompiles"] == 0
    assert report["strict"]["churn_errors"] == 0
    assert report["throughput"]["speedup"] >= 10.0
    assert report["throughput"]["streams"] == 8
    # ISSUE 12 tentpole bars
    pre = report["prefix"]
    assert pre["miss_parity"] and pre["hit_parity"], pre
    assert pre["hits"] >= 3 and pre["cached_tokens"] >= 3 * 64, pre
    ch = report["chunked"]
    assert ch["long_parity"], ch
    assert ch["intertoken_p99_ms"] < ch["bound_ms"], ch
    ev = report["evictions"]
    assert ev["evictions"] >= 1 and ev["evicted_readmit_parity"], ev
    # ISSUE 16 tentpole bars: speculative decoding
    assert all(report["paged_parity"].values()), report["paged_parity"]
    sp = report["spec"]
    assert sp["spec_parity"], sp
    assert sp["acceptance"] > 0.5, sp
    assert sp["spec_gain"] >= 1.3, sp
    assert sp["steady_recompiles"] == 0, sp


# ---------------------------------------------------------------------------
# host-side sampling (temperature / top-k / top-p over fetched logits)
# ---------------------------------------------------------------------------


def test_sample_token_greedy_and_filters():
    """temperature<=0 is exact argmax; top_k=1 collapses to argmax; a
    vanishing top_p nucleus keeps only the most probable token; a
    seeded RNG replays the same draw."""
    rs = np.random.RandomState(5)
    logits = rs.randn(211).astype("float32")
    greedy = int(logits.argmax())
    assert sdecode.sample_token(logits) == greedy
    assert sdecode.sample_token(logits, temperature=0.0, top_k=40,
                                top_p=0.9) == greedy
    assert sdecode.sample_token(
        logits, temperature=5.0, top_k=1,
        rng=np.random.RandomState(0)) == greedy
    assert sdecode.sample_token(
        logits, temperature=5.0, top_p=1e-9,
        rng=np.random.RandomState(0)) == greedy
    a = [sdecode.sample_token(logits, temperature=2.0, top_k=50,
                              top_p=0.95, rng=np.random.RandomState(9))
         for _ in range(4)]
    b = [sdecode.sample_token(logits, temperature=2.0, top_k=50,
                              top_p=0.95, rng=np.random.RandomState(9))
         for _ in range(4)]
    assert a == b
    # top-k really cuts: with k=2 only the two top ids can ever appear
    top2 = set(np.argsort(logits)[-2:].tolist())
    rng = np.random.RandomState(3)
    for _ in range(50):
        assert sdecode.sample_token(logits, temperature=10.0, top_k=2,
                                    rng=rng) in top2


def test_engine_sampling_seeded_and_greedy_untouched(rig):
    """Engine-level knobs: a seeded sampling request replays exactly;
    the default (greedy) request stays token-exact vs the oracle — the
    knobs' existence cannot perturb the parity contract."""
    engine, oracle = rig["engine"], rig["oracle"]
    prompt = [2, 9, 4]
    expect = oracle(prompt)[len(prompt):][:6]
    assert engine.generate(prompt, max_new_tokens=6)\
        .tokens(timeout=60) == expect
    s1 = engine.generate(prompt, max_new_tokens=6, temperature=1.5,
                         top_k=64, seed=77).tokens(timeout=60)
    s2 = engine.generate(prompt, max_new_tokens=6, temperature=1.5,
                         top_k=64, seed=77).tokens(timeout=60)
    assert s1 == s2  # same seed -> same completion, even mid-batch
    # and the sampled stream reports a finish reason like any other
    st = engine.generate(prompt, max_new_tokens=3, temperature=1.5,
                         seed=1)
    st.tokens(timeout=60)
    assert st.finish_reason == "length"


def test_cancel_frees_slot_midflight(rig):
    """An abandoned stream (transport timeout / client disconnect) must
    not decode to max_new_tokens: cancel() retires the slot at the next
    tick and the pool is free for new work."""
    engine = rig["engine"]
    base = engine.stats()
    stream = engine.generate([1, 2], max_new_tokens=MAX_LEN - 3)
    for _tok in stream:  # take one token, then walk away
        break
    stream.cancel()
    deadline = time.monotonic() + 10
    while not stream.done and time.monotonic() < deadline:
        time.sleep(0.01)
    assert stream.finish_reason == "cancelled"
    assert len(stream.tokens(timeout=5)) < MAX_LEN - 3  # stopped early
    deadline = time.monotonic() + 10
    while engine.stats()["active"] > base["active"] and \
            time.monotonic() < deadline:
        time.sleep(0.01)
    st = engine.stats()
    assert st["active"] == base["active"]  # slot back in the pool
    assert st["retirements"] == st["admissions"] - st["active"]
    # the pool still serves fresh (greedy, token-exact) work afterwards
    p = [3, 1]
    assert engine.generate(p, max_new_tokens=4).tokens(timeout=60) == \
        rig["oracle"](p)[len(p):len(p) + 4]


def test_cancel_while_queued_never_takes_a_slot(rig):
    """A request cancelled before admission finishes without ever
    occupying a slot (no retirement tally — it was never admitted),
    and releases its bounded-admission-queue entry WHILE the slots are
    still busy — a cancelled waiter must not shed live traffic."""
    engine = rig["engine"]
    # fill every slot with long-running work
    hogs = [engine.generate([1], max_new_tokens=MAX_LEN - 2)
            for _ in range(SLOTS)]
    queued = engine.generate([2], max_new_tokens=4)
    queued.cancel()
    # the reap sweeps _pending at the next tick, long before any hog
    # retires: done flips and the queue drains while slots stay full
    deadline = time.monotonic() + 30
    while not queued.done and time.monotonic() < deadline:
        time.sleep(0.005)
    assert queued.done and queued.finish_reason == "cancelled"
    assert not all(h.done for h in hogs)  # slots were still busy
    # the cancelled entry left the queue; late hogs admit within a
    # tick or two, so the queue drains to 0 while hogs still run
    deadline = time.monotonic() + 30
    while engine.stats()["queued"] > 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert engine.stats()["queued"] == 0
    assert queued.tokens(timeout=5) == []
    for h in hogs:
        h.tokens(timeout=120)


def test_poisoned_sampling_request_fails_alone(rig):
    """A denormal temperature overflows the softmax to NaN; that
    request must fail with its own error while co-batched greedy
    streams finish token-exact — a client knob can never take down the
    batch."""
    engine, oracle = rig["engine"], rig["oracle"]
    good_p = [2, 9, 4]
    good = engine.generate(good_p, max_new_tokens=8)
    poisoned = engine.generate([1, 5], max_new_tokens=8,
                               temperature=1e-308, seed=3)
    with pytest.raises(ValueError, match="non-finite"):
        poisoned.tokens(timeout=60)
    assert good.tokens(timeout=60) == \
        oracle(good_p)[len(good_p):len(good_p) + 8]
    # the poisoned slot was retired, not leaked
    deadline = time.monotonic() + 10
    while engine.stats()["active"] > 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    st = engine.stats()
    assert st["retirements"] == st["admissions"] - st["active"]


# ---------------------------------------------------------------------------
# ISSUE 12: prefix KV-cache reuse + chunked prefill
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def prig():
    """Prefix/chunk rig: one model + oracle + engine with prefix caching
    (block 4, 6-block store) and chunked prefill (chunk 8) armed."""
    from paddle_tpu.models.gpt import paged_block_bytes

    max_len = 32
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = max_len
    with fluid.unique_name.guard():
        infer, startup, _names, logits = gpt.build_gpt_infer(cfg, max_len)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    engine = sdecode.DecodeEngine(
        cfg, scope=scope, slots=2, max_len=max_len,
        prefill_buckets=[8], param_program=infer,
        block_size=4,
        prefix_cache_mb=6 * paged_block_bytes(cfg, 4) / 2.0 ** 20,
        prefill_chunk=8,
    ).start()

    def oracle(prompt):
        return gpt._reference_generate(
            exe, infer, logits, cfg, prompt, max_len, scope=scope
        )

    yield {"cfg": cfg, "engine": engine, "oracle": oracle,
           "max_len": max_len}
    engine.stop()


def test_prefix_hit_parity_vs_oracle(prig):
    """Parity-on-hit: the same long prompt admitted twice — the second
    admission shares its cached prefix instead of recomputing, and both
    completions are token-exact vs the full-forward oracle."""
    engine, oracle = prig["engine"], prig["oracle"]
    rs = np.random.RandomState(21)
    p = list(rs.randint(0, prig["cfg"].vocab_size, 14))
    want = oracle(p)[len(p):][:6]
    s1 = engine.generate(p, max_new_tokens=6)
    assert s1.tokens(timeout=120) == want
    assert s1.cached_prefix_tokens == 0
    s2 = engine.generate(p, max_new_tokens=6)
    assert s2.tokens(timeout=120) == want
    # 14 tokens = 3 full blocks of 4 cached (the 13-token cap allows 3)
    assert s2.cached_prefix_tokens == 12
    st = engine.stats()
    assert st["prefix_hits"] >= 1 and st["prefix_cached_tokens"] >= 12


def test_chunked_prefill_boundaries(prig):
    """Chunk-plan edge cases, each token-exact: prompt shorter than the
    chunk, prompt an exact chunk multiple, a prompt whose windows
    resume across the bucket boundary, and EOS on the first token of a
    chunked admission."""
    engine, oracle = prig["engine"], prig["oracle"]
    rs = np.random.RandomState(22)
    vocab = prig["cfg"].vocab_size
    for n in (5, 16, 27):  # < chunk, exact 2x chunk, crosses buckets
        p = list(rs.randint(0, vocab, n))
        got = engine.generate(p, max_new_tokens=4).tokens(timeout=120)
        assert got == oracle(p)[len(p):][:4], "prompt len %d" % n
    # EOS during chunked admit: the eos lands on the very first emitted
    # token of a multi-window prompt — retire immediately, token-exact
    p = list(rs.randint(0, vocab, 20))
    first = oracle(p)[len(p)]
    s = engine.generate(p, eos_id=first)
    assert s.tokens(timeout=120) == [first]
    assert s.finish_reason == "eos"


def test_step_write_never_touches_prefilling_rows(prig):
    """Review regression (reproduced live): the fused decode step
    scatter-writes EVERY slot — inactive included — so the write of an
    idle slot, or of one mid-chunked-prefill whose blocks hold the live
    head of its prompt, must land where nothing reads: the sink block.
    Session-level: an idle or prefilling slot's step write lands in sink
    block 0 and no live block changes, whatever position it is fed;
    engine-level: a chunked admission concurrent with a decoding stream
    stays token-exact."""
    engine, oracle = prig["engine"], prig["oracle"]
    sess = engine.session
    rs = np.random.RandomState(26)
    vocab = prig["cfg"].vocab_size
    # blocks the index keeps alive after the stream retires: live data
    engine.generate(list(rs.randint(0, vocab, 9)),
                    max_new_tokens=2).tokens(timeout=120)
    names = [n for layer in sess.pool_names() for n in layer]
    before = [np.asarray(sess.scope.get(n)).copy() for n in names]
    assert any(b[1:].any() for b in before)
    sess.paged_step(np.zeros((2, 1), "int64"), [0, 8], [(), ()],
                    [False, False])
    for n, b in zip(names, before):
        after = np.asarray(sess.scope.get(n))
        np.testing.assert_array_equal(after[1:], b[1:])
    # engine contract: chunked admit + live decode stream, both exact
    pa = list(rs.randint(0, vocab, 3))
    pb = list(rs.randint(0, vocab, 20))  # 3 chunked windows
    sa = engine.generate(pa, max_new_tokens=20)
    deadline = time.monotonic() + 30
    while len(sa._tokens) < 2 and time.monotonic() < deadline:
        time.sleep(0.002)
    sb = engine.generate(pb, max_new_tokens=5)
    assert sb.tokens(timeout=120) == oracle(pb)[len(pb):][:5]
    assert sa.tokens(timeout=120) == oracle(pa)[len(pa):][:20]


def test_engine_eviction_churn_stays_exact(prig):
    """Distinct prefixes overflowing the 6-block store force LRU
    evictions mid-churn; every stream (including a re-admission of an
    evicted prefix) stays token-exact."""
    from paddle_tpu.fluid import profiler

    engine, oracle = prig["engine"], prig["oracle"]
    rs = np.random.RandomState(23)
    vocab = prig["cfg"].vocab_size
    ev0 = profiler.get_counters().get("decode_prefix_evictions", 0)
    first = list(rs.randint(0, vocab, 9))
    prompts = [first] + [list(rs.randint(0, vocab, 9)) for _ in range(5)]
    for p in prompts:  # 2 blocks each x 6 prompts = 12 > 6-block store
        got = engine.generate(p, max_new_tokens=3).tokens(timeout=120)
        assert got == oracle(p)[len(p):][:3]
    assert profiler.get_counters().get(
        "decode_prefix_evictions", 0) > ev0
    # the first prefix is long evicted: re-admitting is a miss that
    # must still be exact
    got = engine.generate(first, max_new_tokens=3).tokens(timeout=120)
    assert got == oracle(first)[len(first):][:3]


def test_engine_collision_fallthrough_runs_full_prefill(prig,
                                                        monkeypatch):
    """Engine-level hash-collision fallthrough: with every chain key
    colliding, a second DIFFERENT prompt must detect the token mismatch,
    run the full-prefill path (cached_prefix_tokens == 0), and stay
    token-exact."""
    engine, oracle = prig["engine"], prig["oracle"]
    monkeypatch.setattr(sdecode, "_block_hash",
                        lambda prev, toks: "collide")
    rs = np.random.RandomState(24)
    vocab = prig["cfg"].vocab_size
    pa = list(rs.randint(0, vocab, 9))
    pb = list(rs.randint(0, vocab, 9))
    assert pa[:4] != pb[:4]
    sa = engine.generate(pa, max_new_tokens=3)
    assert sa.tokens(timeout=120) == oracle(pa)[len(pa):][:3]
    misses0 = engine.stats()["prefix_misses"]
    sb = engine.generate(pb, max_new_tokens=3)
    assert sb.tokens(timeout=120) == oracle(pb)[len(pb):][:3]
    assert sb.cached_prefix_tokens == 0
    assert engine.stats()["prefix_misses"] == misses0 + 1


def test_ttft_and_intertoken_histograms_populate(prig):
    """The TTFT / inter-token histograms land on the profiler (and via
    it the exporter registry) once streams run."""
    from paddle_tpu.fluid import profiler

    engine = prig["engine"]
    s = engine.generate([1, 2, 3], max_new_tokens=4)
    s.tokens(timeout=120)
    assert s.ttft_ms is not None and s.ttft_ms >= 0
    hists = profiler.get_histograms()
    assert len(hists.get("decode_ttft_ms", [])) >= 1
    assert len(hists.get("decode_intertoken_ms", [])) >= 1


# ---------------------------------------------------------------------------
# durable generations (ISSUE 13): RNG fast-forward + token-exact resume
# ---------------------------------------------------------------------------
def test_fast_forward_rng_equals_discarded_draws():
    """``fast_forward_rng(k)`` must leave a freshly seeded RandomState
    in EXACTLY the state ``k`` ``sample_token`` picks leave it — the
    one-uniform-per-pick consumption contract — for every sampling-knob
    combination a request can arm."""
    rows = np.random.RandomState(0).randn(12, 40)
    for knobs in ({"temperature": 0.9},
                  {"temperature": 1.2, "top_k": 7},
                  {"temperature": 0.7, "top_p": 0.85},
                  {"temperature": 1.1, "top_k": 11, "top_p": 0.9}):
        r_full = np.random.RandomState(5)
        seq = [sdecode.sample_token(z, rng=r_full, **knobs) for z in rows]
        for k in range(len(rows) + 1):
            r_ff = sdecode.fast_forward_rng(np.random.RandomState(5), k)
            tail = [sdecode.sample_token(z, rng=r_ff, **knobs)
                    for z in rows[k:]]
            assert tail == seq[k:], (knobs, k)


def test_greedy_pick_consumes_no_rng_state():
    """Greedy picks consume ZERO draws — that's why a greedy resume
    needs no fast-forward at all: the rng is bit-identical after any
    number of greedy sample_token calls."""
    rows = np.random.RandomState(1).randn(5, 16)
    rng = np.random.RandomState(3)
    for z in rows:
        sdecode.sample_token(z, temperature=0.0, top_k=5, top_p=0.9,
                             rng=rng)
    assert rng.random_sample() == np.random.RandomState(3).random_sample()


def test_fast_forward_rng_rejects_negative():
    with pytest.raises(ValueError):
        sdecode.fast_forward_rng(np.random.RandomState(0), -1)


def test_engine_resume_token_exact_every_split_greedy(rig):
    """The resume form vs the full-forward ORACLE at every split point:
    resuming after k emitted tokens produces exactly the suffix the
    uninterrupted run emits — greedy path."""
    engine, oracle = rig["engine"], rig["oracle"]
    p = [3, 1, 4, 1, 5]
    want = oracle(p)[len(p):][:8]
    resumes0 = engine.stats()["resume_admissions"]
    for k in range(1, len(want)):
        st = engine.generate(p, max_new_tokens=8,
                             resume_tokens=want[:k])
        cont = st.tokens(timeout=120)
        assert want[:k] + cont == want, "split at %d" % k
        assert st.emitted_count == len(want)
        assert st.result(timeout=1) == p + want
    stats = engine.stats()
    assert stats["resume_admissions"] >= resumes0 + len(want) - 1
    assert stats["resume_tokens"] >= sum(range(1, len(want)))


def test_engine_resume_token_exact_seeded_sampling(rig):
    """Sampled path: a seeded temperature/top-k/top-p generation
    resumed at every split point replays the uninterrupted run's picks
    exactly (RNG fast-forwarded past the emitted suffix)."""
    engine = rig["engine"]
    p = [7, 2, 9]
    kn = dict(temperature=1.4, top_k=12, top_p=0.9, seed=77)
    full = engine.generate(p, max_new_tokens=9, **kn).tokens(timeout=120)
    assert len(full) == 9
    for k in range(1, len(full)):
        cont = engine.generate(p, max_new_tokens=9,
                               resume_tokens=full[:k],
                               **kn).tokens(timeout=120)
        assert full[:k] + cont == full, "split at %d" % k


def test_engine_resume_validation(rig):
    """The resume form's refusal cases: sampled-without-seed (the
    seed-required rule), already-finished generations, spent budgets,
    and a resumed length that overflows the cache row."""
    engine = rig["engine"]
    with pytest.raises(ValueError, match="seed"):
        engine.submit([1, 2], temperature=1.0, resume_tokens=[3])
    with pytest.raises(ValueError, match="eos"):
        engine.submit([1, 2], eos_id=5, resume_tokens=[3, 5])
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit([1, 2], max_new_tokens=2, resume_tokens=[3, 4])
    # a resume at the max_len WALL is a COMPLETE generation, not a 400:
    # the resuming router cannot know max_len (server-side config), so
    # the engine answers with an already-finished zero-continuation
    # stream — while a plain over-long PROMPT stays a loud error
    s = engine.submit([1, 2], resume_tokens=[0] * (MAX_LEN - 2))
    assert s.tokens(timeout=5) == []
    assert s.finish_reason == "length"
    assert s.emitted_count == MAX_LEN - 2
    with pytest.raises(ValueError, match="room"):
        engine.submit([0] * MAX_LEN)
    # a seeded sampled resume is accepted (and so is plain greedy)
    s = engine.submit([1, 2], temperature=1.0, seed=3, resume_tokens=[4],
                      max_new_tokens=3)
    s.tokens(timeout=120)


def test_engine_resume_respects_budgets(rig):
    """max_new_tokens counts the LOGICAL generation: a resume with k
    replayed tokens emits only max_new - k more, and the max_len wall
    lands at the same total as the unbroken run."""
    engine, oracle = rig["engine"], rig["oracle"]
    p = [11, 4]
    want = oracle(p)[len(p):][:6]
    st = engine.generate(p, max_new_tokens=6, resume_tokens=want[:4])
    cont = st.tokens(timeout=120)
    assert cont == want[4:]
    assert st.finish_reason == "length"


def test_resume_rides_chunked_prefix_admission(prig):
    """A resumed long generation re-prefills through the SAME
    prefix/chunked admission as any other: published blocks serve the
    head (cached_prefix_tokens > 0), the suffix windows through the
    bucket ladder, and the continuation stays token-exact vs the
    oracle."""
    engine, oracle = prig["engine"], prig["oracle"]
    rs = np.random.RandomState(31)
    p = list(rs.randint(0, prig["cfg"].vocab_size, 13))
    want = oracle(p)[len(p):][:8]
    # uninterrupted run first: publishes the prompt's blocks
    assert engine.generate(p, max_new_tokens=8).tokens(timeout=120) \
        == want
    k = 5
    st = engine.generate(p, max_new_tokens=8, resume_tokens=want[:k])
    assert st.tokens(timeout=120) == want[k:]
    # the first run published the 13-token prompt's 3 full blocks of 4:
    # the resume's 18-token re-prefill hits them instead of recomputing
    assert st.cached_prefix_tokens >= 12
    assert st.admit_windows >= 1
    assert engine.stats()["resume_admissions"] >= 1


def test_sample_token_boundary_draw_never_picks_filtered_token():
    """The u≈1 float boundary: u < 1 but u*cdf[-1] can round UP to
    exactly cdf[-1]; side='right' would then land past the flat
    zero-probability tail left by top-k/top-p filtering. The nextafter
    clamp keeps every draw on a positive-probability token."""

    class _Boundary(object):
        @staticmethod
        def random_sample():
            return 1.0 - 2.0 ** -53  # the largest double below 1.0

    logits = np.array([5.0, 4.0, 3.0, 0.1, 0.05])
    # top_k=3 zeroes tokens 3 and 4 -> their cdf entries sit flat at
    # cdf[-1]; a boundary draw must land on token 2, never 3/4
    tok = sdecode.sample_token(logits, temperature=1.0, top_k=3,
                               rng=_Boundary())
    assert tok == 2
    # and the top-p variant of the same flat-tail shape
    tok = sdecode.sample_token(logits, temperature=1.0, top_p=0.95,
                               rng=_Boundary())
    assert tok in (0, 1, 2)
