"""The serve tick decides now and publishes under the next device call
(PR 28): what a stream's consumer sees is what it saw before, in the same
order, whichever way a stream ends; a caller that drives ``_tick()``
itself reads every decided token when it returns; nothing decided stays
unpublished over an idle wait or a ``stop()``; and the two counters say
how many tokens went out with a device call in flight.
"""

import threading
import time

import pytest
from conftest import engine_free_oracle

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import profiler
from paddle_tpu.models import gpt
from paddle_tpu.observability import registry as obs_registry
from paddle_tpu.serving import decode as sdecode
from paddle_tpu.serving.batcher import ServerOverloadedError, ServingError

MAX_LEN = 20
BLOCK = 4
KINDS = {
    # windows between steps, kanana's regime; a chunk of one block so
    # that the 5- and 7-token prompts below take two windows each
    "chunked": dict(block_size=BLOCK, prefill_chunk=BLOCK),
    "paged": dict(block_size=BLOCK),
    "spec2": dict(block_size=BLOCK, spec_tokens=2),
}
PROMPTS = ([2, 9, 4], [7, 1, 8, 2, 8], [3, 1, 4, 1, 5, 9, 2])


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = MAX_LEN + 2   # the verify's headroom
    with fluid.unique_name.guard():
        infer, startup, _names, logits = gpt.build_gpt_infer(cfg, MAX_LEN)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    return {"cfg": cfg, "infer": infer, "exe": exe, "scope": scope,
            "logits": logits}


def _engine(model, kind="paged", slots=4, **more):
    return sdecode.DecodeEngine(
        model["cfg"], scope=model["scope"], slots=slots, max_len=MAX_LEN,
        param_program=model["infer"], **dict(KINDS[kind], **more))


@pytest.fixture(scope="module")
def engines(model):
    made = {}

    def get(kind):
        if kind not in made:
            made[kind] = _engine(model, kind).start()
        return made[kind]

    yield get
    for e in made.values():
        e.stop()


def _oracle(model, prompt, n, sampling=None):
    return engine_free_oracle(model, prompt, n, MAX_LEN, sampling)


def _drain(stream, timeout=120):
    """What the stream's one consumer is handed: the tokens up to the
    sentinel, and the error the sentinel carried (None for an end)."""
    got, error = [], None
    try:
        for tok in stream.stream_tokens(timeout=timeout):
            got.append(tok)
    except TimeoutError:
        raise
    except Exception as e:  # noqa: BLE001 - the stream's own failure
        error = e
    return got, error


def _ended_in_order(stream, got):
    """Every decided token was handed over, then the end and nothing
    after it."""
    assert got == stream._tokens
    assert stream._q.empty()
    assert stream.done


def _idle(engine, timeout=60):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        with engine._cond:
            if engine._idle():
                return
        time.sleep(0.005)
    raise AssertionError("engine not idle")


# -- (e) the outputs are the parent's --------------------------------------
SEEDED = {"temperature": 0.9, "top_k": 24, "seed": 4242}


@pytest.mark.parametrize("sampling", [None, SEEDED],
                         ids=["greedy", "seeded"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_outputs_token_for_token(model, engines, kind, sampling):
    """Three streams in one batch, greedy or seeded: each is what the
    engine-free oracle says, so what it was before publication moved:
    ``pick`` still runs once a token, in slot order, on the host."""
    engine = engines(kind)
    n = 9
    streams = [engine.submit(p, max_new_tokens=n, **(sampling or {}))
               for p in PROMPTS]
    for p, s in zip(PROMPTS, streams):
        assert s.tokens(timeout=120) == _oracle(model, p, n, sampling)
        assert s.finish_reason == "length"
    if kind == "chunked":
        assert [s.admit_windows for s in streams] == [1, 2, 2]


def test_pick_runs_once_a_token_in_slot_order(model, engines, monkeypatch):
    """One pick a token: a greedy stream's first on the host from its
    window's row, its steps' on the device."""
    engine = engines("paged")
    calls = []
    real = sdecode.GenerationStream.pick

    def counting(self, logits):
        calls.append(self)
        return real(self, logits)

    monkeypatch.setattr(sdecode.GenerationStream, "pick", counting)
    before = engine.stats()
    streams = [engine.submit(p, max_new_tokens=6) for p in PROMPTS]
    for s in streams:
        s.tokens(timeout=120)
    assert len(calls) == 3
    for s in streams:
        assert calls.count(s) == 1 and len(s._tokens) == 6
    after = engine.stats()
    assert after["picks_on_device"] - before["picks_on_device"] == 15
    assert after["picks_on_host"] == before["picks_on_host"]


# -- (b) a stream's events keep their order, however it ends --------------
def _ends_by_length(model, engines):
    s = engines("paged").submit(PROMPTS[0], max_new_tokens=5)
    got, error = _drain(s)
    assert error is None and s.finish_reason == "length" and len(got) == 5
    return [(s, got)]


def _ends_by_eos(model, engines):
    want = _oracle(model, PROMPTS[1], 8)
    eos = want[3]
    s = engines("paged").submit(PROMPTS[1], max_new_tokens=8, eos_id=eos)
    got, error = _drain(s)
    assert error is None and s.finish_reason == "eos"
    assert got == want[:want.index(eos) + 1]
    return [(s, got)]


def _ends_by_cancel(model, engines):
    s = engines("paged").submit(PROMPTS[2], max_new_tokens=12)
    it = s.stream_tokens(timeout=120)
    got = [next(it) for _ in range(3)]
    s.cancel()
    got += list(it)
    assert s.finish_reason == "cancelled" and 3 <= len(got) < 12
    return [(s, got)]


def _ends_by_shed(model, engines):
    """A pool that holds one full-length stream and a block: two streams
    grow until the pool cannot cover both, and one is shed in
    ``_build_step`` with tokens already out."""
    engine = _engine(model, "paged", slots=2,
                     pool_blocks=1 + MAX_LEN // BLOCK + 1).start()
    try:
        a = engine.submit(PROMPTS[0], max_new_tokens=16)
        b = engine.submit(PROMPTS[1][:3], max_new_tokens=16)
        (ga, ea), (gb, eb) = _drain(a), _drain(b)
    finally:
        engine.stop()
    shed = [(s, g, e) for s, g, e in ((a, ga, ea), (b, gb, eb))
            if e is not None]
    assert len(shed) == 1
    s, g, e = shed[0]
    assert isinstance(e, ServerOverloadedError) and len(g) >= 1
    assert engine.stats()["oom_sheds"] == 1
    return [(a, ga), (b, gb)]


def _ends_by_a_failing_tick(model, engines):
    """The fourth fused step raises: each stream is handed the token of
    its admission and of three steps, then the error."""
    engine = engines("paged")
    sess, calls = engine.session, []
    step = sess.paged_step_ids

    def failing(*a, **kw):
        calls.append(1)
        if len(calls) == 4:
            raise RuntimeError("step failed")
        return step(*a, **kw)

    sess.paged_step_ids = failing
    try:
        # the first tick admits both, so both ride every step
        with engine._cond:
            streams = [engine.submit(p, max_new_tokens=12)
                       for p in PROMPTS[:2]]
        out = []
        for s in streams:
            got, error = _drain(s)
            assert isinstance(error, RuntimeError) and len(got) == 4
            out.append((s, got))
    finally:
        del sess.paged_step_ids
    # the engine is up for the next request
    assert engine.submit(PROMPTS[0], max_new_tokens=2).tokens(
        timeout=120) == _oracle(model, PROMPTS[0], 2)
    return out


@pytest.mark.parametrize("ending", [
    _ends_by_length, _ends_by_eos, _ends_by_cancel, _ends_by_shed,
    _ends_by_a_failing_tick], ids=lambda f: f.__name__.strip("_"))
def test_all_tokens_then_the_end(model, engines, ending):
    for stream, got in ending(model, engines):
        _ended_in_order(stream, got)


# -- (c) a caller that drives the ticks itself ----------------------------
@pytest.mark.parametrize("kind", ["chunked", "paged"])
def test_hand_driven_tick_publishes_before_it_returns(model, kind):
    engine = _engine(model, kind, slots=2).start(loop=False)
    try:
        streams = [engine.submit(p, max_new_tokens=5) for p in PROMPTS[:2]]
        handed = {s: [] for s in streams}
        ticks = 0
        while not all(s.done for s in streams):
            engine._tick()
            ticks += 1
            assert ticks < 40
            assert not engine._outbox
            for s in streams:
                held = handed[s]
                while not s._q.empty():
                    held.append(s._q.get_nowait())
                ended = bool(held) and held[-1] is sdecode._SENTINEL
                assert [t for t in held if t is not sdecode._SENTINEL] \
                    == s._tokens
                assert ended == s.done == (s.finish_reason is not None)
        for p, s in zip(PROMPTS, streams):
            assert s.tokens(timeout=1) == _oracle(model, p, 5)
    finally:
        engine.stop()


# -- (d) nothing stays unpublished ----------------------------------------
def test_going_idle_publishes_the_last_token(model, engines):
    engine = engines("paged")
    s = engine.submit(PROMPTS[0], max_new_tokens=4)
    assert len(s.tokens(timeout=120)) == 4
    _idle(engine)
    assert not engine._outbox


def test_stop_publishes_what_was_decided_then_fails(model):
    engine = _engine(model, "paged", slots=2).start()
    s = engine.submit(PROMPTS[0], max_new_tokens=16)
    it = s.stream_tokens(timeout=120)
    got, error = [next(it), next(it)], None
    engine.stop()
    try:
        for tok in it:
            got.append(tok)
    except ServingError as e:
        error = e
    assert not engine._outbox
    # 14 more steps do not fit between a token and the stop that follows
    assert error is not None and len(got) < 16
    _ended_in_order(s, got)


def test_stop_from_another_thread_while_streams_run(model):
    """``stop()`` joins the loop and then empties the outbox under the
    publish lock: a consumer blocked on the stream wakes with every
    decided token and then the error."""
    engine = _engine(model, "paged", slots=2).start()
    streams = [engine.submit(p, max_new_tokens=16) for p in PROMPTS[:2]]
    results = []
    readers = [threading.Thread(target=lambda s=s: results.append(
        (s,) + _drain(s))) for s in streams]
    for t in readers:
        t.start()
    time.sleep(0.05)
    engine.stop()
    for t in readers:
        t.join(timeout=60)
    assert len(results) == 2 and not engine._outbox
    for s, got, error in results:
        # finished before the stop, or failed by it: in order either way
        assert error is None or isinstance(error, ServingError)
        _ended_in_order(s, got)


# -- (h) the counters ------------------------------------------------------
def test_published_counters_add_up_to_decode_tokens(model):
    names = ("decode_tokens", "decode_tokens_published_overlapped",
             "decode_tokens_published_exposed")
    before = {n: profiler.get_counter(n) for n in names}
    engine = _engine(model, "paged").start()
    try:
        streams = [engine.submit(p, max_new_tokens=8) for p in PROMPTS]
        total = sum(len(s.tokens(timeout=120)) for s in streams)
        _idle(engine)
        st = engine.stats()
    finally:
        engine.stop()
    rose = {n: profiler.get_counter(n) - before[n] for n in names}
    assert rose["decode_tokens"] == total == st["tokens"] == 24
    assert (rose["decode_tokens_published_overlapped"]
            + rose["decode_tokens_published_exposed"]) == total
    assert st["published_overlapped"] + st["published_exposed"] == total
    # all but the streams' last tokens ride a device call: three streams
    # of equal length end in one tick, whose tokens have no call to ride
    assert st["published_exposed"] <= 3
    assert st["published_overlapped"] >= total - 3
    text = obs_registry.render_prometheus()
    assert "decode_tokens_published_overlapped" in text
    assert "decode_tokens_published_exposed" in text


# -- many readers, a short switch interval ----------------------------------
def test_readers_under_a_short_switch_interval(model):
    """Sixteen consumer threads on a 4-slot engine with the interpreter
    handing the lock over every 10 microseconds, and a ``stop()`` from yet
    another thread while half of them still read: every consumer is handed
    its stream's decided tokens in order, then the end; the counters count
    every token once."""
    import sys

    engine = _engine(model, "paged").start()
    results, refused, lock = [], [], threading.Lock()

    def reader(i):
        try:
            s = engine.submit(PROMPTS[i % 3], max_new_tokens=4 + i % 5)
        except ServingError as e:   # the stop came first
            refused.append(e)
            return
        got, error = _drain(s, timeout=60)
        with lock:
            results.append((s, got, error))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        first = [threading.Thread(target=reader, args=(i,))
                 for i in range(8)]
        for t in first:
            t.start()
        for t in first:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in first)
        assert all(e is None for _s, _g, e in results)
        _idle(engine)
        st = engine.stats()
        assert (st["published_overlapped"] + st["published_exposed"]
                == st["tokens"] == sum(len(g) for _s, g, _e in results))
        second = [threading.Thread(target=reader, args=(i,))
                  for i in range(8, 16)]
        for t in second:
            t.start()
        time.sleep(0.02)
        engine.stop()
        for t in second:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in second)
    finally:
        sys.setswitchinterval(old)
        engine.stop()
    assert len(results) + len(refused) == 16 and not engine._outbox
    for s, got, error in results:
        assert error is None or isinstance(error, ServingError)
        _ended_in_order(s, got)
