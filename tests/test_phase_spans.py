"""What the host does while the chip waits, as the program's own tracer
records it: the executor's phases on both entry points (``Executor.run``
and ``CompiledProgram.with_mesh``; marks on ``executor_run``'s one
record, child spans when the buffer is read through ``with_phases``), the
engine tick's children, one record per request, the Pallas kernels'
names, and nothing at all with ``FLAGS_obs_trace`` off.
"""

import ast
import http.client
import json
import os
import statistics
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.fluid import compiler, profiler
from paddle_tpu.models import gpt
from paddle_tpu.observability import trace
from paddle_tpu.serving.decode import DecodeEngine

PHASES = ("executor_prepare", "executor_run", "executor_marshal",
          "executor_dispatch", "executor_writeback", "executor_fetch")


# -- a span's thread CPU time ------------------------------------------------
@pytest.fixture
def every_span_reads_its_clock():
    """The tracer reads the CPU clock in one outermost ``cpu=True`` span
    of a name in ``CPU_EVERY``; the tests of what a reading span records
    ask for every one, as a measuring run does."""
    before = trace.set_cpu_every(1)
    yield
    assert trace.set_cpu_every(before) == 1


def _busy(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _one_span(work, **kw):
    trace.reset()
    with trace.span("cpu_probe", **kw) as sp:
        work(sp)
    (rec,) = trace.get_spans()
    return rec


@pytest.mark.parametrize("work,lo,hi", [
    (lambda sp: _busy(0.05), 0.8, 1.0),
    (lambda sp: time.sleep(0.05), 0.0, 0.1),
], ids=["busy", "asleep"])
def test_cpu_span_reads_the_threads_cpu_time(every_span_reads_its_clock,
                                             work, lo, hi):
    """``cpu=True``: a busy loop's ``cpu_ms`` is within 20 % of its wall
    time and a sleep's under 10 %; never more than the wall, because the
    CPU clock is read inside the wall clock's two reads. The best of five
    tries: another process may take the core from a busy loop."""
    shares = []
    for _ in range(5):
        rec = _one_span(work, cpu=True)
        wall = 1e3 * (rec["end"] - rec["start"])
        assert 0.0 <= rec["args"]["cpu_ms"] <= wall
        assert rec["args"]["cpu_at"] >= 0.0
        shares.append(rec["args"]["cpu_ms"] / wall)
        if lo <= shares[-1] <= hi:
            break
    assert lo <= shares[-1] <= hi, shares


def test_phases_of_a_cpu_span_get_a_cpu_ms_each(every_span_reads_its_clock):
    """A mark stays (name, start, args) and carries the thread's CPU
    clock in its args; ``with_phases`` gives each phase the CPU time up to
    the next mark (the last: up to the span's end). With the first mark
    made as the span opens, they add up to the span's."""
    def work(sp):
        sp.phase("probe_busy", n=1)
        _busy(0.02)
        own = sp.phase("probe_asleep")
        time.sleep(0.02)
        own["late"] = True
        sp.phase("probe_busy_again")
        _busy(0.01)

    rec = _one_span(work, cpu=True)
    marks = rec["args"]["phases"]
    assert [len(m) for m in marks] == [3, 3, 3]
    assert all("cpu_at" in m[2] for m in marks)
    parent, busy, asleep, again = trace.with_phases([rec])
    assert parent is rec and busy["args"]["n"] == 1
    assert asleep["args"]["late"] is True
    parts = [c["args"]["cpu_ms"] for c in (busy, asleep, again)]
    walls = [1e3 * (c["end"] - c["start"]) for c in (busy, asleep, again)]
    assert all(0.0 <= c <= w + 1.0 for c, w in zip(parts, walls))
    assert sum(parts) <= sum(walls) + 0.15
    assert parts[1] < 0.25 * walls[1]      # asleep: next to no CPU
    before_first = 1e3 * (marks[0][2]["cpu_at"] - rec["args"]["cpu_at"])
    assert sum(parts) + before_first == pytest.approx(
        rec["args"]["cpu_ms"], abs=1e-6)
    # and the exported trace shows them, without the marks
    events = {e["name"]: e for e in trace.chrome_trace()["traceEvents"]
              if e.get("ph") == "X"}
    assert "phases" not in events["cpu_probe"]["args"]
    assert events["probe_asleep"]["args"]["cpu_ms"] == pytest.approx(parts[1])


def test_span_without_cpu_makes_the_record_it_made_before():
    def work(sp):
        sp.phase("probe_phase", k=2)
        sp.note(seen=True)

    rec = _one_span(work, cat="probe", step=7)
    ((name, at, own),) = rec["args"].pop("phases")
    assert (name, own) == ("probe_phase", {"k": 2})
    assert rec["start"] <= at <= rec["end"]
    assert rec["args"] == {"step": 7, "seen": True}
    rec["args"]["phases"] = [(name, at, own)]
    assert sorted(rec) == ["args", "cat", "depth", "end", "id", "instant",
                           "name", "parent", "parent_span_id", "span_id",
                           "start", "tid", "trace_id"]
    (_p, child) = trace.with_phases([rec])
    assert child["args"] == {"k": 2}


def test_cpu_span_reads_no_clock_with_tracing_off(every_span_reads_its_clock,
                                                  monkeypatch):
    reads = []
    real = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: (reads.append(1), real())[1])
    fluid.set_flags({"FLAGS_obs_trace": False})
    try:
        trace.reset()
        with trace.span("cpu_probe", cpu=True) as sp:
            sp.phase("probe_phase")
        assert trace.get_spans() == [] and reads == []
    finally:
        fluid.set_flags({"FLAGS_obs_trace": True})
    # on: a read as it opens, one a phase, one as it closes
    with trace.span("cpu_probe", cpu=True) as sp:
        sp.phase("probe_phase")
    assert len(reads) == 3


def _on_a_new_thread(fn):
    """``fn()`` on a thread of its own: the tracer counts a thread's
    spans from its first."""
    box = []
    t = threading.Thread(target=lambda: box.append(fn()))
    t.start()
    t.join()
    return box[0]


def test_one_outermost_span_of_a_name_in_cpu_every_reads_the_clock(
        monkeypatch):
    """At the rate the program runs at (``CPU_EVERY``, no calibration, the
    same ticks on every host): of the outermost ``cpu=True`` spans of a
    name that a thread opens, the first and then every ``CPU_EVERY``-th
    read the CPU clock, with the ``cpu=True`` spans and phases inside
    them; the others and all inside them read nothing and make the record
    a span without ``cpu`` makes. Two outermost names that take turns (a
    training step's ``executor_run`` and ``executor_fetch``) are counted
    each for itself, so both read it, in the same round."""
    every = trace.CPU_EVERY
    assert every == 11
    reads = []
    real = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: (reads.append(1), real())[1])
    rounds = 2 * every + 3

    def steps():
        said = []
        for i in range(rounds):
            with trace.span("cpu_probe_outer", cpu=True, i=i) as outer:
                outer.phase("probe_phase")
                with trace.span("cpu_probe_inner", cpu=True) as inner:
                    assert inner.cpu == outer.cpu
                with trace.span("probe_plain") as plain:
                    assert plain.cpu is False
            with trace.span("cpu_probe_other", cpu=True, i=i) as other:
                assert other.cpu == outer.cpu
            said.append(outer.cpu)
        return said

    trace.reset()
    said = _on_a_new_thread(steps)
    assert said == [i % every == 0 for i in range(rounds)]
    # an outer span: open, phase, close, and the inner's two; the other: 2
    assert len(reads) == 7 * said.count(True)
    spans = trace.get_spans()
    for s in spans:
        stamped = s["name"] != "probe_plain" and said[[
            o["args"]["i"] for o in spans
            if o["name"] in ("cpu_probe_outer", "cpu_probe_other")
            and o["start"] <= s["start"] and s["end"] <= o["end"]][0]]
        assert ("cpu_ms" in s["args"]) == ("cpu_at" in s["args"]) == stamped
    outers = [s for s in spans if s["name"] == "cpu_probe_outer"]
    assert [("cpu_at" in s["args"]["phases"][0][2]) for s in outers] == said
    # the phases of a span that read nothing get no cpu_ms
    kids = [s for s in trace.with_phases(outers) if s["name"] == "probe_phase"]
    assert [("cpu_ms" in k["args"]) for k in kids] == said
    # another thread counts its own: its first reads
    assert _on_a_new_thread(steps)[:2] == [True, False]


def test_a_measuring_run_may_ask_for_every_span():
    """``set_cpu_every``: the rate a measuring run asks for (every tick's
    split, ``tools/cpu_clocks.py``), and back; it gives the rate before,
    and nothing under 1."""
    def three():
        said = []
        for _ in range(3):
            with trace.span("cpu_probe", cpu=True) as sp:
                said.append(sp.cpu)
        return said

    assert trace.set_cpu_every(1) == trace.CPU_EVERY
    try:
        assert _on_a_new_thread(three) == [True, True, True]
        assert trace.set_cpu_every(0) == 1
        assert _on_a_new_thread(three) == [True, True, True]
    finally:
        trace.set_cpu_every(trace.CPU_EVERY)
    assert _on_a_new_thread(three) == [True, False, False]


# -- executor ----------------------------------------------------------------
def _train_program():
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="int64")
            h = fluid.layers.fc(input=x, size=16, act="relu")
            loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
                fluid.layers.fc(input=h, size=4), y))
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _feed(n=8):
    r = np.random.RandomState(5)
    return {"x": r.rand(n, 8).astype("float32"),
            "y": r.randint(0, 4, (n, 1)).astype("int64")}


def _program_on(entry):
    """-> (executor, scope after startup, what to run, loss)"""
    main, startup, loss = _train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    exe.run(startup, scope=scope)
    target = main
    if entry == "with_mesh":
        target = compiler.CompiledProgram(main).with_mesh(
            loss_name=loss.name, mesh_axes={"data": 4}, fsdp=True)
    return exe, scope, target, loss


def _steps(entry):
    """Two warm steps, then one recorded. -> (its spans with a child
    span a phase, the number of ``jax.device_put`` calls it made, the
    wall time of the run call)"""
    exe, scope, target, loss = _program_on(entry)
    for _ in range(2):
        exe.run(target, feed=_feed(), fetch_list=[loss], scope=scope)
    puts = []
    real = jax.device_put

    def counting(*a, **kw):
        puts.append(1)
        return real(*a, **kw)

    feed = _feed()
    trace.reset()
    jax.device_put = counting
    try:
        t0 = time.perf_counter()
        exe.run(target, feed=feed, fetch_list=[loss], scope=scope)
        wall = time.perf_counter() - t0
    finally:
        jax.device_put = real
    return trace.with_phases(trace.get_spans()), len(puts), wall


@pytest.fixture(scope="module", params=["run", "with_mesh"])
def step(request):
    if request.param == "with_mesh" and len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    return (request.param,) + _steps(request.param)


@pytest.mark.parametrize("phase", PHASES)
def test_executor_phase_once_a_segment(step, phase):
    """One XLA segment: each phase occurs exactly once a run, on
    ``Executor.run`` and on ``CompiledProgram.with_mesh`` alike;
    prepare is two clock reads carried on ``executor_run``."""
    _entry, spans, _puts, _wall = step
    if phase == "executor_prepare":
        (run,) = [s for s in spans if s["name"] == "executor_run"]
        assert run["args"]["prepare_ms"] > 0 and run["args"]["plan_hit"]
        return
    assert [s["name"] for s in spans].count(phase) == 1


def test_a_run_makes_two_records(step):
    """What a run appends to the ring buffer: ``executor_run`` (with its
    phases as marks) and ``executor_fetch``. The tracer's gate (under
    2 % of a sub-millisecond step, ``tools/obs_probe.py``) counts
    records."""
    _entry, spans, _puts, _wall = step
    records = [s["name"] for s in spans if s["id"] is not None]
    assert records == ["executor_run", "executor_fetch"]
    (run,) = [s for s in spans if s["name"] == "executor_run"]
    assert [m[0] for m in run["args"]["phases"]] == [
        "executor_marshal", "executor_dispatch", "executor_writeback"]


def test_executor_phases_nest_and_add_up(step):
    """marshal, dispatch and writeback tile ``executor_run``; prepare
    comes before it and fetch after; together they are the run call."""
    _entry, spans, _puts, wall = step
    by = {s["name"]: s for s in spans}
    run = by["executor_run"]
    edge = run["start"]
    for child in ("executor_marshal", "executor_dispatch",
                  "executor_writeback"):
        assert by[child]["parent"] == "executor_run"
        assert by[child]["tid"] == run["tid"]
        assert by[child]["depth"] == run["depth"] + 1
        assert edge <= by[child]["start"] <= by[child]["end"]
        edge = by[child]["end"]
    assert edge == run["end"]
    assert by["executor_fetch"]["start"] >= run["end"]
    assert by["executor_fetch"]["parent"] != "executor_run"
    total = run["args"]["prepare_ms"] / 1e3 + sum(
        by[n]["end"] - by[n]["start"]
        for n in ("executor_run", "executor_fetch"))
    # a sub-millisecond toy step: what is left over is the calls and
    # returns between the phases (the chip's 100 ms steps: PERF.md)
    assert 0.6 * wall <= total <= wall


@pytest.mark.parametrize("entry", ["run", "with_mesh"])
def test_host_work_between_dispatch_and_fetch(entry):
    """The seam the decode engine publishes through (``Executor._run``'s
    ``while_device_runs``): called exactly once a run, after
    ``executor_run`` has closed and before ``executor_fetch`` opens, on
    both entry points; the run returns what ``run`` returns and leaves
    the records ``run`` leaves. What the callable raises fails the run
    before anything is fetched. ``Executor.run`` itself takes nothing
    new."""
    import inspect

    if entry == "with_mesh" and len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    assert list(inspect.signature(fluid.Executor.run).parameters) == [
        "self", "program", "feed", "fetch_list", "feed_var_name",
        "fetch_var_name", "scope", "return_numpy", "use_program_cache",
        "return_merged"]
    exe, scope, target, loss = _program_on(entry)
    feed = _feed()
    for _ in range(2):
        exe.run(target, feed=feed, fetch_list=[loss], scope=scope)
    trace.reset()
    calls = []

    def host_work():
        with trace.span("host_work_under_the_device"):
            calls.append(time.perf_counter())

    (got,) = exe._run(target, feed, [loss], scope,
                      while_device_runs=host_work)
    spans = trace.get_spans()
    assert len(calls) == 1 and isinstance(got, np.ndarray)
    assert [s["name"] for s in spans if s["id"] is not None] == [
        "executor_run", "host_work_under_the_device", "executor_fetch"]
    run, work, fetch = spans
    assert run["end"] <= work["start"] <= work["end"] <= fetch["start"]
    assert run["depth"] == work["depth"] == fetch["depth"]
    # without the callable: the two records of before
    trace.reset()
    (plain,) = exe.run(target, feed=feed, fetch_list=[loss], scope=scope)
    assert [s["name"] for s in trace.get_spans()] == [
        "executor_run", "executor_fetch"]
    assert plain.shape == got.shape and plain.dtype == got.dtype

    def broken():
        raise RuntimeError("host work failed")

    trace.reset()
    with pytest.raises(RuntimeError, match="host work failed"):
        exe._run(target, feed, [loss], scope, while_device_runs=broken)
    assert [s["name"] for s in trace.get_spans()] == ["executor_run"]


def test_marshal_counts_the_values_it_places(step):
    """``placed`` is the number of ``jax.device_put`` calls marshal made:
    the two feeds, on one device and under a mesh alike, because the
    state a step hands back is resident where the next step wants it."""
    _entry, spans, puts, _wall = step
    args = [s for s in spans if s["name"] == "executor_marshal"][0]["args"]
    assert args["placed"] == puts == 2
    assert args["segment"] == 0 and args["values"] > 10


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_mesh_state_is_placed_once_and_kept_in_the_scope():
    """Under ``with_mesh(fsdp=True)`` the first step lays every
    persistable out over the mesh and commits it to the scope as it does
    (the unsharded original of startup is released before the step
    runs); later steps find it resident: nothing but the feeds is placed
    and nothing is written to the scope before writeback."""
    exe, scope, target, loss = _program_on("with_mesh")
    names = [v.name for v in target._program.list_vars() if v.persistable
             and scope.get(v.name) is not None]
    before = {n: scope.get(n) for n in names}
    assert all(len(v.devices()) == 1 for v in before.values()
               if isinstance(v, jax.Array))

    def marshal_of_one_step():
        trace.reset()
        exe.run(target, feed=_feed(), fetch_list=[loss], scope=scope)
        (m,) = [s for s in trace.with_phases(trace.get_spans())
                if s["name"] == "executor_marshal"]
        return m["args"]

    first = marshal_of_one_step()
    assert first["placed"] == first["values"] > 10
    held = {n: scope.get(n) for n in names}
    assert all(isinstance(v, jax.Array) and len(v.devices()) == 4
               for v in held.values())
    assert any(not v.sharding.is_fully_replicated for v in held.values())
    sets = []
    real = scope.set
    scope.set = lambda n, v: (sets.append(n), real(n, v))[1]
    try:
        second = marshal_of_one_step()
    finally:
        scope.set = real
    assert second["placed"] == 2 and second["values"] == first["values"]
    # the writeback of the step's outputs, once each, and no placement
    assert len(sets) == len(set(sets))


def test_placed_counter_follows_the_spans():
    before = profiler.get_counter("executor_values_placed")
    _spans, puts, _wall = _steps("run")
    # two warm steps and the recorded one, two feeds each; startup's run
    # places nothing
    assert profiler.get_counter("executor_values_placed") - before == 3 * puts


def test_host_segment_gets_its_own_span():
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            out = fluid.layers.Print(fluid.layers.scale(x, scale=2.0),
                                     message="phase-span-test")
            out = fluid.layers.scale(out, scale=3.0)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    exe.run(startup, scope=scope)
    trace.reset()
    exe.run(main, feed={"x": np.ones((2, 4), "float32")},
            fetch_list=[out], scope=scope)
    names = [s["name"] for s in trace.with_phases(trace.get_spans())]
    assert names.count("executor_host_ops") == 1
    assert names.count("executor_marshal") == names.count(
        "executor_dispatch") == 2


# -- engine ------------------------------------------------------------------
MAX_LEN = 48


@pytest.fixture(scope="module")
def gen_server():
    # wide enough that a tick is milliseconds of work on the CPU: the
    # tick's self time (a few tens of microseconds of span bookkeeping)
    # is held to a share of it
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0,
                             hidden_size=256, num_layers=6,
                             intermediate_size=1024, vocab_size=2048)
    cfg.max_position_embeddings = MAX_LEN
    with fluid.unique_name.guard():
        infer, startup, _n, _l = gpt.build_gpt_infer(cfg, MAX_LEN)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    engine = DecodeEngine(cfg, scope=scope, slots=4, max_len=MAX_LEN,
                          block_size=4, prefill_buckets=[8, 16],
                          param_program=infer)

    class Echo(object):
        def run(self, feeds):
            return [np.asarray(feeds[0])]

        def clone(self, share_plans=True):
            return self

    server = serving.InferenceServer(
        Echo(), max_batch_size=1, num_workers=1, decode_engine=engine,
    ).start(warmup_inputs=[np.ones((1, 4), np.float32)])
    yield server
    server.stop()


TICK_CHILDREN = ("tick_reap", "tick_admit", "tick_prefill", "tick_build",
                 "decode_tick", "tick_sample_emit", "tick_publish")


@pytest.fixture(scope="module")
def ticks(gen_server):
    """Spans of a few dozen ticks with three streams in flight."""
    trace.reset()
    with trace.trace_scope(trace.new_trace_id()):
        streams = [gen_server.generate([3 + i, 7, 11], max_new_tokens=30)
                   for i in range(3)]
    for s in streams:
        s.tokens(timeout=120)
    return trace.get_spans()


def _children(parent, spans):
    return [s for s in spans if s is not parent and not s["instant"]
            and s["tid"] == parent["tid"] and s["depth"] == parent["depth"] + 1
            and s["start"] >= parent["start"] and s["end"] <= parent["end"]]


def test_tick_children_tile_the_tick(ticks):
    """Every ``engine_tick`` holds its phases in ``_tick`` order, and what
    the phases leave uncovered (its self time) is under 2 % of it at the
    median. ``tick_publish`` is a child of the tick only where no device
    call follows (the last stream's last token); everywhere else it lies
    inside one, under ``decode_paged_step`` or a window."""
    parents = [s for s in ticks if s["name"] == "engine_tick"]
    assert len(parents) >= 20
    shares, at_the_end = [], 0
    for p in parents:
        kids = sorted(_children(p, ticks), key=lambda s: s["start"])
        names = [k["name"] for k in kids]
        assert names[:3] == ["tick_reap", "tick_admit", "tick_prefill"]
        assert set(names) <= set(TICK_CHILDREN)
        if names[-1] == "tick_publish":
            at_the_end += 1
            names.pop()
        if "decode_tick" in names:
            assert names[3:] == ["tick_build", "decode_tick",
                                 "tick_sample_emit"]
        covered = sum(k["end"] - k["start"] for k in kids)
        shares.append(1.0 - covered / (p["end"] - p["start"]))
    assert statistics.median(shares) < 0.02
    assert 1 <= at_the_end <= 3   # three streams, each ends once


@pytest.mark.parametrize("call,at_least", [("decode_paged_step", 20),
                                           ("decode_paged_window", 2)])
def test_publish_lies_between_dispatch_and_fetch(ticks, call, at_least):
    """With the loop thread, what a tick decided is handed to the streams
    inside the next device call, whichever that is: the next tick's fused
    step, or an admission's window (the three streams are admitted one
    after another: the second's window carries the first's first token).
    ``tick_publish`` starts after that call's ``executor_run`` ends and
    ends before its ``executor_fetch`` begins."""
    loop = [s for s in ticks if s["name"] == "engine_tick"][0]["tid"]
    on_loop = sorted((s for s in ticks if s["tid"] == loop
                      and not s["instant"]), key=lambda s: s["start"])
    under = [s for s in on_loop if s["name"] == "tick_publish"
             and s["args"]["overlapped"] and s["parent"] == call]
    assert len(under) >= at_least
    emitted = {}
    for p in under:
        run = [s for s in on_loop if s["name"] == "executor_run"
               and s["end"] <= p["start"]][-1]
        fetch = [s for s in on_loop if s["name"] == "executor_fetch"
                 and s["start"] >= p["end"]][0]
        # the same device call: nothing of the executor in between
        between = [s for s in on_loop if s["name"] in PHASES
                   and run["end"] < s["start"] < fetch["start"]]
        assert between == []
        assert run["parent"] == fetch["parent"] == call
        assert p["args"]["tokens"] >= 1 and p["args"]["streams"] >= 1
        emit = [s for s in on_loop if s["name"] == "tick_sample_emit"
                and s["end"] <= p["start"]]
        if emit and call == "decode_paged_step":
            emitted[p["start"]] = emit[-1]["args"]["tokens"]
    # a step's publish hands out what the sample + emit before it decided
    # (plus, on some ticks, an admission's first token)
    assert all(p["args"]["tokens"] >= emitted[p["start"]]
               for p in under if p["start"] in emitted)
    assert emitted or call != "decode_paged_step"


def test_tick_says_what_it_held(ticks):
    stepped = [s for s in ticks if s["name"] == "engine_tick"
               and s["args"]["active"]]
    a = stepped[len(stepped) // 2]["args"]
    assert set(a) >= {"tick", "active", "prefilling", "queued",
                      "blocks_in_use", "blocks_total", "live_tokens"}
    assert 0 < a["blocks_in_use"] <= a["blocks_total"]
    # a block holds 4 tokens: the live tokens fit the blocks handed out
    assert a["live_tokens"] <= 4 * a["blocks_in_use"]
    emit = [s for s in ticks if s["name"] == "tick_sample_emit"]
    assert all(s["args"]["tokens"] >= 1 for s in emit)


LOOP_SPANS = ("engine_wait", "engine_tick", "tick_reap", "tick_admit",
              "tick_prefill", "tick_build", "step_feed", "decode_paged_step",
              "decode_paged_window", "executor_run", "executor_marshal",
              "executor_dispatch", "executor_writeback", "executor_fetch",
              "tick_publish", "tick_sample_emit")


@pytest.fixture(scope="module")
def cpu_ticks(gen_server):
    """Spans of served ticks at the rate the program runs at: one tick in
    ``CPU_EVERY`` reads the CPU clocks. Rounds of three short streams (a
    round's length moves the read tick through the round) until a read
    tick has held every kind of span the loop thread makes, a prompt's
    window and the idle wait among them."""
    trace.reset()
    spans = []
    for r in range(60):
        streams = [gen_server.generate([3 + i, 7, 11][:1 + (r + i) % 3],
                                       max_new_tokens=9 + r % 4)
                   for i in range(3)]
        for st in streams:
            st.tokens(timeout=120)
        spans = trace.with_phases(trace.get_spans())
        read = {s["name"] for s in spans if "cpu_ms" in s["args"]}
        if r >= 3 and read >= set(LOOP_SPANS):
            break
    return spans


def _loop_ticks(spans):
    loop = [s for s in spans if s["name"] == "engine_tick"][0]["tid"]
    return sorted((s for s in spans if s["tid"] == loop
                   and s["name"] == "engine_tick"), key=lambda s: s["start"])


def test_one_served_tick_in_cpu_every_reads_the_clocks(cpu_ticks):
    """The loop thread's ticks read their CPU clocks one in ``CPU_EVERY``,
    counted: between two ticks that did lie ``CPU_EVERY`` - 1 that did
    not. A tick that did not reads none in any span inside it and says no
    ``process_cpu_ms``; one that did reads them in every ``cpu=True`` span
    inside it."""
    ticks_ = _loop_ticks(cpu_ticks)
    read = [i for i, s in enumerate(ticks_) if "cpu_ms" in s["args"]]
    assert len(read) >= 3
    assert {b - a for a, b in zip(read, read[1:])} == {trace.CPU_EVERY}
    assert [("process_cpu_ms" in s["args"]) for s in ticks_] == [
        i in read for i in range(len(ticks_))]
    tid = ticks_[0]["tid"]
    inside = [s for s in cpu_ticks if s["tid"] == tid and not s["instant"]
              and s["name"] in LOOP_SPANS and s["name"] != "engine_wait"]
    for s in inside:
        (held,) = [i for i, t in enumerate(ticks_)
                   if t["start"] <= s["start"] and s["end"] <= t["end"]]
        assert ("cpu_ms" in s["args"]) == (held in read), s["name"]


@pytest.mark.parametrize("name", LOOP_SPANS)
def test_loop_thread_span_says_how_long_it_held_a_cpu(cpu_ticks, name):
    """Every kind of span of the loop thread, the executor's phases among
    them, carries ``cpu_ms`` on the ticks that read the clock: the
    thread's CPU time inside it, no more than its wall time. The two
    clocks are read one after the other, so a stall between a pair of
    reads (the machine's, not the program's) shows in one and not the
    other: 0.05 ms a span over all of a name's spans, and no single one
    off by a millisecond."""
    found = [s for s in cpu_ticks
             if s["name"] == name and "cpu_ms" in s["args"]]
    assert found
    walls = [1e3 * (s["end"] - s["start"]) for s in found]
    cpus = [s["args"]["cpu_ms"] for s in found]
    assert all(0.0 <= c <= w + 1.0 for c, w in zip(cpus, walls)), name
    assert sum(cpus) <= sum(walls) + 0.05 * len(found), name
    if name == "engine_wait":
        # asleep on the condition until the next round's first request
        assert all(s["args"]["cpu_ms"] < 5.0 for s in found)


def test_tick_says_what_the_whole_process_used(cpu_ticks):
    """``process_cpu_ms``: the CPU time of all the process's threads over
    the tick, on every tick that reads its thread's CPU clock: no less
    than the loop thread's own."""
    both = [s["args"] for s in _loop_ticks(cpu_ticks)
            if "process_cpu_ms" in s["args"]]
    assert len(both) >= 3
    assert all(a["process_cpu_ms"] >= a["cpu_ms"] - 1.0 for a in both)
    assert (sum(a["process_cpu_ms"] for a in both)
            >= sum(a["cpu_ms"] for a in both) - 0.05 * len(both))


def test_request_record_says_which_tick_took_it(ticks):
    """``submit_tick`` (the step counter as ``submit`` read it) and
    ``dequeue_tick`` (as ``_admit`` read it when it took the request
    off the queue): their difference is the steps the loop made while
    the request waited."""
    recs = [s["args"] for s in ticks if s["name"] == "decode_request"]
    assert len(recs) == 3
    steps = len([s for s in ticks if s["name"] == "decode_paged_step"])
    for a in recs:
        assert 0 <= a["submit_tick"] <= a["dequeue_tick"]
        assert a["dequeue_tick"] - a["submit_tick"] <= steps
    # the three were submitted one after another to an idle engine: the
    # last was dequeued no earlier than the first
    assert recs[0]["submit_tick"] <= recs[-1]["dequeue_tick"]


def test_decode_tick_keeps_its_extent(ticks):
    """``decode_tick`` stays the fused device call: it holds the step's
    feed building and ``decode_paged_step``, whose fetch brings the
    picked ids, and no sampling."""
    tick = [s for s in ticks if s["name"] == "decode_tick"][-1]
    names = {k["name"] for k in _children(tick, ticks)}
    assert names == {"step_feed", "decode_paged_step"}


def test_no_span_per_token_or_slot(ticks):
    n_ticks = len([s for s in ticks if s["name"] == "engine_tick"])
    on_loop = [s for s in ticks if not s["instant"] and s["tid"] == [
        t for t in ticks if t["name"] == "engine_tick"][0]["tid"]]
    # the phases, the step's own spans and the executor's: a fixed number
    # a tick, however many streams or tokens
    assert len(on_loop) <= 16 * n_ticks + 40


# -- one record per request --------------------------------------------------
def _sse_request(port, prompt, n):
    """-> (tokens, the done event, POST sent -> first token seconds)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    body = json.dumps({"prompt_ids": prompt, "max_new_tokens": n}).encode()
    toks, done, first = [], None, None
    try:
        sent = time.perf_counter()
        conn.request("POST", "/v1/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        for line in resp:
            if not line.startswith(b"data: "):
                continue
            event = json.loads(line[6:])
            if "token" in event:
                if first is None:
                    first = time.perf_counter() - sent
                toks.append(event["token"])
            elif event.get("done"):
                done = event  # and read on to the end of the response
    finally:
        conn.close()
    return toks, done, first


def test_request_records_share_a_trace_id_and_agree_with_the_client(
        gen_server):
    gw = serving.Gateway(gen_server, port=0).start()
    try:
        _sse_request(gw.port, [2, 9, 4], 4)  # warm the handler path
        trace.reset()
        toks, done, ttft = _sse_request(gw.port, [5, 6, 7, 8], 12)
    finally:
        gw.stop()
    spans = trace.get_spans()
    tid = done["trace_id"]
    # by the id the done event gave the client (the warm request's
    # handler may close its span after the client has gone on)
    (req,) = [s for s in spans if s["name"] == "decode_request"
              and s["trace_id"] == tid]
    (gws,) = [s for s in spans if s["name"] == "gateway_request"
              and s["trace_id"] == tid]
    assert req["instant"]
    a = req["args"]
    assert a["tokens"] == len(toks) == 12
    assert a["finish_reason"] == "length" and a["preempted"] == 0
    assert a["prefill_windows"] == 1
    assert a["submit"] <= a["dequeue"] <= a["first_token"] <= a["finish"]
    assert a["queue_wait_ms"] == pytest.approx(
        1e3 * (a["dequeue"] - a["submit"]))
    # the client's time to first token is the engine's queue wait and
    # prefill plus the request path on either side of them
    # (same process, same clock; the slack is for a loaded machine)
    inside = (a["queue_wait_ms"] + a["first_token_ms"]) / 1e3
    assert inside <= ttft <= inside + 2.0
    g = gws["args"]
    assert g["status"] == 200 and g["tokens"] == 12
    assert 0 <= g["sse_lag_ms_p50"] <= g["sse_lag_ms_max"] < 2000.0
    assert a["submit_tick"] <= a["dequeue_tick"]


def test_failed_stream_leaves_a_record_too(gen_server):
    trace.reset()
    stream = gen_server.generate([1, 2, 3], max_new_tokens=40)
    next(iter(stream))
    stream.cancel()
    with pytest.raises(Exception):
        stream.tokens(timeout=60)
        raise RuntimeError("cancelled streams end without an error")
    recs = [s for s in trace.get_spans() if s["name"] == "decode_request"]
    assert len(recs) == 1 and recs[0]["args"]["finish_reason"] == "cancelled"


# -- kernels -----------------------------------------------------------------
KERNELS = {
    "flash_decode_paged_attention": "flash_decode_paged",
    "_decode_paged_grouped": "flash_decode_paged_gqa",
    "mla_decode_paged_attention": "mla_decode_paged",
    "_flash_fwd_impl": "flash_fwd",
    "_flash_bwd_core": ("flash_bwd_dq", "flash_bwd_dkv"),
}


def _pallas_call_names():
    """{enclosing function: [the ``name=`` of each pallas_call in it]}"""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "kernels",
        "flash_attention.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    out = {}
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                names = [k.value.value for k in node.keywords
                         if k.arg == "name"]
                out.setdefault(fn.name, []).append(
                    names[0] if names else None)
    return out


@pytest.mark.parametrize("site", sorted(KERNELS))
def test_each_pallas_call_passes_its_own_name(site):
    want = KERNELS[site]
    want = list(want) if isinstance(want, tuple) else [want]
    found = _pallas_call_names()
    assert found[site] == want
    every = [n for names in found.values() for n in names]
    assert None not in every and len(set(every)) == len(every) == 6


def test_kernel_name_reaches_the_lowered_program():
    """The name is what a device trace shows: it is in the text of the
    program jax lowers (interpret mode off, nothing compiled or run)."""
    import importlib

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    q = jax.ShapeDtypeStruct((1, 2, 128, 64), jax.numpy.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True,
                                  interpret=False).astype("float32").sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        q, q, q).jaxpr.pretty_print(use_color=False)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text


# -- off means off -----------------------------------------------------------
def test_no_record_with_tracing_off(gen_server, monkeypatch):
    """... and no span reads a CPU clock: neither the thread's nor the
    process's, in a served request or an ``Executor.run``."""
    reads = []
    for clock in ("thread_time", "process_time"):
        real = getattr(time, clock)
        monkeypatch.setattr(
            time, clock, lambda real=real: (reads.append(1), real())[1])
    fluid.set_flags({"FLAGS_obs_trace": False})
    try:
        # the idle loop's engine_wait was opened with tracing on: let a
        # request close it before the buffer is emptied
        gen_server.generate([1, 2], max_new_tokens=2).tokens(timeout=60)
        trace.reset()
        _steps_main, startup, loss = _train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.core.Scope()
        exe.run(startup, scope=scope)
        exe.run(_steps_main, feed=_feed(), fetch_list=[loss], scope=scope)
        reads.clear()
        exe.run(_steps_main, feed=_feed(), fetch_list=[loss], scope=scope)
        gen_server.generate([4, 5, 6], max_new_tokens=6).tokens(timeout=60)
        assert trace.get_spans() == []
        assert reads == []
    finally:
        fluid.set_flags({"FLAGS_obs_trace": True})
