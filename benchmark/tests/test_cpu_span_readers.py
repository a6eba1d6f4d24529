"""The readers of the loop thread's CPU clock (PR 37) against a window
laid out by hand: two ticks whose spans carry ``cpu_ms`` / ``cpu_at`` as
the tracer records them (the executor's phases as marks with a stamp
each), a device line on a tied clock, the handlers' counter; each reader
gives its value, and None where the program left none of the new args (the
parent commit); and on the rehearsal of a serving cell, where what they
read has to hang together.
"""

import os

import pytest

from benchmark.harness import cells, cpu_spans, reduce
from benchmark.tests.test_control_and_broken_path import (_context,
                                                          _with_limits)
from benchmark.tests.test_program_spans import (HANDLER, LOOP, evidence,
                                                instant, op, read, span)
from benchmark.traffic_kinds import serve_closed

SERVE_CELLS = ["gpt2s-serve-chat", "kanana2-serve-chat4k",
               "solar2-serve-reason4k"]
TO_PROFILER = 1000.0


def cpu_span(name, start, end, cpu_ms, cpu_at=5.0, **args):
    return span(name, start, end, LOOP, cpu_ms=cpu_ms, cpu_at=cpu_at, **args)


def tick(t, number, tick_cpu_ms, process_cpu_ms):
    """One tick of 100 ms on the loop thread, in ms from its start:
    reap 0-2 (2 of CPU), admit 2-10 (4), feed 10-20 (2), the step 20-90:
    run 20-30 (marshal 20-26 with 5 of CPU, dispatch 26-29 with 2.5,
    writeback 29-30 with 0.5), publish 30-40 (3), fetch 40-90 (1);
    sample + emit 90-100 (10)."""
    ms = 1e-3
    run_at = 7.0 + t
    return [
        cpu_span("engine_tick", t, t + 100 * ms, tick_cpu_ms, tick=number,
                 process_cpu_ms=process_cpu_ms),
        cpu_span("tick_reap", t, t + 2 * ms, 2.0),
        cpu_span("tick_admit", t + 2 * ms, t + 10 * ms, 4.0),
        span("decode_tick", t + 10 * ms, t + 90 * ms, LOOP),
        cpu_span("step_feed", t + 10 * ms, t + 20 * ms, 2.0),
        cpu_span("decode_paged_step", t + 20 * ms, t + 90 * ms, 12.0,
                 active=64),
        cpu_span("executor_run", t + 20 * ms, t + 30 * ms, 8.0, run_at,
                 prepare_ms=0.1, plan_hit=True, phases=[
                     ("executor_marshal", t + 20 * ms,
                      {"segment": 0, "cpu_at": run_at}),
                     ("executor_dispatch", t + 26 * ms,
                      {"segment": 0, "cpu_at": run_at + 5.0 * ms}),
                     ("executor_writeback", t + 29 * ms,
                      {"cpu_at": run_at + 7.5 * ms})]),
        cpu_span("tick_publish", t + 30 * ms, t + 40 * ms, 3.0,
                 overlapped=True, tokens=64, streams=64),
        cpu_span("executor_fetch", t + 40 * ms, t + 90 * ms, 1.0, bytes=512),
        cpu_span("tick_sample_emit", t + 90 * ms, t + 100 * ms, 10.0,
                 tokens=64),
    ]


def window():
    spans = tick(10.0, 3, 30.0, 90.0) + tick(10.1, 4, 40.0, 90.0)
    # a publish that hands over an ending alone: not a token's publish
    spans.append(cpu_span("tick_publish", 10.2, 10.25, 15.0,
                          overlapped=False, tokens=0, streams=1))
    for i, (sub, deq) in enumerate(((3, 3), (3, 4), (5, 7))):
        spans.append(instant("decode_request", 10.21 + 0.01 * i,
                             submit=9.0, dequeue=9.5, queue_wait_ms=1.0,
                             submit_tick=sub, dequeue_tick=deq, tokens=40))
    spans.append(span("gateway_request", 9.0, 10.2, HANDLER, status=200))
    return spans


COUNTERS = {"gateway_handler_cpu_us": 6400, "gateway_stream_tokens": 128,
            "gateway_stream_sends": 130}


def device_line():
    """The T = 1 step's program on the first chip, profiler's clock: it
    ends 9 ms before the first tick's fetch returns and 3 ms before the
    second's; a window's program ran between them, in the second tick's
    admission."""
    mods = [op("jit_step(7)", TO_PROFILER + 10.031, 0.050),
            op("jit_window(9)", TO_PROFILER + 10.103, 0.001),
            op("jit_step(7)", TO_PROFILER + 10.131, 0.056)]
    ops = [op("fusion.%d" % i, m.start, m.dur) for i, m in enumerate(mods)]
    return ops, mods


def served(spans=None, counters=COUNTERS, device=True,
           to_profiler=TO_PROFILER):
    ops, mods = device if device and device is not True else (
        device_line() if device else ((), ()))
    ev = evidence(window() if spans is None else spans, ops=ops,
                  modules=mods, to_profiler=to_profiler)
    ev.counters = dict(counters)
    return ev


def as_the_parent_records(spans):
    """The same window from a program without this PR's args: no
    ``cpu_at`` anywhere, ``cpu_ms`` on ``engine_tick`` and
    ``tick_sample_emit`` alone (its two hand-rolled pairs), no
    ``process_cpu_ms``, no tick numbers on the request's record."""
    new = ("cpu_at", "process_cpu_ms", "submit_tick", "dequeue_tick")
    out = []
    for s in spans:
        args = {k: v for k, v in s["args"].items() if k not in new}
        if s["name"] not in ("engine_tick", "tick_sample_emit"):
            args.pop("cpu_ms", None)
        if "phases" in args:
            args["phases"] = [
                (n, at, {k: v for k, v in own.items() if k != "cpu_at"})
                for n, at, own in args["phases"]]
        out.append(dict(s, args=args))
    return out


WANT = {
    # host time 100 - 50 of fetch a tick; its CPU 30 - 1 and 40 - 1: 32 %
    # of the two ticks' host time is waiting, of a median of 50 ms
    "tick_lock_wait_ms_p50": 50.0 * (1.0 - (29.0 + 39.0) / 100.0),
    "tick_publish_ms_p50": 10.0,
    "fetch_past_device_ms_p50": 0.5 * (9.0 + 3.0),
    # the 49 idle ms between the two steps, see the test below
    "idle_host_waiting_pct.serve": 100.0 * 14.2 / 49.0,
    "handler_cpu_us_per_token": 50.0,
    "process_cpu_other_pct": 100.0 * (180.0 - 70.0 - 6.4) / 180.0,
    "admit_ticks_waited_mean": 1.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_value_and_absent(name):
    assert read(name, served()) == pytest.approx(WANT[name])
    old = served(as_the_parent_records(window()),
                 counters={"gateway_stream_tokens": 128,
                           "gateway_stream_sends": 130})
    assert read(name, old) is None
    assert read(name, served(spans=[span("other", 1.0, 2.0)],
                             counters={})) is None


def test_phases_get_their_cpu_from_the_stamps():
    spans = cpu_spans.in_window(served())
    got = {s["name"]: cpu_spans.cpu_ms(s) for s in spans
           if s["name"].startswith("executor_") and s["start"] < 10.1
           and cpu_spans.cpu_ms(s) is not None}
    assert got == pytest.approx({
        "executor_run": 8.0, "executor_marshal": 5.0,
        "executor_dispatch": 2.5, "executor_writeback": 0.5,
        "executor_fetch": 1.0})
    # executor_prepare is made from prepare_ms: it carries no clock
    assert "executor_prepare" in {s["name"] for s in spans}


def test_own_time_is_what_the_spans_within_leave():
    nodes = {(n.label, n.span["start"]): n
             for n in cpu_spans.loop_nodes(served())}
    step = nodes[("decode_paged_step", 10.02)]
    # run + publish + fetch fill the step: 70 ms of wall, 12 of its CPU
    assert step.self_ms == pytest.approx(0.0, abs=1e-9)
    assert step.self_cpu_ms == pytest.approx(0.0, abs=1e-9)
    tick_ = nodes[("engine_tick", 10.0)]
    assert step.tick is tick_
    assert tick_.self_ms == pytest.approx(0.0, abs=1e-9)
    assert tick_.self_cpu_ms == pytest.approx(30.0 - 2 - 4 - 2 - 12 - 10)
    assert not nodes[("step_feed", 10.01)].in_fetch
    assert nodes[("executor_fetch", 10.04)].in_fetch
    own = cpu_spans.own_shares(nodes.values())
    assert own["step_feed"].wait_share == pytest.approx(0.8)
    assert own["executor_marshal"].cpu_share == pytest.approx(5.0 / 6.0)
    run = nodes[("executor_run", 10.02)]
    assert run.self_ms == pytest.approx(0.0, abs=1e-9)


def test_a_windows_spans_are_told_from_the_steps():
    t = 10.002
    spans = window() + [
        cpu_span("decode_paged_window", t + 0.001, t + 0.007, 1.0),
        cpu_span("executor_fetch", t + 0.003, t + 0.006, 0.1)]
    labels = [n.label for n in cpu_spans.loop_nodes(served(spans))
              if n.span["start"] < 10.01]
    assert labels == ["engine_tick", "tick_reap", "tick_admit",
                      "decode_paged_window@window", "executor_fetch@window"]
    # and the step's fetch is the one inside decode_paged_step
    pairs = cpu_spans.step_fetches(served(spans))
    assert [(a["name"], round(b["start"], 3)) for a, b in pairs] == [
        ("decode_paged_step", 10.04), ("decode_paged_step", 10.14)]


def test_wait_by_phase_note():
    ev = served()
    assert read("tick_publish_ms_p50", ev) is not None
    assert read("tick_lock_wait_ms_p50", ev) is not None
    notes = dict(ev.ctx.notes)
    by = notes["tick_wait_by_phase"]["phase_ms_p50"]
    assert by["step_feed"] == pytest.approx({
        "wall": 10.0, "cpu": 2.0, "wait": 8.0, "cpu_share": 0.2,
        "spans": 2, "stamped": 2, "wall_sum": 20.0, "cpu_sum": 4.0})
    assert by["executor_marshal"]["wait"] == pytest.approx(1.0)
    assert by["tick_publish"]["spans"] == 3
    assert all(v["cpu"] <= v["wall"] + 0.05 for v in by.values())
    assert notes["tick_wait_by_phase"]["cpu_clock_step_ms"] == (
        pytest.approx(0.5))
    tiled = notes["tick_wait_by_phase"]["self_ms_a_tick"]
    # the ticks' time tiled by the innermost timed span: 100 ms a tick
    assert sum(v["wall"] for v in tiled.values()) == pytest.approx(100.0)
    assert sum(v["cpu"] for v in tiled.values()) == pytest.approx(35.0)
    assert notes["tick_host_split"] == pytest.approx({
        "wall": 50.0, "cpu": 34.0, "wait": 16.0, "cpu_share": 0.68,
        "spans": 2, "stamped": 2, "wall_sum": 100.0, "cpu_sum": 68.0})


def coarse_ticks(cpus):
    """Ticks of 50 ms of host time each (and a fetch of 50) whose CPU
    clock moves 10 ms at a time: tick i read ``cpus[i]`` ms, in its
    ``step_feed`` where that is 10."""
    spans = []
    for i, cpu in enumerate(cpus):
        t = 10.0 + 0.1 * i
        spans += [cpu_span("engine_tick", t, t + 0.1, cpu, tick=i,
                           process_cpu_ms=50.0),
                  cpu_span("step_feed", t + 0.01, t + 0.02,
                           10.0 if cpu == 10.0 else 0.0),
                  cpu_span("executor_fetch", t + 0.04, t + 0.09, 0.0)]
    return spans


def test_a_clock_that_moves_in_steps_of_ten_ms_reads_by_sums():
    """Under a sandbox's kernel a thread's CPU clock moves 10 ms at a
    time: one span reads 0 or 10 whatever it did. 800 ticks of 50 ms of
    host time, a step in every fourth: 5 % of the host time was CPU, so
    47.5 ms of the median tick's 50 was waiting (the median of the ticks'
    own differences would say all 50)."""
    ev = served(coarse_ticks([10.0 if i % 4 == 0 else 0.0
                              for i in range(800)]), device=False)
    assert read("tick_lock_wait_ms_p50", ev) == pytest.approx(47.5)
    assert read("tick_publish_ms_p50", ev) is None      # no publish there
    note = dict(ev.ctx.notes)["tick_wait_by_phase"]
    assert note["cpu_clock_step_ms"] == 10.0
    assert note["phase_ms_p50"]["step_feed"]["cpu"] == pytest.approx(2.5)
    assert note["phase_ms_p50"]["step_feed"]["wait"] == pytest.approx(7.5)


N = cpu_spans.MIN_CLOCK_STEPS


@pytest.mark.parametrize("cpus,want", [
    # one step of the clock short in the window: too few to say
    ([10.0] * (N - 1) + [0.0] * (N + 1), None),
    # enough: a tenth of the 2 N ticks' host time was CPU
    ([10.0] * N + [0.0] * N, 50.0 * 0.9),
    # steps that scatter past the host time are reported as they came
    # out, not cut to a wait of 0
    ([60.0] * N, 50.0 * (1.0 - 1.2)),
    # every tick read 0: no step to count in
    ([0.0] * 300, None),
], ids=["a_step_short", "enough_steps", "past_the_wall", "no_step"])
def test_a_share_needs_enough_steps_of_the_clock(cpus, want):
    ev = served(coarse_ticks(cpus), device=False)
    got = read("tick_lock_wait_ms_p50", ev)
    assert got == (want if want is None else pytest.approx(want))
    assert cpu_spans.enough(sum(cpus), 10.0) == (want is not None)


def test_idle_seconds_split_by_what_the_host_did():
    """Between the two steps, 10.081 to 10.131 on the host's clock less
    the window's program (1 ms of the second tick's admission): the first
    fetch's tail (9 idle ms: the host waits for its turn to take the
    device's answer), sample + emit (10 ms, all CPU), then the second
    tick up to 1 ms into its publish, each span's share split by its
    name's own CPU over its own wall."""
    ev = served()
    assert read("idle_host_waiting_pct.serve", ev) == pytest.approx(
        100.0 * 14.2 / 49.0)
    (note,) = [n for k, n in ev.ctx.notes if k == "idle_by_what_the_host_did"]
    waiting = (0.0035 + 0.008 + 0.001 + 0.0005 + 0.0005 + 0.0007)
    assert note["in_fetch"] == pytest.approx(0.009)
    assert note["lock_waiting"] == pytest.approx(waiting)
    assert note["working"] == pytest.approx(0.049 - 0.009 - waiting)
    assert note["no_span"] == pytest.approx(0.0, abs=1e-12)
    assert note["idle_s"] == pytest.approx(0.049)
    assert note["top"]["lock_waiting"][0] == (
        "step_feed", pytest.approx(0.008))
    assert note["top"]["in_fetch"] == [("executor_fetch",
                                        pytest.approx(0.009))]
    # the same seconds idle_unattributed_pct.serve shares out
    from benchmark.harness import program_spans as ps

    assert sum(ps.idle_by_span(served()).values()) == pytest.approx(0.049)


def test_fetch_past_device_note_and_what_it_needs():
    ev = served()
    assert read("fetch_past_device_ms_p50", ev) == pytest.approx(6.0)
    (note,) = [n for k, n in ev.ctx.notes if k == "fetch_past_device"]
    assert note == pytest.approx({
        "wall": 50.0, "cpu": 1.0, "wait": 49.0, "cpu_share": 0.02,
        "spans": 2, "stamped": 2, "wall_sum": 100.0, "cpu_sum": 2.0})
    # where windows took most of the profile the step's program is still
    # the one that ran inside ``decode_paged_step``
    ops, mods = device_line()
    early = op("jit_window(9)", TO_PROFILER + 9.5, 0.4)
    ev = served(device=([early] + ops, [early] + mods))
    assert {m.name for m in ev.steps()} == {"jit_window(9)"}
    assert read("fetch_past_device_ms_p50", ev) == pytest.approx(6.0)
    for name in ("fetch_past_device_ms_p50", "idle_host_waiting_pct.serve"):
        assert read(name, served(device=False)) is None
        assert read(name, served(to_profiler=None)) is None
    # a program that had ended before the fetch began (a slow host
    # between dispatch and fetch): the fetch's whole wall time, no more
    late = [dict(s, start=s["start"] + 0.045) if s["name"] == "executor_fetch"
            and s["start"] < 10.1 else s for s in window()]
    assert read("fetch_past_device_ms_p50", served(late)) == pytest.approx(
        0.5 * (5.0 + 3.0))
    # a fetch with no whole program between its step's opening and its
    # own end (the trace began mid-step) is left out, not read as 0
    early = [dict(s, end=s["end"] - 0.012) if s["name"] == "executor_fetch"
             and s["start"] > 10.1 else s for s in window()]
    assert read("fetch_past_device_ms_p50", served(early)) == pytest.approx(
        9.0)


def test_process_cpu_note_adds_up():
    ev = served()
    assert read("process_cpu_other_pct", ev) == pytest.approx(
        WANT["process_cpu_other_pct"])
    (note,) = [n for k, n in ev.ctx.notes if k == "process_cpu_a_tick"]
    assert note["process_ms"] == pytest.approx(90.0)
    assert note["loop_ms"] + note["handlers_ms"] + note["other_ms"] == (
        pytest.approx(note["process_ms"]))
    # a tick that says no process_cpu_ms is left out, not read as 0; the
    # handlers' microseconds are the window's, so half of them a tick
    first = [dict(s, args=dict(s["args"], process_cpu_ms=None))
             if s["name"] == "engine_tick" and s["start"] < 10.05 else s
             for s in window()]
    assert read("process_cpu_other_pct", served(first)) == pytest.approx(
        100.0 * (90.0 - 40.0 - 3.2) / 90.0)


def unstamped(spans):
    """The same spans from a tick that read no CPU clock."""
    out = []
    for s in spans:
        args = {k: v for k, v in s["args"].items()
                if k not in ("cpu_ms", "cpu_at", "process_cpu_ms")}
        if "phases" in args:
            args["phases"] = [
                (n, at, {k: v for k, v in own.items() if k != "cpu_at"})
                for n, at, own in args["phases"]]
        out.append(dict(s, args=args))
    return out


def test_where_one_tick_in_several_reads_the_clock(monkeypatch):
    """The program reads its CPU clock in one tick of several. The shares
    come from the stamped ticks, the medians and the idle seconds from
    all of them: with the first tick stamped and the second not, what the
    first says alone (its 29 ms are 58 of this window's steps of the
    clock: allowed here)."""
    monkeypatch.setattr(cpu_spans, "MIN_CLOCK_STEPS", 50)
    spans = tick(10.0, 3, 30.0, 90.0) + unstamped(tick(10.1, 4, 40.0, 90.0))
    ev = served(spans)
    assert read("tick_publish_ms_p50", ev) == pytest.approx(10.0)
    assert read("tick_lock_wait_ms_p50", ev) == pytest.approx(50.0 - 29.0)
    note = dict(ev.ctx.notes)["tick_wait_by_phase"]
    assert (note["ticks"], note["stamped_ticks"]) == (2, 1)
    assert note["phase_ms_p50"]["step_feed"] == pytest.approx({
        "wall": 10.0, "cpu": 2.0, "wait": 8.0, "cpu_share": 0.2,
        "spans": 2, "stamped": 1, "wall_sum": 10.0, "cpu_sum": 2.0})
    assert sum(v["wall"] for v in note["self_ms_a_tick"].values()) == (
        pytest.approx(100.0))
    assert read("fetch_past_device_ms_p50", ev) == pytest.approx(6.0)
    # the idle seconds lie in the unstamped tick for the most part: they
    # are split by the shares the stamped one gave its names
    assert read("idle_host_waiting_pct.serve", ev) == pytest.approx(
        100.0 * 14.2 / 49.0)
    ev.counters = dict(COUNTERS)
    # 90 ms of the process less the loop's 30 and the handlers' 6.4 over
    # BOTH ticks
    assert read("process_cpu_other_pct", ev) == pytest.approx(
        100.0 * (90.0 - 30.0 - 3.2) / 90.0)
    # and by the rule: one tick's 58 steps are too few for a share
    monkeypatch.undo()
    ev = served(spans)
    assert read("tick_lock_wait_ms_p50", ev) is None
    assert read("idle_host_waiting_pct.serve", ev) is None
    assert read("tick_publish_ms_p50", ev) == pytest.approx(10.0)


@pytest.mark.parametrize("name", sorted(WANT))
def test_manifest_lists_it_for_the_serving_cells(name):
    (entry,) = [m for m in cells.manifest()["per_layer"]
                if m["name"] == name]
    # the two that rest on a share of the loop thread's CPU need the
    # host-bound cell's many ticks (cpu_spans.MIN_CLOCK_STEPS)
    assert entry["workloads"] == (
        SERVE_CELLS[:1] if name in ("tick_lock_wait_ms_p50",
                                    "idle_host_waiting_pct.serve")
        else SERVE_CELLS)
    assert sorted(entry) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
    assert entry["better"] == "lower"
    assert entry["moves"] in ("tpot_p90_ms", "serve_tok_per_s")
    assert os.path.isfile(os.path.join(
        cells.BENCH_DIR, "layer_metrics", name + ".py"))


def test_rehearsal_readings_hang_together(monkeypatch):
    """On the program itself (CPU, toy widths): the five readers that
    need no device trace find their args, and what they read is
    consistent with the readers that time the same thing from outside."""
    _with_limits(monkeypatch, {"served_logit_gap_mean": 1e-4})
    cell, ctx = _context("gpt2s-serve-chat", 6, 2.0)
    notes = {}
    ctx.note = lambda kind, **facts: notes.__setitem__(kind, facts)
    facts = serve_closed.run(ctx)
    ev = reduce.Evidence(ctx, facts, None)
    got = {name: cell.module("layer_metrics", name).read(ev)
           for name in list(WANT) + ["tick_host_ms_p50"]}
    assert got["fetch_past_device_ms_p50"] is None      # no device trace
    assert got["idle_host_waiting_pct.serve"] is None
    assert 0.0 <= got["tick_lock_wait_ms_p50"] <= got["tick_host_ms_p50"]
    assert got["tick_publish_ms_p50"] > 0.0
    assert got["handler_cpu_us_per_token"] > 0.0
    assert 0.0 <= got["process_cpu_other_pct"] < 100.0
    assert got["admit_ticks_waited_mean"] >= 0.0
    by = notes["tick_wait_by_phase"]["phase_ms_p50"]
    assert {"tick_publish", "executor_marshal", "executor_dispatch",
            "executor_writeback", "step_feed", "executor_fetch"} <= set(by)
    assert all(v["cpu"] <= v["wall"] + 0.05 for v in by.values())
    cpu = notes["process_cpu_a_tick"]
    assert cpu["loop_ms"] + cpu["handlers_ms"] <= cpu["process_ms"]
