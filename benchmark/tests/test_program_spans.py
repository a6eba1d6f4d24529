"""The readers of the program's spans (PR 25) against small hand-made
evidence: each gives its value, and None (never 0) where the program
opened no such span; ``program_spans``' self time and gap attribution
against intervals laid out by hand."""

import pytest

from benchmark.harness import cells, reduce, xplane
from benchmark.harness import program_spans as ps

MAIN, LOOP, HANDLER = 1, 2, 3


def span(name, start, end, tid=MAIN, **args):
    return {"name": name, "start": start, "end": end, "tid": tid,
            "args": args, "instant": False}


def instant(name, at, tid=LOOP, **args):
    return dict(span(name, at, at, tid, **args), instant=True)


def op(name, start, dur):
    return xplane.Event(name, start, dur)


class Ctx(object):
    def __init__(self):
        self.notes = []

    def note(self, kind, **facts):
        self.notes.append((kind, facts))


def evidence(spans, window=(0.0, 100.0), ops=(), modules=(), config=None,
             to_profiler=0.0):
    """An ``Evidence`` with what the readers look at filled in by hand."""
    ev = object.__new__(reduce.Evidence)
    ev.ctx, ev.spans, ev.window = Ctx(), list(spans), window
    ev.config, ev.traffic, ev.chips = config or {}, {}, 1
    ev.counters, ev.requests, ev.peaks = {}, [], None
    ev.to_profiler = to_profiler
    ev.trace = None
    if ops:
        ev.trace = xplane.Trace(
            [xplane.DevicePlane("/device:TPU:0", list(ops), list(modules))],
            {})
    return ev


def read(name, ev):
    return cells.load_module("layer_metrics", name).read(ev)


def train_runs():
    """Two steps on the main thread as the tracer records them: one
    ``executor_run`` with its phases as marks (the second step has two
    segments) and one ``executor_fetch``."""
    out = []
    for t, segs in ((10.0, 1), (20.0, 2)):
        marks = []
        for i in range(segs):
            at = t + 0.001 + 0.008 * i
            marks.append(["executor_marshal", at,
                          {"segment": i, "values": 100,
                           "placed": 10 * segs}])
            marks.append(["executor_dispatch", at + 0.002, {"segment": i}])
        end = t + 0.001 + 0.008 * segs + 0.001
        marks.append(["executor_writeback", end - 0.001, {"values": 90}])
        out.append(span("executor_run", t + 0.001, end, prepare_ms=1.0,
                        plan_hit=True, phases=marks))
        out.append(span("executor_fetch", end, end + 0.05 * segs, bytes=4))
    return out


def serve_ticks():
    """Two ticks on the loop thread, a request record, a gateway span."""
    out = []
    for t, emit_cpu in ((10.0, 4.0), (11.0, 10.0)):
        out.append(span("engine_tick", t, t + 0.4, LOOP, tick=int(t),
                        active=64, prefilling=0, queued=0, cpu_ms=50.0,
                        blocks_in_use=1000, blocks_total=4000,
                        live_tokens=12000))
        out.append(span("tick_build", t + 0.01, t + 0.02, LOOP))
        out.append(span("decode_tick", t + 0.02, t + 0.33, LOOP))
        out.append(span("executor_run", t + 0.03, t + 0.04, LOOP))
        out.append(span("executor_fetch", t + 0.04, t + 0.32, LOOP, bytes=1))
        out.append(span("tick_sample_emit", t + 0.33, t + 0.35 + 0.01 * (
            t - 10.0), LOOP, tokens=64, cpu_ms=emit_cpu))
    for i, (wait, lag) in enumerate(((1.0, 2.0), (3.0, 4.0), (5.0, 9.0))):
        out.append(instant("decode_request", 12.0 + i, submit=9.0,
                           dequeue=10.5 + i, first_token=10.6 + i,
                           finish=12.0 + i, queue_wait_ms=wait,
                           first_token_ms=100.0, tokens=40,
                           finish_reason="length", preempted=0,
                           prefill_windows=1))
        out.append(span("gateway_request", 9.0, 12.0 + i, HANDLER + i,
                        status=200, sse_lag_ms_p50=lag,
                        sse_lag_ms_max=2 * lag, tokens=40))
    return out


TRAIN = {
    # per run: 2 ms and 4 ms of marshal, 6 and 12 of dispatch
    "exec_marshal_ms.train": 3.0,
    "exec_dispatch_ms.train": 9.0,
    "exec_fetch_wait_ms.train": 75.0,
    # placed 10 + 20 + 20 of 300 values
    "exec_values_placed_pct.train": 100.0 * 50 / 300,
}
SERVE = {
    "tick_host_ms_p50": 400.0 - 280.0,
    "tick_sample_emit_ms_p50": 25.0,
    # 20 ms - 4 ms and 30 ms - 10 ms
    "tick_emit_starved_ms_p50": 18.0,
    "queue_wait_ms_p50": 3.0,
    "sse_lag_ms_p90": 8.0,
    "kv_pool_used_pct": 25.0,
    "kv_block_fill_pct": 75.0,
}


@pytest.mark.parametrize("name", sorted(TRAIN))
def test_train_reader_value_and_absent(name):
    assert read(name, evidence(train_runs())) == pytest.approx(TRAIN[name])
    # an older program: executor_run alone, as PR 24 recorded it. The
    # readers fetch from the tracer's own buffer when handed no spans, so
    # the evidence holds one that is not theirs
    old = evidence([span("executor_run", 10.0, 10.02), span("other", 1, 2)])
    assert read(name, old) is None


@pytest.mark.parametrize("name", sorted(SERVE))
def test_serve_reader_value_and_absent(name):
    ev = evidence(serve_ticks(), config={"serve": {"block_size": 16}})
    assert read(name, ev) == pytest.approx(SERVE[name])
    old = evidence([span("decode_tick", 10.0, 10.3, LOOP),
                    span("executor_run", 10.0, 10.3, LOOP),
                    span("gateway_request", 9.0, 12.0, HANDLER, status=200)],
                   config={"serve": {"block_size": 16}})
    assert read(name, old) is None


def test_window_cuts_the_spans():
    ev = evidence(train_runs(), window=(15.0, 100.0))
    assert read("exec_marshal_ms.train", ev) == pytest.approx(4.0)
    assert read("queue_wait_ms_p50", evidence(
        serve_ticks(), window=(11.0, 100.0))) == pytest.approx(4.0)


def test_self_time_by_thread_and_containment():
    parent = span("engine_tick", 0.0, 1.0, LOOP)
    spans = [parent,
             span("tick_admit", 0.0, 0.3, LOOP),
             span("decode_paged_window", 0.1, 0.2, LOOP),   # a grandchild
             span("decode_tick", 0.3, 0.7, LOOP),
             span("tick_sample_emit", 0.75, 0.95, LOOP),
             span("gateway_request", 0.0, 1.0, HANDLER),    # another thread
             span("engine_tick", 1.0, 2.0, LOOP)]           # the next tick
    assert ps.covered_seconds(parent, spans) == pytest.approx(0.9)
    assert ps.self_ms(parent, spans) == pytest.approx(100.0)
    assert [s["name"] for s in ps.inside(parent, spans, "decode_tick")] == [
        "decode_tick"]
    # by thread and start, not by a scan: the same spans in the list's
    # order, whatever that order is, instants left out, the list grown
    spans.append(dict(span("decode_request", 0.5, 0.5, LOOP), instant=True))
    spans.reverse()
    assert [s["name"] for s in ps.inside(parent, spans)] == [
        "tick_sample_emit", "decode_tick", "decode_paged_window",
        "tick_admit"]
    assert ps.inside(span("engine_tick", 0.0, 1.0, 12345), spans) == []


def _gap_case():
    """Device ops on the profiler's clock (host clock + 1000 s) with idle
    gaps of 1, 2, 3 and 4 s, and the loop thread's spans around them."""
    ops = [op("fusion.1", 1000.0, 1.0), op("fusion.2", 1002.0, 1.0),
           op("fusion.3", 1005.0, 1.0), op("fusion.4", 1009.0, 1.0),
           op("fusion.5", 1014.0, 1.0)]
    spans = [span("engine_tick", 0.5, 8.0, LOOP),
             span("decode_tick", 0.6, 4.5, LOOP),
             span("executor_run", 0.7, 2.0, LOOP),
             span("executor_fetch", 3.0, 4.5, LOOP),
             span("tick_sample_emit", 7.0, 7.9, LOOP),
             span("gateway_request", 0.0, 20.0, HANDLER)]
    return ops, spans


def test_gaps_are_shared_out_among_the_spans_of_the_driver_thread():
    ops, spans = _gap_case()
    ev = evidence(spans, ops=ops, to_profiler=1000.0)
    # on the host clock the first gap (1.0 to 2.0) lies in executor_run,
    # inside decode_tick; the second (3.0 to 5.0) is executor_fetch's
    # until 4.5 and then the tick's own; the third (6.0 to 9.0) is the
    # tick's but for tick_sample_emit's 0.9 s and the second after the
    # tick; the last lies outside every span of the loop thread (the
    # handler's span does not count)
    assert ps.idle_by_span(ev) == pytest.approx({
        "executor_run": 1.0, "executor_fetch": 1.5, "engine_tick": 1.6,
        "tick_sample_emit": 0.9, "host_no_span": 5.0})
    # executor_run, engine_tick and no span at all explain nothing: 7.6
    # of 10 seconds
    assert read("idle_unattributed_pct.serve", ev) == pytest.approx(76.0)
    assert read("idle_unattributed_pct.train", ev) == pytest.approx(76.0)
    assert ev.ctx.notes[0][0] == "idle_by_program_span"


def test_a_train_gap_is_shared_among_the_phases_and_the_loop_outside():
    """One gap between two steps: the tail of the fetch, the caller's own
    loop between two run calls (no program span: unattributed), prepare,
    marshal, and dispatch up to the launch."""
    runs = [s for s in train_runs() if s["start"] < 15.0]
    # the step's device work ends 10 ms before its fetch returns; the next
    # run call comes 4 ms after that, and launches 1 ms into its dispatch
    fetch_end = 10.01 + 0.05
    t = fetch_end + 0.004
    nxt = [span("executor_run", t + 0.001, t + 0.010, prepare_ms=1.0,
                plan_hit=True, phases=[
                    ["executor_marshal", t + 0.001, {}],
                    ["executor_dispatch", t + 0.003, {}],
                    ["executor_writeback", t + 0.009, {}]])]
    ops = [op("fusion.1", 1010.0, fetch_end - 0.010 - 10.0),
           op("fusion.2", 1000.0 + t + 0.004, 0.02)]
    ev = evidence(runs + nxt, ops=ops, to_profiler=1000.0)
    assert ps.idle_by_span(ev) == pytest.approx({
        "executor_fetch": 0.010, "host_no_span": 0.004,
        "executor_prepare": 0.001, "executor_marshal": 0.002,
        "executor_dispatch": 0.001})
    assert read("idle_unattributed_pct.train", ev) == pytest.approx(
        100.0 * 0.004 / 0.018)


def test_gap_attribution_needs_trace_clock_and_driver():
    ops, spans = _gap_case()
    assert ps.idle_by_span(evidence(spans)) is None
    assert ps.idle_by_span(evidence(spans, ops=ops, to_profiler=None)) is None
    no_driver = [s for s in spans if s["name"] != "executor_run"]
    ev = evidence(no_driver, ops=ops, to_profiler=1000.0)
    assert read("idle_unattributed_pct.serve", ev) is None


def test_segments_match_a_walk_over_every_span():
    _ops, spans = _gap_case()
    loop = [s for s in spans if s["tid"] == LOOP]
    line = ps.segments(loop)
    assert all(a[1] <= b[0] for a, b in zip(line, line[1:]))
    for i in range(0, 90):
        t = 0.1 * i + 0.05
        held = [s for s in loop if s["start"] <= t <= s["end"]]
        want = min(held, key=lambda s: s["end"] - s["start"]) if held else None
        got = [g[2] for g in line if g[0] <= t <= g[1]]
        assert (got[0] if got else None) is want


def test_phases_become_spans():
    """Marks on ``executor_run`` read as child spans that tile it, and
    ``prepare_ms`` as the sibling before it; a span without marks (an
    older program's ``executor_run``) stays as it is."""
    first = [s for s in ps.with_phases(train_runs()) if s["start"] < 15.0]
    assert [(s["name"], round(1e3 * (s["end"] - s["start"]), 6))
            for s in first] == [
        ("executor_run", 9.0), ("executor_marshal", 2.0),
        ("executor_dispatch", 6.0), ("executor_writeback", 1.0),
        ("executor_prepare", 1.0), ("executor_fetch", 50.0)]
    assert first[1]["args"] == {"segment": 0, "values": 100, "placed": 10}
    assert first[4]["end"] == first[0]["start"]
    old = [span("executor_run", 10.0, 10.02)]
    assert ps.with_phases(old) == old


KERNEL_TEXT = ('%%%s.7 = bf16[96,1024,64] custom-call(%%a, %%b), '
               'custom_call_target="tpu_custom_call", '
               'metadata={op_name="jit(fn)/%s/pallas_call"}')


def _flash_evidence(named):
    """One whole step of 10 s holding the three kernels for 1, 2 and 3 s
    and an ordinary fusion."""
    names = (("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv") if named
             else ("fn.55", "fn.71", "fn.72"))
    ops = [op("%fusion.0 = f32[8] fusion(%x)", 100.0, 0.5)]
    at = 101.0
    for i, n in enumerate(names):
        ops.append(op(KERNEL_TEXT % (n, n), at, 1.0 + i))
        at += 1.0 + i
    ops.append(op("%fusion.9 = f32[8] fusion(%x)", 109.5, 0.5))
    modules = [op("jit_fn(1)", 100.0, 10.0)]
    return evidence([], ops=ops, modules=modules)


@pytest.mark.parametrize("name,ms", [("flash_fwd_ms_per_step", 1000.0),
                                     ("flash_bwd_dq_ms_per_step", 2000.0),
                                     ("flash_bwd_dkv_ms_per_step", 3000.0)])
def test_kernel_readers_by_name(name, ms):
    assert read(name, _flash_evidence(True)) == pytest.approx(ms)
    # kernels without a name (%fn.55): nothing to read, and the accepted
    # pattern goes on matching all three
    unnamed = _flash_evidence(False)
    assert read(name, unnamed) is None
    assert read("flash_train_ms_per_step", unnamed) == pytest.approx(6000.0)
    assert read("flash_train_ms_per_step",
                _flash_evidence(True)) == pytest.approx(6000.0)


def test_short_shows_the_kernels_name():
    head = reduce.short(KERNEL_TEXT % ("flash_bwd_dkv", "flash_bwd_dkv"))
    assert head.startswith("flash_bwd_dkv.7 custom_call_target=")
