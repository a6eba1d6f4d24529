"""The trace reduction against a small recorded trace
(``data/small.xplane.pb``: five steps of a two-op jitted program on one
TPU v5 lite chip, recorded by ``record_fixture.py``), and against
hand-made events."""

import os

import pytest

from benchmark.harness import reduce, xplane
from benchmark.harness.xplane import Event

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "small.xplane.pb")


def test_union_busy_and_gaps_hand_made():
    ev = [Event("a", 0.0, 1.0), Event("b", 0.5, 1.0), Event("c", 3.0, 1.0)]
    assert xplane.union_intervals(ev) == [(0.0, 1.5), (3.0, 4.0)]
    assert xplane.busy_seconds(ev) == pytest.approx(2.5)
    assert xplane.busy_seconds(ev, 1.0, 3.5) == pytest.approx(1.0)
    merged = xplane.union_intervals(ev)
    starts = [a for a, _b in merged]
    for t0, t1 in ((1.0, 3.5), (-1.0, 9.0), (0.0, 0.25), (2.0, 2.0),
                   (8.0, 9.0), (0.75, 2.25)):
        assert xplane.busy_between(merged, starts, t0, t1) == pytest.approx(
            xplane.busy_seconds(ev, t0, t1))
    assert xplane.gaps(ev) == [(1.5, 3.0)]
    assert xplane.totals_by_name(ev) == {"a": 1.0, "b": 1.0, "c": 1.0}


def test_whole_modules_and_ops_inside():
    ops = [Event("x", 1.0, 0.2), Event("y", 2.1, 0.3), Event("x", 3.0, 0.1)]
    mods = [Event("m", 0.5, 1.0), Event("m", 2.0, 0.5), Event("m", 2.9, 1.0)]
    plane = xplane.DevicePlane("/device:TPU:0", ops, mods)
    whole = xplane.whole_modules(plane)
    assert [m.start for m in whole] == [2.0]   # the others are cut
    assert [e.name for e in xplane.ops_inside(plane, whole)] == ["y"]
    # the whole modules that run an op of a name: y starts in the second
    assert xplane.modules_running(plane, "^y$") == whole
    assert xplane.modules_running(plane, "^nothing$") == []
    assert xplane.modules_running(plane, "^x$") == [
        m for m in whole
        if xplane.matching(xplane.ops_inside(plane, [m]), "^x$")]


def test_attribute_takes_the_shortest_enclosing_span():
    spans = [("tick", 0.0, 10.0), ("step", 2.0, 4.0), ("next", 10.0, 12.0)]
    times = [0.5, 2.0, 3.0, 4.0, 4.5, 9.9, 10.0, 11.0, 12.5]
    assert reduce.attribute_all(times, spans) == [
        "tick", "step", "step", "step", "tick", "tick", "next", "next",
        "host_no_span"]
    assert reduce.attribute_all([], spans) == []
    assert reduce.attribute_all([3.0], []) == ["host_no_span"]


@pytest.mark.skipif(not os.path.isfile(FIXTURE), reason="no recorded trace")
def test_recorded_trace():
    trace = xplane.load(FIXTURE, host_names=("bench_anchor", "bench_step"))
    assert len(trace.devices) == 1
    plane = trace.devices[0]
    assert plane.name == "/device:TPU:0"
    # five steps of one program were recorded; a module's event reaches a
    # little past its first and last op, so the two at the trace's edges
    # do not count as whole (in a mid-window trace they are cut anyway)
    assert len(plane.modules) == 5
    steps = xplane.whole_modules(plane)
    assert len(steps) == 3
    assert len(trace.host["bench_step"]) == 5
    assert len(trace.host["bench_anchor"]) == 1
    ops = xplane.ops_inside(plane, steps)
    assert len(ops) >= 3
    busy = xplane.busy_seconds(plane.ops)
    t0, t1 = xplane.span_of(plane.ops)
    assert 0 < busy < t1 - t0
    # the steps were 2 ms apart on the host: idle gaps of that order
    longest = max(b - a for a, b in xplane.gaps(plane.ops))
    assert 1e-3 < longest < 0.1
    # every step's ops lie inside its module
    for m in steps:
        inside = [e for e in ops if m.start <= e.start <= m.end]
        assert inside and max(e.end for e in inside) <= m.end + 1e-6


def test_subtract_and_collectives():
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert xplane.subtract([(0, 2)], []) == [(0, 2)]
    ops = [Event("%fusion.1 = f32[] fusion()", 0.0, 1.0),
           Event("%all-gather.3 = f32[] all-gather()", 0.5, 1.0),
           Event("%fusion.2 = f32[] fusion()", 2.0, 1.0)]
    plane = xplane.DevicePlane("/device:TPU:0", ops, [Event("m", -0.1, 4.0)],
                               [Event("%reduce-scatter.1", 2.5, 1.0)])
    coll, other = xplane.collectives(plane, plane.modules)
    assert coll == [(0.5, 1.5), (2.5, 3.5)]
    assert other == [(0.0, 1.0), (2.0, 3.0)]
    exposed = xplane.subtract(coll, other)
    assert exposed == [(1.0, 1.5), (3.0, 3.5)]
    assert xplane.measure(exposed) == pytest.approx(1.0)
