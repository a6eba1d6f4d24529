"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Only ``jax.profiler.ProfileData`` is needed to read the file. A device
plane is one named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per operation run on that chip and ``XLA Modules`` one per program
(a train step, a decode tick). Host planes hold the threads, with any
``TraceAnnotation`` the benchmark wrote.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = (r"all-gather|all-reduce|reduce-scatter|collective-permute"
              r"|all-to-all")
ANCHOR = "bench_anchor"


class Event(object):
    __slots__ = ("name", "start", "dur")

    def __init__(self, name, start, dur):
        self.name, self.start, self.dur = name, start, dur

    @property
    def end(self):
        return self.start + self.dur


class DevicePlane(object):
    def __init__(self, name, ops, modules, async_ops=()):
        self.name = name
        self.ops = ops          # [Event], seconds, sorted by start
        self.modules = modules  # [Event]
        self.async_ops = list(async_ops)  # transfers that run beside ops


class Trace(object):
    """``devices``: [DevicePlane]; ``host``: {annotation name: [Event]}
    for the names asked for; times in seconds on the profiler's clock."""

    def __init__(self, devices, host):
        self.devices = devices
        self.host = host


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def _events(line):
    out = [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
           for e in line.events]
    out.sort(key=lambda e: e.start)
    return out


def load(path, host_names=(ANCHOR,)):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], {n: [] for n in host_names}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            found = {OPS_LINE: [], MODULES_LINE: [], ASYNC_LINE: []}
            for line in plane.lines:
                if line.name in found:
                    found[line.name] = _events(line)
            devices.append(DevicePlane(plane.name, found[OPS_LINE],
                                       found[MODULES_LINE],
                                       found[ASYNC_LINE]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host:
                        host[e.name].append(Event(
                            e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    devices.sort(key=lambda d: int(DEVICE_PLANE.match(d.name).group(1)))
    return Trace(devices, host)


def union_intervals(events):
    """Merged [(start, end)] of the events' intervals (events sorted)."""
    out = []
    for e in events:
        if out and e.start <= out[-1][1]:
            if e.end > out[-1][1]:
                out[-1][1] = e.end
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy_seconds(events, t0=None, t1=None):
    """Seconds inside [t0, t1] in which at least one event ran."""
    total = 0.0
    for a, b in union_intervals(events):
        if t0 is not None:
            a = max(a, t0)
        if t1 is not None:
            b = min(b, t1)
        if b > a:
            total += b - a
    return total


def busy_between(merged, starts, t0, t1):
    """``busy_seconds`` inside [t0, t1] for intervals already merged
    (``union_intervals``; ``starts`` their starts): a reader that asks once
    a step merges a plane's events once, not once a step."""
    total = 0.0
    at = max(bisect.bisect_right(starts, t0) - 1, 0)
    while at < len(merged) and merged[at][0] < t1:
        a, b = max(merged[at][0], t0), min(merged[at][1], t1)
        if b > a:
            total += b - a
        at += 1
    return total


def span_of(events):
    """(first start, last end) of a sorted, non-empty list of events."""
    return events[0].start, max(e.end for e in events)


def gaps(events):
    """Idle [(start, end)] between the merged intervals of the events."""
    merged = union_intervals(events)
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:])]


def totals_by_name(events):
    out = {}
    for e in events:
        out[e.name] = out.get(e.name, 0.0) + e.dur
    return out


def matching(events, pattern):
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def whole_modules(plane):
    """The plane's module events that lie wholly inside the span its op
    events cover (a trace starts and stops mid-program)."""
    if not plane.ops or not plane.modules:
        return []
    t0, t1 = span_of(plane.ops)
    return [m for m in plane.modules if m.start >= t0 and m.end <= t1]


def modules_running(plane, pattern):
    """The plane's whole modules inside which an op named by ``pattern``
    starts: one pass over the ops, not one a module."""
    starts = [e.start for e in matching(plane.ops, pattern)]
    out = []
    for m in whole_modules(plane):
        at = bisect.bisect_left(starts, m.start)
        if at < len(starts) and starts[at] <= m.end:
            out.append(m)
    return out


def ops_inside(plane, modules):
    """Op events of ``plane`` that run inside any of ``modules``."""
    out, i = [], 0
    mods = sorted(modules, key=lambda m: m.start)
    for e in plane.ops:
        while i < len(mods) and mods[i].end < e.start:
            i += 1
        if i < len(mods) and mods[i].start <= e.start <= mods[i].end:
            out.append(e)
    return out


def measure(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, cover):
    """The parts of merged ``intervals`` that merged ``cover`` leaves."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, at = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > at:
                out.append((at, cover[k][0]))
            at = max(at, cover[k][1])
            k += 1
        if at < b:
            out.append((at, b))
    return out


def collectives(plane, modules):
    """(merged intervals of the collective operations, merged intervals of
    every other operation) inside ``modules`` on ``plane``."""
    inside = ops_inside(plane, modules)
    coll = matching(inside, COLLECTIVE)
    names = {id(e) for e in coll}
    other = [e for e in inside if id(e) not in names]
    extra = DevicePlane(plane.name, sorted(
        matching(plane.async_ops, COLLECTIVE), key=lambda e: e.start), [])
    coll = sorted(coll + ops_inside(extra, modules), key=lambda e: e.start)
    return union_intervals(coll), union_intervals(other)
