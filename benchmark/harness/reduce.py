"""From what a traced run left (profiler trace, the program's spans and
counters, the client's records) to the per-layer metrics.

Each metric is a reader of its own, ``layer_metrics/<name>.py`` with
``read(evidence) -> number or None``. A reader that finds nothing to read
returns None and the metric is left out of the line; it never returns 0
for a share of a roofline or of a peak.
"""

import time

from . import xplane


class Evidence(object):
    """Everything a reader may look at.

    ``trace``       xplane.Trace of the profiled seconds, or None
    ``spans``       the program's spans inside the window (dicts; host
                    clock), ``counters`` their deltas over the window
    ``requests``    the client's records (serving)
    ``facts``       what the traffic kind measured and counted
    ``peaks``       the device's row of the peaks table
    ``to_profiler`` seconds to add to a host-clock time to put it on the
                    profiler's clock, or None
    """

    def __init__(self, ctx, facts, peaks):
        self.ctx, self.facts, self.peaks = ctx, facts, peaks
        self.config, self.traffic = ctx.config, ctx.traffic
        self.chips = ctx.cell.chips
        self.window = facts["window"]
        self.spans = facts.get("spans", [])
        self.counters = facts.get("counters", {})
        self.requests = facts.get("requests", [])
        self.trace, self.to_profiler = None, None
        self._tracer = facts.get("tracer")
        if self._tracer is not None and self._tracer.trace_dir:
            self.trace = xplane.load(xplane.find_xplane(
                self._tracer.trace_dir), host_names=(
                    xplane.ANCHOR, "bench_feed", "bench_step"))
            anchors = self.trace.host.get(xplane.ANCHOR)
            if anchors:
                self.to_profiler = anchors[0].start - self._tracer.anchor

    # -- the profiled seconds ------------------------------------------------
    def planes(self):
        return self.trace.devices[:self.chips] if self.trace else []

    def steps(self, plane=None):
        """Whole step programs inside the trace on ``plane`` (default: the
        first chip): of the programs that ran there, the kind that took
        most time (the train step; in serving the T = 1 step, not the
        prefill windows)."""
        planes = self.planes()
        if not planes:
            return []
        plane = plane or planes[0]
        mods = xplane.whole_modules(plane)
        if not mods:
            return mods
        by_name = xplane.totals_by_name(mods)
        top = max(by_name, key=by_name.get)
        return [m for m in mods if m.name == top]

    def step_ops(self, pattern=None, plane=None):
        """Op events inside the whole steps (optionally by name)."""
        planes = self.planes()
        if not planes:
            return []
        plane = plane or planes[0]
        ops = xplane.ops_inside(plane, self.steps(plane))
        return xplane.matching(ops, pattern) if pattern else ops

    def kernel_seconds_per_step(self, pattern):
        """Summed time of the op events named by ``pattern`` inside the
        whole steps on the first chip, per step; None without either."""
        steps = self.steps()
        ops = self.step_ops(pattern)
        if not steps or not ops:
            return None
        return sum(e.dur for e in ops) / len(steps)

    def device_times(self):
        """busy_s and window_s for the result's ``device``: seconds in
        which an operation ran, averaged over the chips, and the traced
        window's length on the device's own clock."""
        planes = [p for p in self.planes() if p.ops]
        if not planes:
            return {}
        t0 = min(xplane.span_of(p.ops)[0] for p in planes)
        t1 = max(xplane.span_of(p.ops)[1] for p in planes)
        busy = sum(xplane.busy_seconds(p.ops) for p in planes) / len(planes)
        return {"busy_s": busy, "window_s": t1 - t0}

    def host_intervals(self):
        """[(name, start, end)] on the profiler's clock: the benchmark's
        own annotations, and the program's spans where the clocks could
        be tied."""
        out = []
        if self.trace:
            for name, events in self.trace.host.items():
                if name != xplane.ANCHOR:
                    out += [(name, e.start, e.end) for e in events]
        if self.to_profiler is not None:
            # only the threads that drive the device say what the host
            # was doing while it idled (64 request handlers are always
            # inside some span)
            drivers = {s["tid"] for s in self.spans
                       if s["name"] == "executor_run"}
            out += [(s["name"], s["start"] + self.to_profiler,
                     s["end"] + self.to_profiler) for s in self.spans
                    if s["tid"] in drivers]
        return out

    def breakdown(self):
        planes = [p for p in self.planes() if p.ops]
        if not planes:
            return None
        plane = planes[0]
        tops = sorted(xplane.totals_by_name(plane.ops).items(),
                      key=lambda kv: -kv[1])[:10]
        gaps = xplane.gaps(plane.ops)
        names = attribute_all([0.5 * (a + b) for a, b in gaps],
                              self.host_intervals())
        idle = {}
        for (a, b), name in zip(gaps, names):
            idle[name] = idle.get(name, 0.0) + (b - a)
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[short(n), s] for n, s in tops],
                "idle_gaps": [[n, s] for n, s in gaps]}

    def shape(self):
        """What the trace holds, for an earlier line: per device plane the
        events of its two lines and the names that took most time."""
        out = {}
        for p in self.planes():
            tops = sorted(xplane.totals_by_name(p.ops).items(),
                          key=lambda kv: -kv[1])[:6]
            mods = sorted(xplane.totals_by_name(p.modules).items(),
                          key=lambda kv: -kv[1])[:4]
            calls = sorted(xplane.totals_by_name(xplane.matching(
                p.ops, "custom.call")).items(), key=lambda kv: -kv[1])[:4]
            out[p.name] = {"ops": len(p.ops), "modules": len(p.modules),
                           "top_ops": [[short(n), s] for n, s in tops],
                           "top_modules": mods,
                           "custom_calls": [[n[:700], s] for n, s in calls],
                           "whole_steps": len(self.steps(p))}
        return {"planes": out, "clock_tied": self.to_profiler is not None}

    def discard(self):
        if self._tracer is not None:
            self._tracer.discard()


def short(name, width=96):
    """An op event's name is its whole HLO text: keep the instruction's
    name and, for a custom call, what it calls."""
    head = name.split(" = ")[0].lstrip("%")
    for key in ("kernel_name=", "custom_call_target="):
        at = name.find(key)
        if at >= 0:
            head += " " + name[at:at + 60].split(",")[0].split("}")[0]
            break
    return head[:width]


def attribute_all(times, intervals):
    """For each of ``times``, which ascend, the name of the shortest
    interval (name, start, end) that holds it, ``host_no_span`` where none
    does: one sweep over the intervals, of which a traced serve window
    holds tens of thousands, as it does of idle gaps."""
    order = sorted(intervals, key=lambda iv: iv[1])
    out, open_now, at = [], [], 0
    for t in times:
        while at < len(order) and order[at][1] <= t:
            open_now.append(order[at])
            at += 1
        open_now = [iv for iv in open_now if iv[2] >= t]
        best = min(open_now, key=lambda iv: iv[2] - iv[1], default=None)
        out.append(best[0] if best else "host_no_span")
    return out


def read_layer_metrics(cell, evidence, log):
    """{metric: value or None} of the cell's per-layer metrics, and an
    earlier line with the seconds each reader that took over a second
    needed: a traced run has 360 s for everything."""
    values, took = {}, {}
    for metric in cell.per_layer:
        reader = cell.module("layer_metrics", metric["name"])
        t = time.perf_counter()
        value = reader.read(evidence)
        took[metric["name"]] = time.perf_counter() - t
        if value is None:
            log("layer_metric_absent", name=metric["name"])
        values[metric["name"]] = value
    log("layer_metric_seconds", total=sum(took.values()),
        over_a_second={k: v for k, v in took.items() if v >= 1.0})
    return values
