"""The program's own spans (``paddle_tpu.observability.trace``) as the
per-layer readers take them: which lie inside the window, what a span
spent outside its children, and which span the host was in while the
first chip idled.

A span is the dict ``trace.get_spans()`` gives: ``name``, ``start`` and
``end`` on the host clock (``perf_counter``), ``tid``, ``args``,
``instant``. Where a span would cost the program a record each on a
millisecond path, it marks phases on one record instead
(``args["phases"]``: name, start, args; each ends where the next begins
or the span does), and ``executor_run`` carries the milliseconds its
entry point spent before it as ``prepare_ms``: ``with_phases`` makes the
child spans ``executor_marshal``, ``executor_dispatch``,
``executor_writeback`` and the sibling ``executor_prepare`` of them, so
the readers see spans. A program that opens no such span (an older
commit) gives every function here nothing to read, and the readers
return None.
"""

import bisect

from . import xplane
from .stats import median

# the coarse spans: a gap that only one of these explains is not explained
COARSE = ("executor_run", "engine_tick")


def with_phases(spans):
    """``spans`` and, after each that carries them, a span for every
    phase it marked and ``executor_prepare`` before an ``executor_run``
    that says how long its entry point prepared."""
    out = []
    for s in spans:
        out.append(s)
        args = s.get("args") or {}
        marks = args.get("phases") or ()
        ends = [m[1] for m in marks[1:]] + [s["end"]]
        for (name, start, own), end in zip(marks, ends):
            out.append(dict(s, name=name, start=start, end=end,
                            args=dict(own or {})))
        if args.get("prepare_ms") is not None:
            out.append(dict(
                s, name="executor_prepare", end=s["start"],
                start=s["start"] - 1e-3 * args["prepare_ms"],
                args={"plan_hit": args.get("plan_hit")}))
    return out


def in_window(ev):
    """The program's spans, phases among them, that lie wholly inside the
    window: what the traffic kind handed over (serving), else the
    tracer's ring buffer cut to ``ev.window`` (training passes none)."""
    cut = getattr(ev, "_program_spans", None)
    if cut is None:
        spans = ev.spans
        if not spans:
            try:
                from paddle_tpu.observability import trace
            except ImportError:
                return []
            spans = trace.get_spans()
        t0, t1 = ev.window
        cut = ev._program_spans = [
            s for s in with_phases(spans)
            if s["start"] >= t0 and s["end"] <= t1]
    return cut


def named(spans, name):
    return [s for s in spans if s["name"] == name and not s.get("instant")]


def instants(spans, name):
    return [s for s in spans if s["name"] == name and s.get("instant")]


def ms(span):
    return 1e3 * (span["end"] - span["start"])


# the lists ``inside`` was last asked about, each with its spans by thread
# and start: a reader asks once a tick, and a scan of all spans a tick is
# quadratic in the ticks of a window
_INDEXED = []


def _by_thread(spans):
    """{tid: (sorted starts, positions in ``spans``)} of the spans that are
    not instants, kept for the last few lists asked about."""
    for kept, size, index in _INDEXED:
        if kept is spans and size == len(spans):
            return index
    rows = {}
    for at, s in enumerate(spans):
        if not s.get("instant"):
            rows.setdefault(s["tid"], []).append((s["start"], at))
    index = {}
    for tid, pairs in rows.items():
        pairs.sort()
        index[tid] = ([p[0] for p in pairs], [p[1] for p in pairs])
    _INDEXED.append((spans, len(spans), index))
    del _INDEXED[:-4]
    return index


def inside(parent, spans, name=None):
    """The spans of ``parent``'s thread that lie within it (itself left
    out), optionally only those called ``name``, in the order of
    ``spans``."""
    starts, positions = _by_thread(spans).get(parent["tid"], ((), ()))
    lo = bisect.bisect_left(starts, parent["start"])
    hi = bisect.bisect_right(starts, parent["end"])
    out = []
    for at in sorted(positions[lo:hi]):
        s = spans[at]
        if (s is not parent and s["end"] <= parent["end"]
                and (name is None or s["name"] == name)):
            out.append(s)
    return out


def covered_seconds(parent, spans):
    """Seconds of ``parent`` during which a span inside it was open."""
    total, edge = 0.0, parent["start"]
    for s in sorted(inside(parent, spans), key=lambda s: s["start"]):
        if s["end"] > edge:
            total += s["end"] - max(s["start"], edge)
            edge = s["end"]
    return total


def self_ms(parent, spans):
    """``parent``'s duration less the time its children cover."""
    return ms(parent) - 1e3 * covered_seconds(parent, spans)


def per_parent_ms(spans, parent_name, child_name):
    """For every ``parent_name`` span, the summed milliseconds of the
    ``child_name`` spans inside it; [] when either is absent."""
    children = named(spans, child_name)
    if not children:
        return []
    return [sum(ms(c) for c in inside(p, children))
            for p in named(spans, parent_name)]


EXECUTOR_PHASES = ("executor_prepare", "executor_marshal",
                   "executor_dispatch", "executor_writeback",
                   "executor_fetch")


def _phase_medians(spans, names):
    """{name: median milliseconds} of the named spans that occur."""
    out = {}
    for name in names:
        xs = [ms(s) for s in named(spans, name)]
        if xs:
            out[name] = median(xs)
    return out


def note_executor_phases(ev):
    """An earlier line for PERF.md: the median milliseconds of each
    executor phase over the window and their sum; and over the profiled
    seconds alone (the profiler slows the host's Python), beside the
    median of the benchmark's own annotation around the whole run call
    there (``bench_step``, profiler's clock)."""
    spans = in_window(ev)
    phases = _phase_medians(spans, EXECUTOR_PHASES)
    if not phases:
        return
    facts = dict(phases, sum=sum(phases.values()))
    traced = getattr(getattr(ev, "_tracer", None), "window", None)
    calls = ev.trace.host.get("bench_step", []) if ev.trace else []
    if traced and calls:
        inside_trace = _phase_medians(
            [s for s in spans
             if s["start"] >= traced[0] and s["end"] <= traced[1]],
            EXECUTOR_PHASES)
        facts["profiled"] = dict(
            inside_trace, sum=sum(inside_trace.values()),
            run_call=median([1e3 * e.dur for e in calls]))
    ev.ctx.note("executor_phases_ms", **facts)


TICK_PHASES = ("engine_tick", "tick_reap", "tick_admit", "tick_prefill",
               "tick_build", "decode_tick", "step_feed", "executor_prepare",
               "executor_marshal", "executor_dispatch", "executor_writeback",
               "executor_fetch", "step_logits", "tick_sample_emit",
               "tick_publish", "engine_wait")


def note_tick_self(ev):
    """An earlier line: ``engine_tick``'s median self time (what its
    children leave uncovered) as a share of its duration, and the median
    milliseconds of each phase of the loop thread."""
    spans = in_window(ev)
    shares = [self_ms(t, spans) / ms(t)
              for t in named(spans, "engine_tick") if ms(t) > 0]
    if not shares:
        return
    ev.ctx.note("engine_tick_self", pct_p50=100.0 * median(shares),
                ticks=len(shares),
                phase_ms_p50=_phase_medians(spans, TICK_PHASES))


def median_arg(spans, name, fn):
    """Median over the ``name`` spans of ``fn(args)`` where it is not
    None; None when no span gives a value."""
    xs = [fn(s["args"]) for s in named(spans, name)]
    xs = [x for x in xs if x is not None]
    return median(xs) if xs else None


def driver_tids(spans):
    """The threads that drive the device: those with an ``executor_run``
    span (64 request handlers are always inside some span)."""
    return {s["tid"] for s in spans if s["name"] == "executor_run"}


def segments(spans):
    """One thread's spans (they nest: a thread closes them last in, first
    out) flattened to [(start, end, the innermost span open then)],
    sorted and not overlapping."""
    out = []
    stack, t = [], None
    for s in sorted(spans, key=lambda s: (s["start"], -s["end"])):
        while stack and stack[-1]["end"] <= s["start"]:
            top = stack.pop()
            out.append((t, top["end"], top))
            t = top["end"]
        if stack:
            out.append((t, s["start"], stack[-1]))
        stack.append(s)
        t = s["start"]
    while stack:
        top = stack.pop()
        out.append((t, top["end"], top))
        t = top["end"]
    return [g for g in out if g[1] > g[0]]


def idle_by_span(ev):
    """{span name: idle seconds} of the first chip over the profiled
    seconds: each gap between its operations is shared out, by overlap,
    among the innermost program spans that the driver thread (the one
    with most spans) had open during it; what no span covers goes to
    ``host_no_span``. A gap of 80 ms between two steps has several
    causes: none is picked for all of it. None without a trace, tied
    clocks and a driver thread."""
    planes = [p for p in ev.planes() if p.ops]
    if not planes or ev.to_profiler is None:
        return None
    spans = [s for s in in_window(ev) if not s.get("instant")]
    by_tid = {tid: [s for s in spans if s["tid"] == tid]
              for tid in driver_tids(spans)}
    if not by_tid:
        return None
    line = segments(max(by_tid.values(), key=len))
    starts = [g[0] for g in line]
    out = {}
    for a, b in xplane.gaps(planes[0].ops):
        a, b = a - ev.to_profiler, b - ev.to_profiler
        left = b - a
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(line) and line[i][0] < b:
            s0, s1, held = line[i]
            over = min(b, s1) - max(a, s0)
            if over > 0:
                out[held["name"]] = out.get(held["name"], 0.0) + over
                left -= over
            i += 1
        if left > 0:
            out["host_no_span"] = out.get("host_no_span", 0.0) + left
    return out


def idle_unattributed_pct(ev):
    """Share of the first chip's idle seconds that no program span finer
    than ``executor_run`` / ``engine_tick`` explains."""
    idle = idle_by_span(ev)
    if not idle:
        return None
    ev.ctx.note("idle_by_program_span",
                by_overlap=sorted(idle.items(), key=lambda kv: -kv[1])[:16])
    loose = sum(s for name, s in idle.items()
                if name == "host_no_span" or name in COARSE)
    return 100.0 * loose / sum(idle.values())
