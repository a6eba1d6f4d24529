"""What the engine's loop thread did inside its spans: held a CPU, or
waited for one (on a thread that shares an interpreter: for the
interpreter lock, behind the handlers' threads; the benchmark's clients
run in a process of their own, ``harness/loadgen.py``, and contend for a
core at most), or waited for the device.

A span the program opened with ``cpu=True`` carries ``cpu_ms`` (the
thread's CPU time between its two ends, ``time.thread_time``) and
``cpu_at`` (the thread's CPU clock, seconds, when it opened); a phase of
such a span carries ``cpu_at`` alone, and its CPU time ends where the
next phase's begins or its parent's does. ``program_spans.with_phases``
hands the stamps through; ``in_window`` here turns them into a ``cpu_ms``
a phase. Wall less CPU is waiting: inside ``executor_fetch`` for the
device, anywhere else for the lock or a core.

Two things a reader has to live with (PERF.md section 6, PR 37). The
clock may be coarse: under a sandbox's kernel it moves in steps of 10 ms,
so one span's ``cpu_ms`` is 0 or 10 whatever it did, and only sums over
many spans say anything (a step lands in a span as often as the thread
is on a CPU in it). And it is dear there, so the program reads it in one
tick of several: only some spans of a name are stamped. So nothing here
takes a median of CPU times or of differences: a share is the stamped
spans' summed CPU over their summed wall time, by span name (``Share``),
as it comes out: a sum of few steps scatters, past 1 as well as under
it, and is neither cut to [0, 1] nor reported: a reader whose figure
rests on a share returns None where the CPU sum behind it holds fewer
than ``MIN_CLOCK_STEPS`` steps of the clock (``enough``). A figure in
milliseconds is that share of the median wall time of all the name's
spans. A program without the stamps (an older commit) gives every
function here nothing to read, and the readers return None.
"""

import bisect

from . import program_spans as ps
from . import xplane
from .stats import median

FETCH = "executor_fetch"
# a sum of n steps of the clock is good to about 1 in sqrt(n): under
# this many, one in eight, a share says more of the clock than of the
# span. (A window of the host-bound serve cell holds 131 +- 11 at the
# program's rate, PERF.md section 6, PR 37: a hundred would leave a run
# in three hundred without the metric in a cell that lists it.)
MIN_CLOCK_STEPS = 64
# where a prompt's windows run: what lies inside them is a window's, not
# the step's
WINDOW_PHASES = ("tick_admit", "tick_prefill")


def in_window(ev):
    """``program_spans.in_window`` with a ``cpu_ms`` on every phase whose
    parent, also in the window, read its thread's CPU clock."""
    cut = getattr(ev, "_cpu_spans", None)
    if cut is not None:
        return cut
    spans = ps.in_window(ev)
    found = {}
    for s in spans:
        args = s.get("args") or {}
        marks = args.get("phases") or ()
        if not marks or args.get("cpu_at") is None:
            continue
        stamps = [(m[2] or {}).get("cpu_at") for m in marks]
        stamps.append(args["cpu_at"] + 1e-3 * args["cpu_ms"])
        for (name, start, _own), a, b in zip(marks, stamps, stamps[1:]):
            if a is not None and b is not None:
                found[(s["tid"], name, start)] = 1e3 * (b - a)
    cut = []
    for s in spans:
        key = (s["tid"], s["name"], s["start"])
        if key in found and "phases" not in s["args"]:
            s = dict(s, args=dict(s["args"], cpu_ms=found[key]))
        cut.append(s)
    ev._cpu_spans = cut
    return cut


def cpu_ms(span):
    """The span's thread CPU milliseconds, or None where it read none."""
    return (span.get("args") or {}).get("cpu_ms")


class Share(object):
    """The wall milliseconds of some spans, and of those among them that
    read their CPU clock the summed wall and CPU milliseconds."""

    __slots__ = ("walls", "stamped", "wall_ms", "cpu_ms")

    def __init__(self):
        self.walls, self.stamped = [], 0
        self.wall_ms, self.cpu_ms = 0.0, 0.0

    def add(self, wall_ms, cpu_ms):
        self.walls.append(wall_ms)
        if cpu_ms is not None:
            self.stamped += 1
            self.wall_ms += wall_ms
            self.cpu_ms += cpu_ms

    @property
    def cpu_share(self):
        """Summed CPU over summed wall, as it comes out: a coarse clock's
        steps scatter, so it may pass 1."""
        return self.cpu_ms / self.wall_ms if self.wall_ms > 0 else 0.0

    @property
    def wait_share(self):
        return 1.0 - self.cpu_share

    def facts(self):
        """``wall``: the median of all the spans; ``cpu`` and ``wait``:
        the stamped spans' shares of it; ``cpu_sum`` says how many steps
        of the clock the shares rest on."""
        wall = median(self.walls)
        return {"wall": wall, "cpu": wall * self.cpu_share,
                "wait": wall * self.wait_share, "cpu_share": self.cpu_share,
                "spans": len(self.walls), "stamped": self.stamped,
                "wall_sum": self.wall_ms, "cpu_sum": self.cpu_ms}


def enough(cpu_sum_ms, step_ms):
    """Whether a CPU sum holds ``MIN_CLOCK_STEPS`` steps of a clock that
    moves by ``step_ms``."""
    return bool(step_ms) and cpu_sum_ms >= MIN_CLOCK_STEPS * step_ms


def share_of(spans):
    out = Share()
    for s in spans:
        out.add(ps.ms(s), cpu_ms(s))
    return out


def loop_spans(ev):
    """The engine's loop thread (the thread with the most ``engine_tick``
    spans that read their CPU clock) inside the window: its spans of the
    names that read it anywhere, stamped or not, sorted as they nest; []
    where no tick did."""
    spans = [s for s in in_window(ev) if not s.get("instant")]
    ticks, names = {}, set()
    for s in spans:
        if cpu_ms(s) is None:
            continue
        names.add((s["tid"], s["name"]))
        if s["name"] == "engine_tick" and s["args"].get("cpu_at") is not None:
            ticks[s["tid"]] = ticks.get(s["tid"], 0) + 1
    if not ticks:
        return []
    loop = max(ticks, key=ticks.get)
    return sorted((s for s in spans
                   if s["tid"] == loop and (loop, s["name"]) in names),
                  key=lambda s: (s["start"], -s["end"]))


class Node(object):
    """A span of the loop thread with what it spent outside the spans
    within it: ``self_ms`` of wall and, where it read its CPU clock (then
    those within did too), ``self_cpu_ms`` of CPU, else None; ``label``
    is its name, with ``@window`` where it lies inside an admission or a
    prefill phase; ``tick`` the ``engine_tick`` node around it, or None."""

    __slots__ = ("span", "label", "tick", "self_ms", "self_cpu_ms")

    def __init__(self, span, parent):
        self.span = span
        self.self_ms = ps.ms(span)
        self.self_cpu_ms = cpu_ms(span)
        self.label = span["name"]
        self.tick = None
        if parent is not None:
            parent.self_ms -= self.self_ms
            if self.self_cpu_ms is not None and (
                    parent.self_cpu_ms is not None):
                parent.self_cpu_ms -= self.self_cpu_ms
            self.tick = (parent if parent.span["name"] == "engine_tick"
                         else parent.tick)
            if (parent.span["name"] in WINDOW_PHASES
                    or parent.label.endswith("@window")):
                self.label += "@window"

    @property
    def in_fetch(self):
        return self.span["name"] == FETCH


def nest(spans):
    """One thread's spans (sorted as they nest) -> a Node each, in the
    same order."""
    out, stack = [], []
    for s in spans:
        while stack and stack[-1].span["end"] <= s["start"]:
            stack.pop()
        node = Node(s, stack[-1] if stack else None)
        out.append(node)
        stack.append(node)
    return out


def loop_nodes(ev):
    """``nest(loop_spans(ev))``, made once a run."""
    nodes = getattr(ev, "_loop_nodes", None)
    if nodes is None:
        nodes = ev._loop_nodes = nest(loop_spans(ev))
    return nodes


def own_shares(nodes):
    """{label: Share} of what each node spent outside the spans within
    it."""
    out = {}
    for n in nodes:
        out.setdefault(n.label, Share()).add(n.self_ms, n.self_cpu_ms)
    return out


def clock_step_ms(ev):
    """The smallest CPU time any span of the loop thread reads above 0
    (above a nanosecond: a phase's is a difference of floats): the
    clock's step where it is coarse (10.0), next to nothing where it is
    not; None where every one reads 0."""
    steps = [c for c in (cpu_ms(n.span) for n in loop_nodes(ev))
             if c and c > 1e-6]
    return min(steps) if steps else None


def tick_host(ev):
    """The Share of the window's ticks' host time: a tick less the
    ``executor_fetch`` spans inside it, on the wall clock and (the ticks
    that read it) on the thread's CPU clock; None without a stamp, or
    with too few steps of the clock in the stamped ticks to say."""
    nodes = loop_nodes(ev)
    fetch_ms, fetch_cpu = {}, {}
    for n in nodes:
        if n.in_fetch and n.tick is not None:
            key = id(n.tick)
            fetch_ms[key] = fetch_ms.get(key, 0.0) + ps.ms(n.span)
            fetch_cpu[key] = fetch_cpu.get(key, 0.0) + (cpu_ms(n.span) or 0.0)
    host = Share()
    for n in nodes:
        if n.span["name"] == "engine_tick":
            cpu = cpu_ms(n.span)
            host.add(ps.ms(n.span) - fetch_ms.get(id(n), 0.0),
                     None if cpu is None else cpu - fetch_cpu.get(id(n), 0.0))
    return host if enough(host.cpu_ms, clock_step_ms(ev)) else None


def note_wait_by_phase(ev):
    """An earlier line, ``tick_wait_by_phase``: for each span name of the
    loop thread that reads its CPU clock (a window's apart, ``@window``)
    the median wall milliseconds and, by the CPU share of the name's
    stamped spans, how much of it is CPU and how much waiting;
    ``self_ms_a_tick``: the stamped ticks' time tiled by the innermost
    such span, the mean milliseconds a tick of wall and of CPU (they add
    up to the mean stamped tick); and the CPU clock's step."""
    nodes = loop_nodes(ev)
    by = {}
    for n in nodes:
        by.setdefault(n.label, Share()).add(ps.ms(n.span), cpu_ms(n.span))
    ticks = by["engine_tick"].stamped if "engine_tick" in by else 0
    if not ticks:
        return
    tiled = own_shares(n for n in nodes if n.self_cpu_ms is not None and (
        n.tick is not None or n.span["name"] == "engine_tick"))
    ev.ctx.note(
        "tick_wait_by_phase", ticks=len(by["engine_tick"].walls),
        stamped_ticks=ticks,
        cpu_clock_step_ms=clock_step_ms(ev),
        phase_ms_p50={k: v.facts() for k, v in sorted(by.items())},
        self_ms_a_tick={k: {"wall": v.wall_ms / ticks,
                            "cpu": v.cpu_ms / ticks}
                        for k, v in sorted(tiled.items())})


def step_fetches(ev):
    """[(the ``decode_paged_step`` span, the ``executor_fetch`` inside
    it)] of the loop thread."""
    out, step = [], None
    for s in (n.span for n in loop_nodes(ev)):
        if s["name"] == "decode_paged_step":
            step = s
        elif (s["name"] == FETCH and step is not None
              and s["end"] <= step["end"]):
            out.append((step, s))
    return out


def fetch_past_device_ms(ev):
    """For each T = 1 step of the profiled seconds, how long after the
    step's program had ended on the first chip its fetch returned
    (counted from the fetch's own start where the program had ended
    before it): [(ms past the device, the fetch span)]. The program a
    fetch waited for is the last whole module that began after its
    ``decode_paged_step`` opened and ended before the fetch did: the
    loop thread alone drives the device, so no other program begins
    inside that span. None without a trace, tied clocks or whole
    modules."""
    planes = ev.planes()
    if not planes or ev.to_profiler is None:
        return None
    mods = sorted(xplane.whole_modules(planes[0]), key=lambda m: m.start)
    if not mods:
        return None
    starts = [m.start for m in mods]
    out = []
    for step, fetch in step_fetches(ev):
        opened = step["start"] + ev.to_profiler
        back = fetch["end"] + ev.to_profiler
        i = bisect.bisect_left(starts, opened)
        ran = [m for m in mods[i:bisect.bisect_right(starts, back)]
               if m.end <= back]
        if ran:
            ready = max(ran[-1].end, fetch["start"] + ev.to_profiler)
            out.append((1e3 * (back - ready), fetch))
    return out


def idle_by_what_the_host_did(ev):
    """The first chip's idle seconds over the profiled seconds, each gap
    shared out by overlap among the loop thread's innermost spans
    (``program_spans.segments``, as ``idle_by_span`` does), and each
    share split by what the stamped spans of that name did with their
    own time over the window (``own_shares``):
    {"working", "lock_waiting", "in_fetch", "no_span": seconds (the last:
    what no span covers, or one whose name read no clock in the window),
    "top": {kind: [(span label, seconds)]}}: a label's share rests on
    few steps of the clock and may pass 1, so its two parts may have
    either sign; the sums rest on all of them. None without a trace, tied
    clocks or the stamps, or where the stamped spans outside a fetch hold
    too few steps of the clock between them to say."""
    planes = [p for p in ev.planes() if p.ops]
    if not planes or ev.to_profiler is None:
        return None
    nodes = {id(n.span): n for n in loop_nodes(ev)}
    held_ms = sum(n.self_cpu_ms or 0.0 for n in nodes.values()
                  if not n.in_fetch)
    if not enough(held_ms, clock_step_ms(ev)):
        return None
    waits = {k: v.wait_share for k, v in own_shares(nodes.values()).items()
             if v.stamped}
    line = ps.segments([n.span for n in nodes.values()])
    starts = [g[0] for g in line]
    sums = {"working": 0.0, "lock_waiting": 0.0, "in_fetch": 0.0,
            "no_span": 0.0}
    top = {k: {} for k in ("working", "lock_waiting", "in_fetch")}

    def add(kind, label, seconds):
        sums[kind] += seconds
        top[kind][label] = top[kind].get(label, 0.0) + seconds

    for a, b in xplane.gaps(planes[0].ops):
        a, b = a - ev.to_profiler, b - ev.to_profiler
        left = b - a
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(line) and line[i][0] < b:
            s0, s1, held = line[i]
            over = min(b, s1) - max(a, s0)
            node = nodes[id(held)]
            if over > 0 and (node.in_fetch or node.label in waits):
                if node.in_fetch:
                    add("in_fetch", node.label, over)
                else:
                    wait = waits[node.label]
                    add("lock_waiting", node.label, over * wait)
                    add("working", node.label, over * (1.0 - wait))
                left -= over
            i += 1
        if left > 0:
            sums["no_span"] += left
    sums["top"] = {k: sorted(v.items(), key=lambda kv: -kv[1])[:6]
                   for k, v in top.items()}
    return sums


def tick_cpu_sums(ev):
    """(the window's ticks, those of them that say their own and the
    process's CPU milliseconds, the process's summed over those, the loop
    thread's); None where no tick says both."""
    ticks = [s["args"] for s in ps.named(in_window(ev), "engine_tick")]
    both = [a for a in ticks if a.get("process_cpu_ms") is not None
            and a.get("cpu_ms") is not None]
    if not both:
        return None
    return (len(ticks), len(both), sum(a["process_cpu_ms"] for a in both),
            sum(a["cpu_ms"] for a in both))
