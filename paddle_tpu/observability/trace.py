"""Thread-safe span tracer with Chrome trace-event export.

Reference lineage: the Fluid stack's ``platform/profiler.h`` RecordEvent
+ ``platform/device_tracer.h`` timeline, whose proto ``tools/timeline.py``
converted to chrome://tracing JSON. This is the host half of that design
rebuilt as one spine: every subsystem (executor step loop, DeviceFeeder,
checkpoint snapshot + writer thread, serving batcher/pool dispatch,
pserver RPC client, legacy ``fluid.profiler.RecordEvent``) opens spans
here, and one export answers "where did this step's milliseconds go".
Device-side timelines still come from ``jax.profiler`` (xprof); the two
complement each other — this trace carries the host orchestration XLA
cannot see.

Design constraints, in order:

- **Always-on cheap**: recording is gated by ``FLAGS_obs_trace``
  (default on) behind a flags-version-cached check, and a completed span
  costs two ``perf_counter`` reads, a tuple, and one locked deque append
  (bounded: ``FLAGS_obs_trace_buffer`` newest spans survive — a
  long-lived server must not grow host memory without bound).
  ``tools/obs_probe.py`` measures the enabled-vs-disabled step-path
  overhead and gates it <2%.
- **Thread-safe with explicit nesting**: each thread keeps its own span
  stack (``threading.local``), so parent/child edges are exact even with
  the checkpoint writer, serving batcher workers, and the feeder all
  tracing concurrently. ``tid`` in the export is the OS thread ident,
  ``pid`` is the gang rank (``PADDLE_TRAINER_ID``), so a multi-rank
  job's merged traces line up side by side in Perfetto.
- **Standard format**: ``chrome_trace()`` emits trace-event JSON
  (``ph: "X"`` complete events + thread-name metadata) that loads in
  Perfetto / chrome://tracing unchanged.

- **Phases, where a record each would cost too much**: a span that is
  a sequence of steps marks where each begins (``span.phase(name)``: one
  clock read and a list append, a tenth of a span) and carries the marks
  in its one record (``args["phases"]``). ``with_phases()`` turns them
  into child spans when the buffer is READ (``chrome_trace()`` always
  does). A millisecond
  ``Executor.run`` so makes two records, not seven.
- **A thread's CPU time, where a wall time alone misleads**:
  ``span(name, cpu=True)`` also reads ``time.thread_time()`` as it opens
  and closes (inside the wall clock's two reads, so never more than the
  wall time) and records ``cpu_ms`` and ``cpu_at`` (the thread's CPU
  clock, seconds, as it opened); its phases carry a ``cpu_at`` stamp in
  their args and ``with_phases`` makes a ``cpu_ms`` of each. Two more
  clock reads a span and one a phase; none where ``cpu`` is not asked
  for, none with tracing off. The clock is a system call, and under a
  sandbox's kernel a dear and a coarse one (20 us a read, steps of
  10 ms), so of the outermost such spans of a name that a thread opens
  one in ``CPU_EVERY`` reads it, the first among them, with every
  ``cpu=True`` span and phase inside it; the others make the record a
  span without ``cpu`` makes. What a reader sums over a window is a
  sample of the ticks. ``set_cpu_every(1)`` has every one read it, for
  a measuring run (``tools/cpu_clocks.py``).

Names on the two hot paths (PERF.md section 3 lists each with the
benchmark metric that reads it). One ``Executor.run`` or
``CompiledProgram`` run: ``executor_run`` (args ``prepare_ms``: the
entry point's own work before it, and ``plan_hit``) with the phases
``executor_marshal`` / ``executor_dispatch`` per XLA segment
(``executor_host_ops`` per host segment) and ``executor_writeback``,
then the span ``executor_fetch`` (the wait for the device). One engine
tick:
``engine_tick`` holding ``tick_reap``, ``tick_admit``, ``tick_prefill``,
``tick_build``, ``decode_tick``, ``tick_sample_emit``; ``engine_wait``
while the loop has nothing to do. ``decode_tick`` is NOT the tick: it is
the fused device call of a tick (``step_feed``, ``decode_paged_step``),
annotated with the trace ids of the streams it decoded; ``step_logits``
is the copy of logits to the host (a window's row; of a step the rows of
a sampled stream, inside ``tick_sample_emit``). A request leaves one
``decode_request`` instant
(its times on this clock, and the engine's step counter at its submit
and at its dequeue) and one ``gateway_request`` span.

Every span of the engine's loop thread carries ``cpu_ms`` (on the ticks
that read the clock, one in ``CPU_EVERY``):
``engine_tick`` (with ``process_cpu_ms``: ``time.process_time()`` over
the tick, all the process's threads), ``tick_reap``,
``tick_admit``, ``tick_prefill``, ``tick_build``, ``step_feed``,
``decode_paged_step``, ``decode_paged_window``, ``tick_publish``,
``tick_sample_emit``, ``engine_wait``, and the executor's
``executor_run`` (so its phases) and ``executor_fetch``, on the training
path too. A span's wall time less its ``cpu_ms`` is time its thread
held no CPU. In ``executor_fetch`` and ``engine_wait`` that is the wait
they exist for (the device, a request). Anywhere else, on a thread that
shares the interpreter with a gateway's handler threads, it is the wait
for the interpreter lock (or, where the host is short of them, for a
core): a phase that reads 6 ms of wall and 0.4 ms of CPU did 0.4 ms of
work and queued for the rest, and is sped up by having fewer threads
ask for the lock, not by doing less in it. The handlers' own CPU is
counted once a stream, where it ends (``gateway_handler_cpu_us``,
``gateway._count_events``).
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import re
import threading
import time
from collections import deque

from ..fluid import flags as _flags

__all__ = [
    "span",
    "traced",
    "instant",
    "enabled",
    "force_enable",
    "set_cpu_every",
    "gang_rank",
    "get_spans",
    "with_phases",
    "reset",
    "chrome_trace",
    "save_chrome_trace",
    "new_trace_id",
    "parse_traceparent",
    "format_traceparent",
    "trace_scope",
    "current_context",
    "clock_anchor",
    "TRACE_SCHEMA_VERSION",
]

# /trace payload schema: bumped to 2 when the export grew the
# distributed-tracing envelope (schema_version, clock_anchor, ts_base,
# per-event trace_id/span_id/parent_span_id args) — fleet_trace.py and
# foreign consumers version-negotiate on it
TRACE_SCHEMA_VERSION = 2

# record layout (tuple for append cheapness):
# (name, cat, start_s, end_s, tid, depth, parent_name, span_id, args|None,
#  trace_id|None, span_hex|None, parent_hex|None, is_instant)
# The last four are the DISTRIBUTED identity: trace_id is the W3C
# 32-hex request id minted at the fleet's front door and carried across
# processes via `traceparent`; span_hex/parent_hex are this span's and
# its parent's 16-hex W3C span ids (chained through trace_scope + span
# nesting, so a child on another THREAD or PROCESS still names its real
# parent). All None outside a trace_scope — the always-on in-process
# tracer pays nothing for the fleet machinery.
_lock = threading.Lock()
_buf = deque(maxlen=65536)
_ids = itertools.count(1)  # .__next__ is atomic under the GIL
_tls = threading.local()
_thread_names = {}  # tid -> thread name, for trace metadata
# (flags.version(), enabled) — the disarmed/armed check must cost one
# integer compare on hot paths, same idiom as testing/chaos.py
_enabled_cache = (None, True)
# ref-count of force_enable holders (an explicit profiling session must
# record spans even when the always-on tracer is flagged off)
_force_on = 0


def enabled():
    """Is span recording armed (FLAGS_obs_trace, or a force_enable
    holder)? Cached per flags-version so per-span cost stays at one
    integer compare. The same once-per-flags-change branch applies
    FLAGS_obs_trace_buffer, so the bound takes effect on live paths
    (trainer, server) that never call reset()."""
    global _enabled_cache
    ver = _flags.version()
    cached_ver, cached = _enabled_cache
    if cached_ver != ver:
        cached = bool(_flags.get_flag("obs_trace", True))
        _enabled_cache = (ver, cached)
        _apply_buffer_bound()
    return cached or _force_on > 0


def _buffer_bound():
    try:
        return max(int(_flags.get_flag("obs_trace_buffer", 65536)), 1)
    except (TypeError, ValueError):
        return 65536


def _apply_buffer_bound():
    """Re-size the ring buffer to FLAGS_obs_trace_buffer, keeping the
    newest spans."""
    global _buf
    n = _buffer_bound()
    if _buf.maxlen != n:
        with _lock:
            _buf = deque(_buf, maxlen=n)


# A thread's CPU clock is a system call: half a microsecond on a plain
# kernel, but under a sandbox's (gVisor, where the benchmark's chips are)
# 25 us among the 140 threads of a serving process, on a clock that moves
# in steps of 10 ms (PERF.md section 6, PR 37): the 36 reads of a serve
# tick were 5 % of the tick there. So of the outermost ``cpu=True`` spans
# of a name that a thread opens, one in ``CPU_EVERY`` reads it, with every
# ``cpu=True`` span and phase inside it: a serve tick then pays about the
# four reads its two hand-read spans paid before. Counted, so the same
# ticks whatever the host; a name, so that two outermost spans taking
# turns (``executor_run``, ``executor_fetch``) are both read, in the same
# step; a prime, so that no tick in two or in four is the only kind read.
CPU_EVERY = 11
_cpu_every = CPU_EVERY


def set_cpu_every(n):
    """Read the CPU clock in one outermost ``cpu=True`` span of a name in
    ``n`` (``CPU_EVERY`` until asked otherwise): 1 for a measuring run
    that wants every tick's split and pays for it. -> the rate before."""
    global _cpu_every
    before, _cpu_every = _cpu_every, max(1, int(n))
    return before


def _cpu_sampled(name):
    """Whether the outermost ``cpu=True`` span of this name now opening
    on this thread is one that reads the CPU clock."""
    counts = getattr(_tls, "cpu_n", None)
    if counts is None:
        counts = _tls.cpu_n = {}
    n = counts.get(name, 0)
    counts[name] = n + 1
    return n % _cpu_every == 0


def force_enable(on):
    """Arm (``True``) / disarm (``False``) recording regardless of
    FLAGS_obs_trace. Ref-counted: ``fluid.profiler.start_profiler``
    holds this for the session so the legacy API keeps producing a
    timeline when the always-on tracer was turned off for overhead."""
    global _force_on
    _force_on = max(0, _force_on + (1 if on else -1))


# -- distributed trace context ----------------------------------------------
# W3C trace-context shapes: trace_id is 32 lowercase hex, span ids are
# 16. Span ids are DERIVED, not drawn from urandom per span: a random
# per-process seed XOR a Weyl-sequence hash of the process-local span
# counter is unique within the process by construction, collision-odds
# ~2^-64 across processes, and costs one multiply — span enter/exit
# stays on the <2% overhead budget even inside a scope.
_PROC_SEED = int.from_bytes(os.urandom(8), "big")
_SPAN_MASK = (1 << 64) - 1
_TRACEPARENT = re.compile(
    r"^[0-9a-f]{2}-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$"
)


def _span_hex(local_id):
    return "%016x" % (
        (_PROC_SEED ^ (local_id * 0x9E3779B97F4A7C15)) & _SPAN_MASK
    )


def new_trace_id():
    """A fresh W3C trace id (32 hex chars) — minted once per request at
    the fleet's front door (router, or a directly-fronted gateway)."""
    return os.urandom(16).hex()


def parse_traceparent(value):
    """``(trace_id, parent_span_id)`` from a W3C ``traceparent`` header,
    or None for absent/malformed values (a bad header means "mint your
    own", never an error — foreign clients send arbitrary bytes)."""
    if not value:
        return None
    m = _TRACEPARENT.match(str(value).strip().lower())
    if m is None:
        return None
    tid = m.group(1)
    if tid == "0" * 32 or m.group(2) == "0" * 16:
        return None  # the spec's all-zero ids are invalid
    return tid, m.group(2)


def format_traceparent(trace_id, span_id):
    """The ``traceparent`` header value naming ``span_id`` as the
    remote parent of whatever the receiving hop opens."""
    return "00-%s-%s-01" % (trace_id, span_id)


class trace_scope(object):
    """Thread-local ambient trace context: every span opened inside the
    scope records ``trace_id`` and chains ``parent_span_id`` from the
    nearest enclosing span (or the scope's remote parent — the
    traceparent a hop received). ``trace_id=None`` makes the scope a
    no-op, so call sites pass whatever context they captured without
    branching. Scopes nest; each thread owns its own stack."""

    __slots__ = ("_entry", "_pushed")

    def __init__(self, trace_id, parent_span_id=None):
        self._entry = (trace_id, parent_span_id) if trace_id else None
        self._pushed = False

    def __enter__(self):
        if self._entry is not None:
            stack = getattr(_tls, "ctx", None)
            if stack is None:
                stack = _tls.ctx = []
            stack.append(self._entry)
            self._pushed = True
        return self

    def __exit__(self, *exc):
        if self._pushed:
            _tls.ctx.pop()
            self._pushed = False
        return False


def current_context():
    """``(trace_id, parent_span_id)`` of the innermost ambient scope on
    THIS thread (the parent is the nearest enclosing span's id), or
    None. Capture it where a request is accepted and re-enter it via
    ``trace_scope(*ctx)`` on whatever thread later works for that
    request — that hand-off is how the batcher worker's and decode
    loop's spans join the request's tree."""
    stack = getattr(_tls, "ctx", None)
    return stack[-1] if stack else None


def clock_anchor():
    """The ``(ts, ts_mono)`` pair that lets a merger map THIS process's
    span timestamps onto a wall clock: ``ts_mono`` is sampled from the
    SAME clock spans record (``perf_counter``), so
    ``wall = ts + (span_t - ts_mono)`` exactly. Exposed by the
    exporter's ``/healthz``, the replica endpoint file, and the
    ``/trace`` payload itself — fleet_trace.py aligns per-process
    clocks against the controller's anchor."""
    return {"ts": time.time(), "ts_mono": time.perf_counter()}


class span(object):
    """Context manager recording one timed span.

    ``with span("ckpt_snapshot", cat="ckpt", step=7): ...`` — kwargs
    land in the Chrome event's ``args``. Nesting is tracked per thread:
    a span opened inside another becomes its child (``parent``/``depth``
    in the record, time containment in Perfetto). ``cpu=True`` adds the
    thread's CPU time (``cpu_ms``, ``cpu_at``; see the module's header)
    where the open span's ``cpu`` says so.
    Disabled tracing makes enter/exit a near-no-op."""

    __slots__ = ("name", "cat", "args", "_t0", "_armed", "_parent",
                 "trace_id", "span_id", "_parent_hex", "_ctx_pushed",
                 "_stack", "_phases", "_cpu", "cpu", "_c0")

    def __init__(self, name, cat="host", cpu=False, **args):
        self.name = name
        self.cat = cat
        self.args = args or None
        self._armed = False
        self._phases = None
        # asked for the thread's CPU time; ``cpu``: whether this span,
        # once open, is one that reads it (``_cpu_sampled``)
        self._cpu = cpu
        self.cpu = False
        # distributed identity, populated at __enter__ when an ambient
        # trace_scope is active on this thread (None otherwise). span_id
        # is readable the moment the span opens — a hop forwards it in
        # `traceparent` BEFORE its children exist.
        self.trace_id = None
        self.span_id = None

    def note(self, **args):
        """Add facts known only once the work is under way (how many
        values were placed, the status a handler ended with): they land
        in the record's ``args`` when the span closes."""
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)

    def phase(self, name, **args):
        """Mark that the phase ``name`` of this span begins now; it ends
        where the next one begins or the span closes. -> the phase's
        ``args`` dict, for facts known only when it is over. The marks
        travel in the span's own record; ``with_phases`` makes child
        spans of them when the buffer is read."""
        if self._armed:
            if self._phases is None:
                self._phases = []
            if self.cpu:
                # beside the mark, not in it: a mark stays three fields
                args["cpu_at"] = time.thread_time()
            self._phases.append((name, time.perf_counter(), args))
        return args

    def __enter__(self):
        if not enabled():
            return self
        try:
            stack = _tls.stack
            ctx = _tls.ctx
        except AttributeError:
            # first span of this thread (a trace_scope may have made ctx)
            stack = _tls.stack = getattr(_tls, "stack", [])
            ctx = _tls.ctx = getattr(_tls, "ctx", [])
        self._stack = stack
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        self._armed = True
        self._ctx_pushed = False
        if ctx:
            # inside a trace_scope: mint this span's W3C id, remember
            # the enclosing id as parent, and become the ambient parent
            # for anything opened (or captured) underneath
            trace_id, parent = ctx[-1]
            self.trace_id = trace_id
            self._parent_hex = parent
            self.span_id = _span_hex(next(_ids))
            ctx.append((trace_id, self.span_id))
            self._ctx_pushed = True
        if self._cpu:
            # the outermost cpu=True span of a thread decides for those
            # inside it
            held = getattr(_tls, "cpu_open", 0)
            if not held:
                _tls.cpu_stamp = _cpu_sampled(self.name)
            _tls.cpu_open = held + 1
            self.cpu = _tls.cpu_stamp
        self._t0 = time.perf_counter()
        if self.cpu:
            self._c0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        if not self._armed:
            return False
        if self.cpu:
            # read inside the wall interval, so cpu_ms never exceeds it
            c0 = self._c0
            self.note(cpu_ms=(time.thread_time() - c0) * 1e3, cpu_at=c0)
        t1 = time.perf_counter()
        if self._cpu:
            _tls.cpu_open -= 1
        self._armed = False
        stack = self._stack
        if stack:
            stack.pop()
        if self._ctx_pushed:
            _tls.ctx.pop()
            self._ctx_pushed = False
        if self._phases is not None:
            self.note(phases=self._phases)
        tid = threading.get_ident()
        rec = (
            self.name, self.cat, self._t0, t1, tid, len(stack),
            self._parent, next(_ids), self.args,
            self.trace_id, self.span_id,
            self._parent_hex if self.trace_id else None, False,
        )
        with _lock:
            if tid not in _thread_names:  # once per thread, not per span
                _thread_names[tid] = threading.current_thread().name
            _buf.append(rec)
        return False


def instant(name, cat="host", **args):
    """Record a zero-duration INSTANT event (Perfetto ``ph: "i"``) —
    the attributable mark for moments that have no extent, like the
    router's failover splice between two replicas' stream segments.
    Carries the ambient trace context like a span (so the mark lands
    inside the request's tree), costs one append, no-op when tracing
    is off."""
    if not enabled():
        return
    t = time.perf_counter()
    tid = threading.get_ident()
    ctx = getattr(_tls, "ctx", None)
    trace_id = span_hex = parent = None
    if ctx:
        trace_id, parent = ctx[-1]
        span_hex = _span_hex(next(_ids))
    stack = getattr(_tls, "stack", None)
    rec = (
        name, cat, t, t, tid, len(stack) if stack else 0,
        stack[-1] if stack else None, next(_ids), args or None,
        trace_id, span_hex, parent, True,
    )
    with _lock:
        if tid not in _thread_names:
            _thread_names[tid] = threading.current_thread().name
        _buf.append(rec)


def traced(name=None, cat="host"):
    """Decorator form: ``@traced`` / ``@traced("label", cat="serving")``
    wraps the call in a span (label defaults to the qualified name)."""
    if callable(name):  # bare @traced
        return traced(None)(name)

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with span(label, cat=cat):
                return fn(*a, **kw)

        return wrapper

    return deco


def get_spans(newest=None):
    """Snapshot of the ring buffer as dicts (oldest first); list and
    dicts are copies — same isolation contract as profiler counters.
    ``trace_id``/``span_id``/``parent_span_id`` are the distributed
    identity (None outside a trace_scope); ``instant`` marks
    zero-duration events. ``newest=`` bounds the snapshot to the newest
    N records BEFORE dict conversion — the periodic black-box dump must
    not pay a full-ring copy to keep 1/16th of it. Phases a span marked
    stay in its ``args``; ``with_phases`` makes child spans of them."""
    with _lock:
        recs = list(_buf)
    if newest is not None:
        n = int(newest)
        recs = recs[-n:] if n > 0 else []  # -0 would slice the WHOLE ring
    return [
        {
            "name": r[0], "cat": r[1], "start": r[2], "end": r[3],
            "tid": r[4], "depth": r[5], "parent": r[6], "id": r[7],
            "args": dict(r[8]) if r[8] else {},
            "trace_id": r[9], "span_id": r[10],
            "parent_span_id": r[11], "instant": r[12],
        }
        for r in recs
    ]


def with_phases(spans):
    """``spans`` (dicts as ``get_spans`` gives them) and, after each span
    that marked phases, one child span a phase: from its mark to the
    next one or to the parent's end, on the parent's thread, one level
    deeper, with the phase's own ``args``. A phase of a ``cpu=True`` span
    gets its own ``cpu_ms``, from its ``cpu_at`` stamp to the next
    phase's or to the parent's last. The children were never
    records (``id`` None); inside a trace scope each gets a span id of its
    own under its parent's, so a merged trace keeps its tree."""
    out = []
    for s in spans:
        out.append(s)
        marks = s["args"].get("phases")
        if not marks:
            continue
        ends = [m[1] for m in marks[1:]] + [s["end"]]
        own = s["args"]
        cpu_ends = [(m[2] or {}).get("cpu_at") for m in marks[1:]] + [
            own["cpu_at"] + own["cpu_ms"] / 1e3 if "cpu_at" in own else None]
        for (name, start, args), end, cpu_end in zip(marks, ends, cpu_ends):
            args = dict(args or {})
            if args.get("cpu_at") is not None and cpu_end is not None:
                args["cpu_ms"] = (cpu_end - args["cpu_at"]) * 1e3
            out.append(dict(
                s, name=name, start=start, end=end, depth=s["depth"] + 1,
                parent=s["name"], id=None, args=args,
                span_id=_span_hex(next(_ids)) if s["span_id"] else None,
                parent_span_id=s["span_id"]))
    return out


def reset():
    """Drop every retained span and re-read the buffer bound from
    FLAGS_obs_trace_buffer (so tests can shrink it)."""
    global _buf
    with _lock:
        _buf = deque(maxlen=_buffer_bound())


def gang_rank(rank=None):
    """The gang rank labeling every per-rank artifact (trace ``pid``,
    snapshot filename, exporter identity): an explicit value wins, else
    PADDLE_TRAINER_ID, else 0 (non-numeric counts as unset). One
    resolver so a change to rank discovery can't skew artifacts apart."""
    if rank is not None:
        return int(rank)
    try:
        return int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    except ValueError:
        return 0


def _span_matches(s, trace_id):
    """Does this span belong to ``trace_id``? Either its own distributed
    identity matches, or it is a shared-work span (a batched dispatch /
    fused decode tick) whose ``trace_ids`` args list names the trace."""
    if s["trace_id"] == trace_id:
        return True
    tids = s["args"].get("trace_ids")
    return isinstance(tids, (list, tuple)) and trace_id in tids


def chrome_trace(trace_id=None, newest=None):
    """The retained spans as a Chrome trace-event dict: ``ph: "X"``
    complete events (``ph: "i"`` for instants) with ``ts``/``dur`` in
    microseconds, ``pid`` = gang rank, ``tid`` = thread, nesting by
    containment (exact, because spans close LIFO per thread), plus
    process/thread-name metadata. Loads in Perfetto / chrome://tracing
    as-is. The distributed envelope rides as EXTRA top-level keys
    (Perfetto ignores them): ``schema_version``, ``clock_anchor`` (the
    wall/mono pair a merger aligns on), ``ts_base`` (the mono origin
    subtracted from every ``ts``, so absolute times reconstruct), and
    process identity; per-event ``trace_id``/``span_id``/
    ``parent_span_id`` land in ``args``. ``trace_id=`` filters to one
    request's spans (shared-work spans whose ``trace_ids`` list names
    it included); ``newest=`` keeps only the newest N spans (bounded
    periodic dumps)."""
    # the newest bound applies pre-conversion when it can (no filter);
    # with a trace_id filter it must run AFTER, on the matching spans
    spans = get_spans(newest=None if trace_id is not None else newest)
    if trace_id is not None:
        spans = [s for s in spans if _span_matches(s, trace_id)]
        if newest is not None:
            n = int(newest)
            spans = spans[-n:] if n > 0 else []
    # a phase shows as the child span it is; its parent keeps the marks
    # out of its own event's args
    spans = [
        dict(s, args={k: v for k, v in s["args"].items() if k != "phases"})
        if "phases" in s["args"] else s
        for s in with_phases(spans)
    ]
    rank = gang_rank()
    t0 = min((s["start"] for s in spans), default=0.0)
    events = [
        {
            "name": "process_name", "ph": "M", "pid": rank, "tid": 0,
            "args": {"name": "rank %d" % rank},
        }
    ]
    with _lock:  # span exits insert names concurrently
        names = list(_thread_names.items())
    # OS thread idents are pthread addresses — huge and collision-prone
    # under any modulus — so the export aliases each distinct ident to a
    # small stable row id (collision-free by construction)
    alias = {
        t: i + 1
        for i, t in enumerate(sorted(
            {t for t, _ in names} | {s["tid"] for s in spans}
        ))
    }
    for tid, tname in sorted(names):
        events.append({
            "name": "thread_name", "ph": "M", "pid": rank,
            "tid": alias[tid], "args": {"name": tname},
        })
    for s in spans:
        args = dict(s["args"])
        args["depth"] = s["depth"]
        if s["parent"]:
            args["parent"] = s["parent"]
        if s["trace_id"]:
            args["trace_id"] = s["trace_id"]
            args["span_id"] = s["span_id"]
            if s["parent_span_id"]:
                args["parent_span_id"] = s["parent_span_id"]
        ev = {
            "name": s["name"], "cat": s["cat"],
            "ts": (s["start"] - t0) * 1e6,
            "pid": rank, "tid": alias[s["tid"]], "args": args,
        }
        if s["instant"]:
            ev["ph"] = "i"
            ev["s"] = "p"  # process-scoped instant mark
        else:
            ev["ph"] = "X"
            ev["dur"] = (s["end"] - s["start"]) * 1e6
        events.append(ev)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "schema_version": TRACE_SCHEMA_VERSION,
        "clock_anchor": clock_anchor(),
        "ts_base": t0,
        "rank": rank,
        "pid_os": os.getpid(),
    }


def save_chrome_trace(path):
    """Write ``chrome_trace()`` to ``path`` (atomic tmp+rename so a
    half-written export never loads as torn JSON). Returns the path."""
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w") as f:
        json.dump(chrome_trace(), f)
    os.replace(tmp, path)
    return path
