"""Device-plane telemetry: compile spans, recompile sentinel, cost census.

PR 5's tracer/registry see every host-side layer; everything below
``jax.jit`` was a black box — compiles, per-executable FLOP/HBM-byte
costs, device memory — visible only through one-off ``tools/hlo_scan.py``
runs. This module is the device-plane counterpart of ``trace.py`` /
``registry.py``: the executor's lower-and-compile path reports here, and
three always-on signals come out:

- **Compile telemetry**: every ``_CompiledBlock`` build and every XLA
  executable compile emits a span plus a structured record — program
  cache key, wall ms, and a trigger classification (``cold`` /
  ``shape_change`` / ``program_mutation`` / ``feed_order_change`` /
  ``lru_eviction`` / ``uncached_rebuild``). The **recompile sentinel**
  diffs the new cache key against the nearest prior key of the same
  program, so a record says *which component changed* (version, feed
  set/order, fetch list, a feed's shape), not just "it recompiled".
- **Cost census**: the executor compiles ahead-of-time per feed-shape
  signature, so the compiled executable is in hand at record time and
  XLA cost analysis + the optimized-HLO op census are FREE (no second
  compile). Per-program-key gauges (``xla_flops_<key>``,
  ``xla_bytes_accessed_<key>``, ``xla_out_bytes_<key>``) publish through
  the registry; live/peak device-memory gauges register where the
  backend exposes ``memory_stats()`` (TPU/GPU — the CPU backend
  doesn't). ``tools/hlo_scan.py`` shares the census functions below, so
  the one-off scan and the always-on plane can never disagree.
- **Strict serving gate**: ``serving.InferenceServer`` arms the gate
  (``arm_serving_steady()``, counted per live server) once warmup
  finished; an executable compile on a serving-request thread (inside a
  ``serving_request_window()``, as the dispatch workers are) and outside
  a ``warmup_window()`` then bumps ``serving_steady_recompiles`` and —
  under ``FLAGS_serving_strict_compiles`` — raises
  ``SteadyStateRecompileError`` with the attribution attached, turning
  the "0 recompiles after warmup" claim into an enforced invariant. A
  colocated trainer's compiles never touch the gate.

Everything is bounded (``FLAGS_obs_compile_records`` records, capped
key history and census map) and lock-guarded; the steady-state step path
touches none of it.
"""

from __future__ import annotations

import collections
import itertools
import os
import re
import threading
import time
import weakref
import zlib
from collections import OrderedDict, deque

from ..fluid import flags as _flags
from ..fluid import profiler as _profiler

__all__ = [
    "INTERESTING_OPS",
    "SteadyStateRecompileError",
    "op_census",
    "interesting_ops",
    "cost_summary",
    "executable_census",
    "program_label",
    "make_key",
    "fingerprint",
    "key_slug",
    "on_build",
    "on_dispatch_rebind",
    "on_xla_compile",
    "note_eviction",
    "serving_steady",
    "arm_serving_steady",
    "disarm_serving_steady",
    "serving_request_window",
    "warmup_window",
    "get_records",
    "summary",
    "compiles_endpoint",
    "census_by_key",
    "headline_census",
    "attach_headline_census",
    "reset",
]


# ---------------------------------------------------------------------------
# Shared HLO census library (extracted from tools/hlo_scan.py — the scan
# now imports THESE, so scan output and the always-on census share one
# implementation)
# ---------------------------------------------------------------------------

# the op families PERF.md's fusion-hygiene methodology watches
INTERESTING_OPS = (
    "transpose", "convert", "copy", "fusion", "dot", "convolution",
    "all-reduce", "custom-call",
)

# `%name = <type> opcode(...)`: the opcode is the first lower-case word
# followed by "(" after the "=". The type before it may be a tuple
# `(f32[..], ..)` for multi-output fusions and, on the TPU, carries tiled
# layouts `{1,0:T(8,128)(2,1)S(1)}` — parentheses, colons and upper-case
# tags, but never a lower-case word before a parenthesis
_HLO_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*[^\n]*?\s([a-z][a-z\-]*)\(",
    re.M,
)


def op_census(hlo_text):
    """{opcode: count} over one optimized-HLO module's instruction list."""
    hist = collections.Counter()
    for m in _HLO_OP_RE.finditer(hlo_text):
        hist[m.group(1)] += 1
    return dict(hist)


def interesting_ops(hist):
    """The fixed fusion-hygiene subset (zero-filled) of an op census."""
    return {k: hist.get(k, 0) for k in INTERESTING_OPS}


def cost_summary(raw_cost):
    """{"flops", "bytes_accessed", "out_bytes"} from a
    ``Compiled.cost_analysis()`` dict (missing keys surface as None)."""
    cost = raw_cost or {}
    return {
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "out_bytes": cost.get("bytes accessedout{}"),
    }


def executable_census(compiled):
    """Full census of one compiled executable: cost analysis + optimized
    HLO op histogram. ``hlo_ops`` is the complete {opcode: count} map
    (callers wanting the fusion-hygiene subset apply
    ``interesting_ops``)."""
    out = cost_summary(compiled.cost_analysis())
    if out["out_bytes"] is None:
        try:  # backends without the per-operand cost keys still know sizes
            out["out_bytes"] = float(
                compiled.memory_analysis().output_size_in_bytes
            )
        except Exception:
            pass
    text = compiled.as_text()
    hist = op_census(text)
    out["hlo_ops"] = hist
    out["total_hlo_ops"] = sum(hist.values())
    # Pallas (Mosaic) kernels in the program. The TPU compiler adds
    # custom calls of its own, so the opcode count above cannot tell
    # whether a kernel is there or gave way to its jnp reference
    out["pallas_calls"] = text.count('custom_call_target="tpu_custom_call"')
    return out


# ---------------------------------------------------------------------------
# Program identity + cache keys
# ---------------------------------------------------------------------------

# program object -> stable per-process label. Weakly keyed: telemetry
# must never pin a Program (the executor LRU test relies on dead clones
# collecting), and a recycled id() can't alias two programs to one label.
_prog_ids = weakref.WeakKeyDictionary()
_prog_seq = itertools.count(1)

_lock = threading.Lock()


def program_label(program):
    with _lock:
        label = _prog_ids.get(program)
        if label is None:
            label = "P%d" % next(_prog_seq)
            _prog_ids[program] = label
        return label


def make_key(program, feed_names, fetch_names, mesh=None, block_idx=0,
             spmd=None):
    """The serializable image of the executor's program cache key:
    program label + version + sorted feed names + ordered fetch names
    (+ SPMD mesh shape / sharding-policy summary / non-zero block index
    when applicable)."""
    extra = []
    if block_idx:
        extra.append(("block", int(block_idx)))
    if mesh is not None:
        extra.append((
            "spmd",
            tuple(zip(list(mesh.axis_names), list(mesh.devices.shape))),
        ))
    if spmd:
        extra.append(
            ("spmd_policy", tuple(sorted(spmd.items())))
        )
    return {
        "program": program_label(program),
        "version": int(getattr(program, "_version", 0)),
        "feeds": tuple(sorted(feed_names)),
        "fetches": tuple(fetch_names),
        "extra": tuple(extra),
    }


def fingerprint(key):
    return "%s|v%d|f=%s|o=%s|x=%s" % (
        key["program"], key["version"], ",".join(key["feeds"]),
        ",".join(key["fetches"]), repr(key["extra"]),
    )


def key_slug(key):
    """Prometheus-safe short name for per-key gauge families:
    ``P3_v2_1a2b3c4d`` (the hash disambiguates feed/fetch variants of
    one program version)."""
    return "%s_v%d_%08x" % (
        key["program"], key["version"],
        zlib.crc32(fingerprint(key).encode()) & 0xFFFFFFFF,
    )


# ---------------------------------------------------------------------------
# Record store + recompile sentinel
# ---------------------------------------------------------------------------

_KEY_HISTORY_CAP = 16      # prior keys remembered per program
_PROGRAMS_CAP = 64         # program labels carrying key history
_TRIGGER_CAP = 256         # build-trigger fingerprints remembered
_EXEC_SEEN_CAP = 1024      # (fingerprint, segment) shape signatures
_EVICTED_CAP = 256         # evicted-key fingerprints remembered
_CENSUS_CAP = 64           # program keys carrying census gauges

_records = deque(maxlen=1024)
_records_flag_ver = None
_key_history = OrderedDict()   # program label -> [key, ...] newest last
_evicted = OrderedDict()   # fingerprint -> eviction wall-clock ts
_build_trigger = OrderedDict()  # fingerprint -> newest (trigger, diff)
_exec_seen = OrderedDict()  # (fingerprint, segment) -> last feed_shapes
_census = OrderedDict()    # fingerprint -> accumulated census totals
# monotonic process-lifetime totals (NOT derived from the bounded record
# ring: a recompile storm larger than the ring must still be fully
# counted in snapshots / the gang report)
_totals = {"builds": 0, "compiles": 0, "dispatch_rebinds": 0,
           "compile_ms": 0.0}
_trigger_totals = collections.Counter()
_steady_count = 0         # armed steady-state gates (one per live server)
_tls = threading.local()  # per-thread request-window + warmup depths
_mem_gauges_done = False


class SteadyStateRecompileError(RuntimeError):
    """A steady-state serving compile under FLAGS_serving_strict_compiles.
    Carries the structured record so the shedding layer / client can see
    the attribution."""

    def __init__(self, record):
        self.record = record
        super().__init__(
            "steady-state XLA recompile in serving (strict mode): "
            "trigger=%s key=%s diff=%r"
            % (record["trigger"], record["fingerprint"], record["diff"])
        )


def _apply_record_bound():
    """Resize the record ring to FLAGS_obs_compile_records on any flags
    change (same once-per-version idiom as trace.enabled)."""
    global _records, _records_flag_ver
    ver = _flags.version()
    if _records_flag_ver == ver:
        return
    _records_flag_ver = ver
    try:
        n = max(int(_flags.get_flag("obs_compile_records", 1024)), 1)
    except (TypeError, ValueError):
        n = 1024
    if _records.maxlen != n:
        _records = deque(_records, maxlen=n)


def _phase():
    if getattr(_tls, "warmup", 0) > 0:
        return "warmup"
    if _steady_count > 0:
        return "steady"
    return ""


def _key_diff(new, prior):
    """(changed_components, detail) between two cache keys of the same
    program — the attribution payload of the sentinel."""
    changed, detail = [], {}
    if new["version"] != prior["version"]:
        changed.append("version")
        detail["version"] = [prior["version"], new["version"]]
    if new["feeds"] != prior["feeds"]:
        changed.append("feeds")
        detail["feeds_added"] = sorted(set(new["feeds"]) - set(prior["feeds"]))
        detail["feeds_removed"] = sorted(
            set(prior["feeds"]) - set(new["feeds"])
        )
    if new["fetches"] != prior["fetches"]:
        changed.append("fetches")
        detail["fetches"] = [list(prior["fetches"]), list(new["fetches"])]
    if new["extra"] != prior["extra"]:
        changed.append("extra")
        detail["extra"] = [repr(prior["extra"]), repr(new["extra"])]
    return changed, detail


def _classify_build(key):
    """Trigger + diff for a new _CompiledBlock build, against the nearest
    prior key of the same program (fewest changed components wins, newest
    breaks ties) and the evicted-key memory. Caller holds _lock."""
    fp = fingerprint(key)
    if fp in _evicted:
        return "lru_eviction", {
            "prior": fp, "changed": ["evicted"],
            "evicted_ts": _evicted[fp],
        }
    hist = _key_history.get(key["program"], [])
    if not hist:
        return "cold", {}
    best = None
    for prior in reversed(hist):  # newest first
        changed, detail = _key_diff(key, prior)
        if best is None or len(changed) < len(best[1]):
            best = (prior, changed, detail)
        if not changed:
            break
    prior, changed, detail = best
    diff = {"prior": fingerprint(prior), "changed": changed,
            "detail": detail}
    if not changed:
        # identical key rebuilt while still remembered and never evicted:
        # the caller bypassed the program cache (use_program_cache=False)
        return "uncached_rebuild", diff
    if "version" in changed:
        return "program_mutation", diff
    return "feed_order_change", diff


def _append(record):
    _apply_record_bound()
    from . import trace as _trace

    record.setdefault("ts", time.time())
    record.setdefault("rank", _trace.gang_rank())
    _records.append(record)
    return record


def on_build(key, wall_ms, n_xla_segments=0):
    """One ``_CompiledBlock`` construction (trace + segment lowering).
    Classifies the trigger via the sentinel and remembers the key as the
    program's newest. Returns the record."""
    _maybe_register_device_memory_gauges()
    with _lock:
        trigger, diff = _classify_build(key)
        fp = fingerprint(key)
        _evicted.pop(fp, None)
        hist = _key_history.setdefault(key["program"], [])
        hist[:] = [k for k in hist if fingerprint(k) != fp]
        hist.append(dict(key))
        del hist[:-_KEY_HISTORY_CAP]
        _key_history.move_to_end(key["program"])
        while len(_key_history) > _PROGRAMS_CAP:
            _key_history.popitem(last=False)
        _build_trigger[fp] = (trigger, diff)
        _build_trigger.move_to_end(fp)
        while len(_build_trigger) > _TRIGGER_CAP:
            _build_trigger.popitem(last=False)
        # a rebuild replaces the block's executables wholesale: its
        # fresh compiles must inherit THIS build's trigger (eviction,
        # mutation, ...), not read as shape changes against executables
        # that no longer exist
        for seen_key in [k for k in _exec_seen if k[0] == fp]:
            del _exec_seen[seen_key]
        record = _append({
            "kind": "build", "key": dict(key), "fingerprint": fp,
            "slug": key_slug(key), "trigger": trigger, "diff": diff,
            "wall_ms": round(float(wall_ms), 3),
            "segments": int(n_xla_segments), "phase": _phase(),
        })
        _totals["builds"] += 1
    _profiler.bump_counter("xla_builds")
    _profiler.bump_histogram("xla_build_ms", wall_ms)
    return record


def on_dispatch_rebind(key, ordered_feeds):
    """The executor's dispatch-plan cache missed but the canonical cache
    hit: same compiled block, new feed ORDER. No XLA work happened — the
    record (trigger ``feed_order_change``, ``recompiled: false``) exists
    so ``/compiles`` proves the cache absorbed it."""
    with _lock:
        record = _append({
            "kind": "dispatch", "key": dict(key),
            "fingerprint": fingerprint(key), "slug": key_slug(key),
            "trigger": "feed_order_change",
            "diff": {"changed": ["feed_order"],
                     "detail": {"feed_order": list(ordered_feeds)}},
            "recompiled": False, "wall_ms": 0.0, "phase": _phase(),
        })
        _totals["dispatch_rebinds"] += 1
    _profiler.bump_counter("xla_dispatch_rebinds")
    return record


def on_xla_compile(key, segment, feed_shapes, wall_ms, compiled=None):
    """One real XLA executable compile (the executor's AOT
    lower-and-compile of one segment at one feed-shape signature).
    Runs the cost census on the in-hand executable (free — no second
    compile), registers the per-key gauges, and applies the strict
    serving gate. Raises SteadyStateRecompileError AFTER recording when
    the gate is armed and tripped."""
    census = None
    if compiled is not None and bool(
        _flags.get_flag("obs_compile_census", True)
    ):
        try:
            census = executable_census(compiled)
        except Exception:  # census must never break execution
            census = None
    with _lock:
        fp = fingerprint(key)
        seen_key = (fp, int(segment))
        prev_shapes = _exec_seen.get(seen_key)
        if prev_shapes is None:
            trigger, diff = _build_trigger.get(fp, ("cold", {}))
        else:
            changed = {
                n: [prev_shapes.get(n), feed_shapes.get(n)]
                for n in set(prev_shapes) | set(feed_shapes)
                if prev_shapes.get(n) != feed_shapes.get(n)
            }
            trigger = "shape_change"
            diff = {"changed": ["feed_shapes"],
                    "detail": {"feed_shapes": changed} if changed
                    else {"state_or_const": True}}
        _exec_seen[seen_key] = dict(feed_shapes)
        _exec_seen.move_to_end(seen_key)
        while len(_exec_seen) > _EXEC_SEEN_CAP:
            _exec_seen.popitem(last=False)
        record = _append({
            "kind": "compile", "key": dict(key), "fingerprint": fp,
            "slug": key_slug(key), "segment": int(segment),
            "trigger": trigger, "diff": diff,
            "feed_shapes": dict(feed_shapes),
            "wall_ms": round(float(wall_ms), 3),
            "census": census, "phase": _phase(),
        })
        if census is not None:
            _accumulate_census(key, fp, segment, census)
        _totals["compiles"] += 1
        _totals["compile_ms"] += float(wall_ms)
        _trigger_totals[trigger] += 1
        # only a compile on a serving-request thread can violate the
        # gate: a colocated trainer's legitimate new-shape compile in
        # the same process is neither a serving recompile nor a reason
        # to crash the training step under strict mode. The warmup
        # exemption is per-thread too — one server's live ladder growth
        # must not mask a sibling server's steady recompile
        steady_violation = (
            _steady_count > 0
            and getattr(_tls, "warmup", 0) == 0
            and getattr(_tls, "depth", 0) > 0
        )
    _profiler.bump_counter("xla_compiles")
    _profiler.bump_histogram("xla_compile_ms", wall_ms)
    if trigger != "cold":
        _profiler.bump_counter("xla_recompiles")
    if steady_violation:
        _profiler.bump_counter("serving_steady_recompiles")
        if bool(_flags.get_flag("serving_strict_compiles", False)):
            raise SteadyStateRecompileError(record)
    return record


def note_eviction(key):
    """The executor's bounded LRU dropped a compiled block: remember the
    fingerprint so the sentinel can label its re-build ``lru_eviction``
    instead of a puzzling re-``cold``. The eviction counter covers every
    drop — including keyless entries (pipeline programs) that carry no
    fingerprint to remember."""
    _profiler.bump_counter("executor_compiled_block_evictions")
    if key is None:
        return
    with _lock:
        fp = fingerprint(key)
        _evicted[fp] = time.time()
        _evicted.move_to_end(fp)
        while len(_evicted) > _EVICTED_CAP:
            _evicted.popitem(last=False)


# ---------------------------------------------------------------------------
# Census accumulation + gauges
# ---------------------------------------------------------------------------

def _accumulate_census(key, fp, segment, census):
    """Fold one executable's census into the per-program-key totals and
    (re-)register the registry gauges. Caller holds _lock."""
    entry = _census.get(fp)
    if entry is None:
        entry = _census[fp] = {
            "slug": key_slug(key), "key": dict(key), "segments": {},
        }
    entry["segments"][int(segment)] = {
        "flops": census.get("flops"),
        "bytes_accessed": census.get("bytes_accessed"),
        "out_bytes": census.get("out_bytes"),
        "hlo_ops": interesting_ops(census.get("hlo_ops") or {}),
        "total_hlo_ops": census.get("total_hlo_ops"),
    }
    for field in ("flops", "bytes_accessed", "out_bytes"):
        # a backend whose cost analysis lacks a key must total None, not
        # 0.0 — a false zero would render as a real gauge and let bench
        # bank a zeroed baseline over the true one
        vals = [
            s[field] for s in entry["segments"].values()
            if s[field] is not None
        ]
        entry[field] = sum(vals) if vals else None
    _census.move_to_end(fp)
    from . import registry as _registry

    slug = entry["slug"]
    _registry.register_gauge("xla_flops_" + slug,
                             lambda e=entry: e["flops"])
    _registry.register_gauge("xla_bytes_accessed_" + slug,
                             lambda e=entry: e["bytes_accessed"])
    _registry.register_gauge("xla_out_bytes_" + slug,
                             lambda e=entry: e["out_bytes"])
    while len(_census) > _CENSUS_CAP:
        _fp, dropped = _census.popitem(last=False)
        for prefix in ("xla_flops_", "xla_bytes_accessed_",
                       "xla_out_bytes_"):
            _registry.unregister_gauge(prefix + dropped["slug"])


def census_by_key():
    """{fingerprint: totals} snapshot of every program key censused so
    far (totals summed over that key's compiled segments)."""
    with _lock:
        return {
            fp: {
                "slug": e["slug"], "key": dict(e["key"]),
                "flops": e.get("flops"),
                "bytes_accessed": e.get("bytes_accessed"),
                "out_bytes": e.get("out_bytes"),
                "segments": {str(i): dict(s)
                             for i, s in e["segments"].items()},
            }
            for fp, e in _census.items()
        }


def headline_census():
    """The census totals of the heaviest program key compiled in this
    process (max flops) — what a bench rung banks as its flops/bytes
    budget. None when nothing was censused."""
    cens = census_by_key()
    if not cens:
        return None
    fp, best = max(
        cens.items(), key=lambda kv: kv[1].get("flops") or 0.0
    )
    return {
        "fingerprint": fp, "slug": best["slug"],
        "flops": best["flops"], "bytes_accessed": best["bytes_accessed"],
        "out_bytes": best["out_bytes"], "census_keys": len(cens),
    }


def attach_headline_census(result):
    """Copy the headline census totals (flops / bytes_accessed /
    out_bytes) into a bench RESULT dict — the single definition of the
    banked field set, shared by every bench child. No-op (and returns
    the dict unchanged) when nothing was censused."""
    census = headline_census()
    if census is not None:
        for k in ("flops", "bytes_accessed", "out_bytes"):
            # never emit a None/zeroed field: bank_write only protects
            # the banked baseline when the key is ABSENT
            if census[k] is not None:
                result[k] = census[k]
    return result


def _maybe_register_device_memory_gauges():
    """Register live/peak device-memory gauges once, where the backend
    exposes ``Device.memory_stats()`` (TPU/GPU; the CPU backend returns
    None — nothing registers, nothing poisons a scrape)."""
    global _mem_gauges_done
    if _mem_gauges_done:
        return
    _mem_gauges_done = True
    try:
        import jax

        devices = [
            d for d in jax.local_devices() if d.memory_stats() is not None
        ]
    except Exception:
        return
    if not devices:
        return
    from . import registry as _registry

    def _sum_stat(stat):
        total = 0
        for d in devices:
            stats = d.memory_stats() or {}
            total += stats.get(stat, 0)
        return total

    _registry.register_gauge(
        "xla_mem_bytes_in_use", lambda: _sum_stat("bytes_in_use")
    )
    _registry.register_gauge(
        "xla_mem_peak_bytes_in_use",
        lambda: _sum_stat("peak_bytes_in_use"),
    )


# ---------------------------------------------------------------------------
# Serving steady-state gate
# ---------------------------------------------------------------------------

def serving_steady(on):
    """Force the steady-state recompile gate to an absolute state
    (tests / probes). Servers use the counted ``arm_serving_steady`` /
    ``disarm_serving_steady`` pair instead, so stopping an old server
    never disarms the gate out from under a live successor."""
    global _steady_count
    with _lock:
        _steady_count = 1 if on else 0


def arm_serving_steady():
    """One server finished warmup: count its gate in (ownership-scoped —
    each live server arms once, disarms once at stop)."""
    global _steady_count
    with _lock:
        _steady_count += 1


def disarm_serving_steady():
    """One server stopped: count its gate out; the gate stays armed
    while any other server in the process is still live."""
    global _steady_count
    with _lock:
        _steady_count = max(0, _steady_count - 1)


class serving_request_window(object):
    """Marks the current thread as executing a serving request (the
    dispatch workers wrap ``_run_batch`` in one): only compiles inside
    a request window can violate the armed steady-state gate. Scoping
    the gate to request threads keeps a colocated trainer's (or a
    second, still-warming workload's) legitimate compiles from bumping
    ``serving_steady_recompiles`` or strict-raising into code that never
    touched serving. Thread-local and re-entrant."""

    def __enter__(self):
        _tls.depth = getattr(_tls, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.depth = max(0, getattr(_tls, "depth", 0) - 1)
        return False


class warmup_window(object):
    """Context manager marking deliberate compile activity (server
    warmup, ladder growth on a live server): compiles inside the window
    record with phase ``warmup`` and never trip the strict gate.
    Thread-local and re-entrant — warmup compiles run on the warming
    caller's thread, and a global exemption would let one server's live
    ladder growth mask a SIBLING server's steady recompile."""

    def __enter__(self):
        _tls.warmup = getattr(_tls, "warmup", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.warmup = max(0, getattr(_tls, "warmup", 0) - 1)
        return False


# ---------------------------------------------------------------------------
# Read side
# ---------------------------------------------------------------------------

def get_records():
    """Snapshot copy of the retained records, oldest first."""
    with _lock:
        return [dict(r) for r in _records]


def summary():
    """Compact roll-up for snapshots / the gang report: totals by kind
    and trigger, steady-state violations, compile wall-clock, and the
    newest few records' fingerprints. Totals are monotonic
    process-lifetime counters, NOT ring-derived — a recompile storm
    larger than ``FLAGS_obs_compile_records`` still counts in full in
    the gang report; only ``recent`` reads the (bounded) ring."""
    with _lock:
        totals = dict(_totals)
        by_trigger = dict(_trigger_totals)
        recent = [
            {"kind": r["kind"], "fingerprint": r["fingerprint"],
             "trigger": r["trigger"], "wall_ms": r["wall_ms"],
             "phase": r["phase"]}
            for r in list(_records)[-8:]
        ]
    return {
        "builds": totals["builds"],
        "compiles": totals["compiles"],
        "dispatch_rebinds": totals["dispatch_rebinds"],
        "by_trigger": by_trigger,
        "steady_recompiles": _profiler.get_counter(
            "serving_steady_recompiles"
        ),
        "compile_ms_total": round(totals["compile_ms"], 3),
        "recent": recent,
    }


# newest SPMD plan summary (set by parallel.spmd.lower via
# set_active_spmd — a setter hook so spmd.py never imports this module
# at its own import time and vice versa). Rides /compiles so the
# exporter shows which mesh/policy the live compiles were built under.
_active_spmd = None


def set_active_spmd(summary_dict):
    global _active_spmd
    with _lock:
        _active_spmd = dict(summary_dict) if summary_dict else None


def active_spmd():
    with _lock:
        return dict(_active_spmd) if _active_spmd else None


def compiles_endpoint():
    """The ``/compiles`` document: summary + full records + per-key
    census (the whole device plane in one JSON GET)."""
    from . import trace as _trace

    return {
        "schema_version": 1,
        "ts": time.time(),
        "rank": _trace.gang_rank(),
        "pid": os.getpid(),
        "serving_steady": _steady_count > 0,
        "spmd": active_spmd(),
        "summary": summary(),
        "records": get_records(),
        "census": census_by_key(),
    }


def reset():
    """Drop records, key history, census, and gate state (tests). Gauges
    for dropped census keys unregister so a later scrape isn't poisoned
    by stale closures."""
    global _steady_count
    from . import registry as _registry

    with _lock:
        dropped = [e["slug"] for e in _census.values()]
        _records.clear()
        _key_history.clear()
        _evicted.clear()
        _build_trigger.clear()
        _exec_seen.clear()
        _census.clear()
        _totals.update(builds=0, compiles=0, dispatch_rebinds=0,
                       compile_ms=0.0)
        _trigger_totals.clear()
        _steady_count = 0
        _tls.depth = 0
        _tls.warmup = 0
    for slug in dropped:
        for prefix in ("xla_flops_", "xla_bytes_accessed_",
                       "xla_out_bytes_"):
            _registry.unregister_gauge(prefix + slug)
