"""Device-plane compile telemetry (observability/xla_stats + executor
AOT dispatch): census library, recompile sentinel classification,
cache-eviction alignment, strict serving gate, /compiles endpoint,
snapshot/gang-report merge — plus the fast subset of
tools/compile_probe.py as the closed loop."""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import profiler
from paddle_tpu.observability import aggregate, exporter, registry
from paddle_tpu.observability import xla_stats

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
TOOLS = os.path.join(REPO, "tools")
for _p in (REPO, TOOLS):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@pytest.fixture(autouse=True)
def _xla_stats_state():
    """Each test starts from an empty record store / disarmed gate and
    leaves the flags at defaults."""
    xla_stats.reset()
    yield
    fluid.set_flags({
        "FLAGS_serving_strict_compiles": False,
        "FLAGS_obs_compile_census": True,
        "FLAGS_obs_compile_records": 1024,
    })
    xla_stats.reset()


def _tiny_program(hidden=6, seed=0):
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            h = fluid.layers.fc(x, size=hidden)
            loss = fluid.layers.reduce_mean(h)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feed(batch=3):
    return {"x": np.ones((batch, 4), np.float32)}


# ---------------------------------------------------------------------------
# census library (shared with tools/hlo_scan.py)
# ---------------------------------------------------------------------------
def test_op_census_parses_hlo_shapes_and_tuples():
    hlo = "\n".join([
        "HloModule m",
        "  %p0 = f32[8,4]{1,0} parameter(0)",
        "  %t = f32[4,8]{0,1} transpose(%p0), dimensions={1,0}",
        "  ROOT %fused = (f32[4,8]{1,0}, f32[]) fusion(%t), kind=kLoop",
        "  %d = f32[8,8]{1,0} dot(%p0, %t)",
        # what the TPU compiler prints: tiled layouts inside the type
        "  %c.1 = f32[8,3,8,64]{3,2,1,0:T(8,128)S(1)} custom-call(%d), "
        'custom_call_target="tpu_custom_call"',
        "  %f.2 = (bf16[48,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, "
        "f32[48,1024,1]{2,1,0:T(8,128)}) fusion(%c.1), kind=kOutput",
    ])
    hist = xla_stats.op_census(hlo)
    assert hist == {"parameter": 1, "transpose": 1, "fusion": 2, "dot": 1,
                    "custom-call": 1}
    interesting = xla_stats.interesting_ops(hist)
    assert interesting["transpose"] == 1 and interesting["dot"] == 1
    assert interesting["convolution"] == 0  # zero-filled
    assert set(interesting) == set(xla_stats.INTERESTING_OPS)


def test_cost_summary_handles_dict_and_missing():
    cost = {"flops": 8.0, "bytes accessed": 32.0,
            "bytes accessedout{}": 16.0}
    assert xla_stats.cost_summary(cost) == {
        "flops": 8.0, "bytes_accessed": 32.0, "out_bytes": 16.0}
    empty = xla_stats.cost_summary(None)
    assert empty == {"flops": None, "bytes_accessed": None,
                     "out_bytes": None}


def test_executable_census_on_real_compiled_fn():
    import jax

    co = jax.jit(lambda a: (a @ a).sum()).lower(
        np.ones((8, 8), np.float32)
    ).compile()
    census = xla_stats.executable_census(co)
    assert census["flops"] and census["flops"] > 0
    assert census["bytes_accessed"] and census["bytes_accessed"] > 0
    assert census["total_hlo_ops"] == sum(census["hlo_ops"].values())
    assert census["pallas_calls"] == 0  # plain XLA, no kernel


# ---------------------------------------------------------------------------
# keys + program identity
# ---------------------------------------------------------------------------
def test_program_labels_are_stable_and_weakly_held():
    import gc
    import weakref

    main, _s, _l = _tiny_program()
    assert xla_stats.program_label(main) == xla_stats.program_label(main)
    ref = weakref.ref(main)
    del main, _s, _l
    gc.collect()
    assert ref() is None, "telemetry pinned the Program"


def test_make_key_fingerprint_and_slug():
    main, _s, _l = _tiny_program()
    k1 = xla_stats.make_key(main, ["b", "a"], ["loss"])
    k2 = xla_stats.make_key(main, ["a", "b"], ["loss"])
    # feeds sort in the key (the canonical-cache contract)
    assert xla_stats.fingerprint(k1) == xla_stats.fingerprint(k2)
    k3 = xla_stats.make_key(main, ["a", "b"], ["loss"], block_idx=2)
    assert xla_stats.fingerprint(k3) != xla_stats.fingerprint(k1)
    slug = xla_stats.key_slug(k1)
    assert slug == registry.prom_name(slug), "slug not prometheus-safe"


# ---------------------------------------------------------------------------
# sentinel classification (unit level, no executor)
# ---------------------------------------------------------------------------
def test_sentinel_classifies_cold_mutation_feed_change_and_rebuild():
    main, _s, _l = _tiny_program()
    k1 = xla_stats.make_key(main, ["x"], ["loss"])
    assert xla_stats.on_build(k1, 1.0)["trigger"] == "cold"
    # identical key rebuilt (use_program_cache=False path)
    assert xla_stats.on_build(k1, 1.0)["trigger"] == "uncached_rebuild"
    # version bump
    main._bump_version()
    k2 = xla_stats.make_key(main, ["x"], ["loss"])
    rec = xla_stats.on_build(k2, 1.0)
    assert rec["trigger"] == "program_mutation"
    assert rec["diff"]["changed"] == ["version"]
    assert rec["diff"]["prior"] == xla_stats.fingerprint(k1)
    # fetch-list change at the same version
    k3 = xla_stats.make_key(main, ["x"], ["loss", "acc"])
    rec = xla_stats.on_build(k3, 1.0)
    assert rec["trigger"] == "feed_order_change"
    assert rec["diff"]["changed"] == ["fetches"]
    # feed-set change picks the nearest prior (fewest components)
    k4 = xla_stats.make_key(main, ["x", "mask"], ["loss", "acc"])
    rec = xla_stats.on_build(k4, 1.0)
    assert rec["trigger"] == "feed_order_change"
    assert rec["diff"]["changed"] == ["feeds"]
    assert rec["diff"]["detail"]["feeds_added"] == ["mask"]


def test_sentinel_classifies_lru_eviction():
    main, _s, _l = _tiny_program()
    k = xla_stats.make_key(main, ["x"], ["loss"])
    xla_stats.on_build(k, 1.0)
    xla_stats.note_eviction(k)
    rec = xla_stats.on_build(k, 1.0)
    assert rec["trigger"] == "lru_eviction"
    assert rec["diff"]["changed"] == ["evicted"]


def test_compile_inherits_build_trigger_then_shape_change():
    main, _s, _l = _tiny_program()
    k = xla_stats.make_key(main, ["x"], ["loss"])
    xla_stats.on_build(k, 1.0)
    r1 = xla_stats.on_xla_compile(k, 0, {"x": [4, 8]}, 2.0)
    assert r1["trigger"] == "cold"
    r2 = xla_stats.on_xla_compile(k, 0, {"x": [2, 8]}, 2.0)
    assert r2["trigger"] == "shape_change"
    assert r2["diff"]["detail"]["feed_shapes"] == {"x": [[4, 8], [2, 8]]}
    # a REBUILD resets the executable memory: next compile inherits
    xla_stats.note_eviction(k)
    xla_stats.on_build(k, 1.0)
    r3 = xla_stats.on_xla_compile(k, 0, {"x": [4, 8]}, 2.0)
    assert r3["trigger"] == "lru_eviction"


def test_record_ring_bound_applies_from_flag():
    main, _s, _l = _tiny_program()
    fluid.set_flags({"FLAGS_obs_compile_records": 4})
    k = xla_stats.make_key(main, ["x"], ["loss"])
    for _ in range(10):
        xla_stats.on_build(k, 0.1)
    assert len(xla_stats.get_records()) == 4


def test_census_missing_cost_keys_stay_none_not_zero():
    """A backend whose cost_analysis() lacks the flops/bytes keys must
    total None, not 0.0 — a false zero would scrape as a real gauge and
    bank a zeroed baseline over the true one (attach_headline_census
    must then omit the fields entirely: bank_write only protects the
    banked baseline when a key is ABSENT)."""

    class Stub(object):
        def cost_analysis(self):
            return {}

        def memory_analysis(self):
            raise RuntimeError("n/a")

        def as_text(self):
            return "  %a.1 = f32[2]{0} add(f32[2]{0} %x, f32[2]{0} %y)\n"

    main, _s, _l = _tiny_program()
    k = xla_stats.make_key(main, ["x"], ["loss"])
    xla_stats.on_xla_compile(k, 0, {"x": [1, 8]}, 1.0, compiled=Stub())
    entry = next(iter(xla_stats.census_by_key().values()))
    assert entry["flops"] is None
    assert entry["bytes_accessed"] is None
    result = xla_stats.attach_headline_census({"ips": 1.0})
    assert "flops" not in result and "bytes_accessed" not in result
    # the None-valued gauges are skipped at scrape time, not rendered 0
    from paddle_tpu.observability import registry as _registry

    assert not any(
        name.startswith("xla_flops_") and val == 0.0
        for name, val in _registry.gauge_values().items()
    )


def test_summary_totals_survive_ring_overflow():
    """summary() totals are monotonic, not ring-derived: a recompile
    storm larger than FLAGS_obs_compile_records still counts in full in
    snapshots and the gang report."""
    main, _s, _l = _tiny_program()
    fluid.set_flags({"FLAGS_obs_compile_records": 4})
    k = xla_stats.make_key(main, ["x"], ["loss"])
    xla_stats.on_build(k, 0.1)
    for seg in range(10):
        xla_stats.on_xla_compile(k, seg, {"x": [1, 8]}, 1.0)
    assert len(xla_stats.get_records()) == 4
    s = xla_stats.summary()
    assert s["builds"] == 1
    assert s["compiles"] == 10
    assert sum(s["by_trigger"].values()) == 10
    assert s["compile_ms_total"] == 10.0


# ---------------------------------------------------------------------------
# strict serving gate
# ---------------------------------------------------------------------------
def test_strict_gate_counts_and_raises_outside_warmup():
    main, _s, _l = _tiny_program()
    k = xla_stats.make_key(main, ["x"], ["loss"])
    xla_stats.serving_steady(True)
    c0 = profiler.get_counter("serving_steady_recompiles")
    # warmup window: counted as warmup, gate silent
    with xla_stats.warmup_window():
        rec = xla_stats.on_xla_compile(k, 0, {"x": [1, 8]}, 1.0)
    assert rec["phase"] == "warmup"
    assert profiler.get_counter("serving_steady_recompiles") == c0
    # steady, on a request thread: counter bumps; strict flag raises
    with xla_stats.serving_request_window():
        xla_stats.on_xla_compile(k, 0, {"x": [2, 8]}, 1.0)
        assert profiler.get_counter("serving_steady_recompiles") == c0 + 1
        fluid.set_flags({"FLAGS_serving_strict_compiles": True})
        with pytest.raises(xla_stats.SteadyStateRecompileError) as ei:
            xla_stats.on_xla_compile(k, 0, {"x": [3, 8]}, 1.0)
        assert ei.value.record["trigger"] == "shape_change"
        assert "shape_change" in str(ei.value)
    xla_stats.serving_steady(False)
    with xla_stats.serving_request_window():
        xla_stats.on_xla_compile(k, 0, {"x": [4, 8]}, 1.0)  # disarmed: ok


def test_warmup_exemption_is_thread_local():
    """One server's live ladder growth must not mask a SIBLING server's
    steady recompile: the warmup window only exempts compiles on the
    warming thread itself."""
    main, _s, _l = _tiny_program()
    k = xla_stats.make_key(main, ["x"], ["loss"])
    xla_stats.serving_steady(True)
    c0 = profiler.get_counter("serving_steady_recompiles")

    def sibling_dispatch():
        with xla_stats.serving_request_window():
            xla_stats.on_xla_compile(k, 0, {"x": [1, 8]}, 1.0)

    with xla_stats.warmup_window():
        t = threading.Thread(target=sibling_dispatch)
        t.start()
        t.join()
        # the warming thread's own compile stays exempt
        rec = xla_stats.on_xla_compile(k, 1, {"x": [1, 8]}, 1.0)
    assert rec["phase"] == "warmup"
    assert profiler.get_counter("serving_steady_recompiles") == c0 + 1
    xla_stats.serving_steady(False)


def test_strict_gate_ignores_compiles_off_request_threads():
    """The gate is scoped to serving-request threads: a colocated
    trainer's legitimate new-shape compile while a strict server is
    steady must neither bump serving_steady_recompiles nor raise into
    the training step."""
    main, _s, _l = _tiny_program()
    k = xla_stats.make_key(main, ["x"], ["loss"])
    fluid.set_flags({"FLAGS_serving_strict_compiles": True})
    xla_stats.serving_steady(True)
    c0 = profiler.get_counter("serving_steady_recompiles")
    # not on a request thread: the trainer's compile passes untouched
    xla_stats.on_xla_compile(k, 0, {"x": [1, 8]}, 1.0)
    xla_stats.on_xla_compile(k, 0, {"x": [2, 8]}, 1.0)
    assert profiler.get_counter("serving_steady_recompiles") == c0
    xla_stats.serving_steady(False)


def test_steady_gate_is_arm_counted_across_server_succession():
    """Stopping an older server must not disarm the gate under a live
    successor in the same process: arms are counted (one per server),
    and extra disarms floor at zero."""
    main, _s, _l = _tiny_program()
    k = xla_stats.make_key(main, ["x"], ["loss"])
    xla_stats.arm_serving_steady()    # server A
    xla_stats.arm_serving_steady()    # server B (successor)
    c0 = profiler.get_counter("serving_steady_recompiles")
    xla_stats.disarm_serving_steady()  # A stops; B still live
    with xla_stats.serving_request_window():
        xla_stats.on_xla_compile(k, 0, {"x": [1, 8]}, 1.0)
    assert profiler.get_counter("serving_steady_recompiles") == c0 + 1
    assert xla_stats.compiles_endpoint()["serving_steady"]
    xla_stats.disarm_serving_steady()  # B stops: gate off
    xla_stats.disarm_serving_steady()  # repeated stop: floors at 0
    with xla_stats.serving_request_window():
        xla_stats.on_xla_compile(k, 0, {"x": [2, 8]}, 1.0)
    assert profiler.get_counter("serving_steady_recompiles") == c0 + 1
    assert not xla_stats.compiles_endpoint()["serving_steady"]


# ---------------------------------------------------------------------------
# executor integration
# ---------------------------------------------------------------------------
def test_executor_records_compiles_and_steady_state_is_silent():
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    n0 = len(xla_stats.get_records())
    exe.run(main, feed=_feed(), fetch_list=[loss])
    recs = xla_stats.get_records()[n0:]
    kinds = [r["kind"] for r in recs]
    assert "build" in kinds and "compile" in kinds
    compile_rec = [r for r in recs if r["kind"] == "compile"][0]
    assert compile_rec["trigger"] == "cold"
    assert compile_rec["wall_ms"] > 0
    assert compile_rec["census"]["flops"] > 0
    assert compile_rec["feed_shapes"]["x"] == [3, 4]
    # spans from the compile path landed in the tracer
    from paddle_tpu.observability import trace

    names = {s["name"] for s in trace.get_spans()}
    assert "xla_build" in names and "xla_compile" in names
    # steady state: no further records, no extra spans per step
    n1 = len(xla_stats.get_records())
    exe.run(main, feed=_feed(), fetch_list=[loss])
    assert len(xla_stats.get_records()) == n1


def test_executor_census_gauges_render_in_prometheus():
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[loss])
    gauges = registry.gauge_values()
    flop_gauges = {k: v for k, v in gauges.items()
                   if k.startswith("xla_flops_")}
    assert flop_gauges and all(v > 0 for v in flop_gauges.values())
    text = registry.render_prometheus()
    parsed = registry.parse_prometheus(text)
    for name, val in flop_gauges.items():
        assert parsed[(registry.prom_name(name), "")] == float(val)


def test_executor_census_disabled_by_flag():
    fluid.set_flags({"FLAGS_obs_compile_census": False})
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[loss])
    compiles = [r for r in xla_stats.get_records()
                if r["kind"] == "compile"]
    assert compiles and all(r["census"] is None for r in compiles)
    assert xla_stats.census_by_key() == {}


def test_eviction_drops_dispatch_plans_and_classifies_rebuild():
    """Cache-alignment satellite: when the canonical LRU evicts a block,
    matching dispatch-plan entries drop too — the re-run is a counted
    plan miss and an ``lru_eviction``-classified rebuild, not a silent
    stale hit."""
    main, startup, loss = _tiny_program()
    other, other_startup, other_loss = _tiny_program(hidden=3, seed=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(other_startup)
    exe.run(main, feed=_feed(), fetch_list=[loss])
    assert any(c.program is main for c in exe._plans.values())
    exe._CACHE_CAPACITY = 1
    ev0 = profiler.get_counter("executor_compiled_block_evictions")
    exe.run(other, feed=_feed(), fetch_list=[other_loss])
    assert profiler.get_counter("executor_compiled_block_evictions") > ev0
    assert all(c.program is not main for c in exe._plans.values()), (
        "evicted block still reachable through the dispatch-plan cache"
    )
    m0 = profiler.get_counter("executor_plan_cache_misses")
    n0 = len(xla_stats.get_records())
    exe.run(main, feed=_feed(), fetch_list=[loss])
    assert profiler.get_counter("executor_plan_cache_misses") == m0 + 1
    builds = [r for r in xla_stats.get_records()[n0:]
              if r["kind"] == "build"]
    assert builds and builds[0]["trigger"] == "lru_eviction"


def test_feed_order_change_records_dispatch_rebind_without_recompile():
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            a = fluid.layers.data(name="a", shape=[2], dtype="float32")
            b = fluid.layers.data(name="b", shape=[2], dtype="float32")
            out = a + b
    exe = fluid.Executor(fluid.CPUPlace())
    d = np.ones((1, 2), np.float32)
    exe.run(main, feed={"a": d, "b": d}, fetch_list=[out.name])
    c0 = profiler.get_counter("xla_compiles")
    n0 = len(xla_stats.get_records())
    exe.run(main, feed={"b": d, "a": d}, fetch_list=[out.name])
    assert profiler.get_counter("xla_compiles") == c0, "reorder recompiled"
    recs = xla_stats.get_records()[n0:]
    assert [r["kind"] for r in recs] == ["dispatch"]
    assert recs[0]["trigger"] == "feed_order_change"
    assert recs[0]["diff"]["detail"]["feed_order"] == ["b", "a"]


# ---------------------------------------------------------------------------
# export surfaces
# ---------------------------------------------------------------------------
def test_compiles_endpoint_serves_records_and_census():
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[loss])
    exp = exporter.Exporter(port=0, rank=0).start()
    try:
        with urllib.request.urlopen(exp.url("/compiles"), timeout=10) as r:
            doc = json.loads(r.read().decode())
    finally:
        exp.stop()
    live = xla_stats.compiles_endpoint()
    assert doc["schema_version"] == 1
    assert [r["fingerprint"] for r in doc["records"]] == [
        r["fingerprint"] for r in live["records"]
    ]
    assert doc["summary"]["compiles"] == live["summary"]["compiles"]
    assert doc["census"], "census missing from /compiles"
    for entry in doc["census"].values():
        assert entry["flops"] > 0


def test_snapshot_carries_compile_summary():
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[loss])
    snap = registry.snapshot(rank=0)
    assert snap["compiles"]["compiles"] >= 1
    assert snap["compiles"]["by_trigger"].get("cold", 0) >= 1
    assert len(snap["compiles"]["recent"]) >= 1


def test_gang_report_rolls_up_per_rank_compiles():
    snaps = {
        0: {"compiles": {"compiles": 3, "steady_recompiles": 1,
                         "by_trigger": {"cold": 2, "shape_change": 1}}},
        1: {"compiles": {"compiles": 2, "steady_recompiles": 0,
                         "by_trigger": {"cold": 2}}},
        2: {},  # a rank whose snapshot predates the schema
    }
    roll = aggregate._gang_compiles(snaps)
    assert roll == {
        "compiles_total": 5,
        "by_trigger": {"cold": 4, "shape_change": 1},
        "steady_recompiles": 1,
    }
    assert aggregate._rank_summary(snaps[0])["compiles"]["compiles"] == 3


def test_bench_bank_entry_keeps_census_fields():
    import bench

    line = {"metric": "m", "value": 1.0, "unit": "u", "device": "tpu",
            "flops": 1e12, "bytes_accessed": 2e9, "out_bytes": 1e8,
            "vs_baseline": 2.0}
    entry = bench._bank_entry(line)
    assert entry["flops"] == 1e12
    assert entry["bytes_accessed"] == 2e9
    assert entry["out_bytes"] == 1e8
    assert "vs_baseline" not in entry  # run-relative fields still drop


def test_bench_bank_entry_keeps_census_source_provenance():
    """Re-banking a faster result must not silently drop the slot's
    census provenance marker (hand-recorded hlo_scan artifact vs
    live census)."""
    import bench

    line = {"metric": "m", "value": 1.0, "unit": "u", "device": "tpu",
            "flops": 1e12, "census_source": "live_census"}
    assert bench._bank_entry(line)["census_source"] == "live_census"


def test_bench_lines_skip_census_for_flash_and_stamp_provenance():
    """The flash rung must NOT bank a census (cost analysis can't see
    inside the Pallas custom call — an undercounted bytes baseline is
    worse than none); the dense rung stamps live-census provenance."""
    import bench

    result = {"sps": 10.0, "device": "tpu", "flops": 1e12,
              "bytes_accessed": 2e9, "out_bytes": 1e8}
    dense = bench._bert_line(result, 24, 384)
    assert dense["flops"] == 1e12
    assert dense["census_source"] == "live_census"
    flash = bench._bert_line(result, 24, 384, flash=True)
    for k in ("flops", "bytes_accessed", "out_bytes", "census_source"):
        assert k not in flash
    rn = bench._resnet_line(dict(result, ips=10.0), 256)
    assert rn["census_source"] == "live_census"


# ---------------------------------------------------------------------------
# closed loop
# ---------------------------------------------------------------------------
def test_compile_probe_fast_acceptance():
    """ISSUE 7 closed loop: every synthetic trigger classified +
    key-diff-attributed, strict serving gate (0 warmed recompiles +
    fires unwarmed), /compiles + /metrics round-trip, census equals the
    hlo_scan code path."""
    p = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "compile_probe.py"),
         "--fast"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""),
    )
    assert p.returncode == 0, "probe failed:\n%s\n%s" % (
        p.stdout[-3000:], p.stderr[-2000:]
    )
    assert "PROBE PASS" in p.stdout
    report_line = next(
        ln for ln in p.stdout.splitlines() if ln.startswith("REPORT ")
    )
    report = json.loads(report_line[len("REPORT "):])
    assert report["strict_serving"]["steady_recompiles_warmed"] == 0
    assert report["strict_serving"]["strict_gate_fired"]
    for trig in ("cold", "lru_eviction", "program_mutation",
                 "shape_change"):
        assert report["triggers"]["by_trigger"].get(trig), trig
    assert report["census"]["flops"] > 0
