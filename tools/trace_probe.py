"""CPU-runnable closed-loop probe for fleet-wide distributed tracing.

Drives a REAL serving fleet — FleetController + Router fronting two GPT
decode replicas (seeded identical params), strict compile gate armed —
with concurrent ``/v1/infer`` + ``/v1/generate`` traffic while the
chaos harness SIGKILLs one replica mid-stream, then pulls and merges
every process's ``/trace`` and asserts the ISSUE 15 bars:

- ROUND-TRIP: every response carries ``X-Trace-Id``; every SSE done
  event's ``trace_id`` matches its stream's header; the router's and
  gateways' access logs carry the same ids (with backend / retries /
  failover counts on the router lines);
- ONE TREE PER REQUEST: after clock alignment the merged fleet trace
  resolves every driven request to a single CONNECTED cross-process
  span tree — the router span time-contains the gateway span contains
  the engine spans (zero containment violations within slack);
- FAILOVER SEAM: the chaos-killed generation's tree holds BOTH
  replicas' segments under ONE trace_id (the victim's engine spans
  arrive via its black-box dump; orphans attach to the synthetic
  process root, never dropped) plus the router's ``generate_failover``
  instant event naming from/to backends;
- FLIGHT RECORDER: ``fleet_report.json`` merges every process's flight
  dumps into a slowest-requests table whose rows carry trace ids;
- OVERHEAD: tracer + propagation cost, measured as (span cost inside a
  trace_scope x spans-per-request + traceparent parse/format), stays
  under 2% of the measured request p50 (the PR 5 gate), with 0
  steady-state recompiles fleet-wide while tracing is armed.

Run directly (prints one REPORT json line + PROBE PASS/FAIL)::

    JAX_PLATFORMS=cpu python tools/trace_probe.py --fast

or via tests/test_fleet_trace.py (tier-1, subprocess). Overhead-only
misses are prefixed "throughput" so the shared retry policy can re-run
a probe squeezed by a loaded box without retrying correctness.
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from gateway_probe import _post, _percentile  # noqa: E402
from fleet_probe import _sse_collect, build_model  # noqa: E402

REPORT_SCHEMA_VERSION = 1

# cross-process containment slack: same-host wall clocks are identical,
# so the only noise is anchor sampling + NTP slew over the probe's run
_SLACK_S = 0.15


def _read_jsonl(path):
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return out


def _measure_overhead(report, failures, request_p50_ms, spans_per_request):
    """The PR 5 gate, extended with propagation: span cost INSIDE an
    armed trace_scope (ids minted + chained) x the spans a request
    actually opens, plus one traceparent parse+format per hop, as a
    percentage of the measured request p50."""
    from paddle_tpu.observability import trace

    n = 20000
    tid = trace.new_trace_id()
    with trace.trace_scope(tid, "ab" * 8):
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("overhead_bench", cat="bench"):
                pass
        span_us = (time.perf_counter() - t0) / n * 1e6
    tp = trace.format_traceparent(tid, "cd" * 8)
    t0 = time.perf_counter()
    for _ in range(n):
        trace.parse_traceparent(tp)
        trace.format_traceparent(tid, "cd" * 8)
    prop_us = (time.perf_counter() - t0) / n * 1e6
    per_request_us = span_us * spans_per_request + prop_us
    pct = per_request_us / max(request_p50_ms * 1e3, 1e-9) * 100.0
    report["overhead"] = {
        "span_cost_us": round(span_us, 3),
        "propagation_cost_us": round(prop_us, 3),
        "spans_per_request": round(spans_per_request, 1),
        "request_p50_ms": round(request_p50_ms, 3),
        "overhead_pct": round(pct, 4),
    }
    if pct >= 2.0:
        failures.append(
            "throughput: tracer+propagation overhead %.3f%% >= 2%% "
            "(%.2fus/span x %.1f spans + %.2fus propagation vs "
            "p50 %.1fms)"
            % (pct, span_us, spans_per_request, prop_us, request_p50_ms)
        )


def run_probe(fast=True, verbose=False):
    import numpy as np

    from paddle_tpu.fluid import flags as _flags
    from paddle_tpu.observability import exporter as _obs_exporter
    from paddle_tpu.observability import fleet_trace
    from paddle_tpu.observability import registry as _reg
    from paddle_tpu.serving import fleet as fleet_mod
    from paddle_tpu.serving.fleet import FleetController

    report = {"schema_version": REPORT_SCHEMA_VERSION, "fast": bool(fast)}
    failures = []
    tmp = tempfile.mkdtemp(prefix="trace_probe_")
    workdir = os.path.join(tmp, "fleet")
    model_dir = os.path.join(tmp, "export_v1")
    xd = build_model(model_dir, seed=1)

    spec = {"seed": 17, "vocab_size": 97, "hidden_size": 32,
            "num_layers": 2, "num_heads": 2, "intermediate_size": 64,
            "max_len": 48, "slots": 8, "prefill_buckets": [8, 16, 48]}
    router_log = os.path.join(tmp, "router_access.jsonl")
    gateway_log = os.path.join(tmp, "gateway_access.jsonl")
    ctrl_obs = os.path.join(workdir, "obs", "controller")

    # the CONTROLLER process (the router lives here) arms its own
    # exporter: /trace for the merge pull, obs_dir for its black box
    _flags.set_flags({
        "FLAGS_obs_http_port": 0,
        "FLAGS_obs_dir": ctrl_obs,
        "FLAGS_router_access_log": router_log,
        "FLAGS_router_generate_retries": 2,
        "FLAGS_router_health_interval_s": 0.25,
    })
    gen_env = {
        "FLAGS_serving_strict_compiles": "1",
        "FLAGS_decode_prefill_chunk": "8",
        "FLAGS_decode_prefix_cache_mb": "2",
        "FLAGS_decode_block_size": "8",
        # replica 0 SIGKILLs itself after its 6th stream token — the
        # mid-stream chaos seam the merged trace must survive
        "FLAGS_chaos_die_after_tokens": "6",
        "FLAGS_chaos_die_replica": "0",
        "FLAGS_obs_snapshot_interval_s": "1.0",
        # both replicas append whole lines to one shared gateway log
        # (O_APPEND, line-atomic at this size)
        "FLAGS_gateway_access_log": gateway_log,
    }
    ctrl = FleetController(
        model_dir=model_dir, workdir=workdir, replicas=2,
        replica_env=gen_env, autoscale=False, seed=0,
        replica_args=["--gpt-decode", json.dumps(spec)],
    )
    t_boot = time.monotonic()
    ctrl.start()
    try:
        ctrl.wait_ready(timeout=180 if fast else 300)
        report["boot_s"] = round(time.monotonic() - t_boot, 1)
        gen_url = ctrl.router.url("/v1/generate")
        inf_url = ctrl.router.url("/v1/infer")

        # ---- concurrent traffic: streams + infer, one chaos kill -----
        from paddle_tpu.serving.gateway import encode_tensor

        rs = np.random.RandomState(23)
        streams = []
        for i in range(4):
            prompt = [int(t) for t in rs.randint(0, spec["vocab_size"],
                                                 10 + i)]
            knobs = ({} if i % 2 == 0 else
                     {"temperature": 1.3, "top_k": 20, "seed": 100 + i})
            streams.append({"prompt": prompt, "knobs": knobs})
        gen_results = [None] * len(streams)
        inf_results = [None] * 8

        def gen_client(i):
            s = streams[i]
            body = dict(prompt_ids=s["prompt"], max_new_tokens=10,
                        deadline_ms=60000, **s["knobs"])
            try:
                st, events, comments, _gaps, hdrs = _sse_collect(
                    gen_url, body, timeout=90)
                gen_results[i] = {"status": st, "events": events,
                                  "comments": comments, "headers": hdrs}
            except Exception as e:  # noqa: BLE001 - surfaced below
                gen_results[i] = {"error": repr(e)}

        inf_body = {"inputs": [encode_tensor(xd)], "deadline_ms": 30000}

        def inf_client(i):
            try:
                st, b, h = _post(inf_url, inf_body, timeout=30)
                inf_results[i] = {"status": st, "body": b, "headers": h}
            except Exception as e:  # noqa: BLE001
                inf_results[i] = {"error": repr(e)}

        ths = [threading.Thread(target=gen_client, args=(i,))
               for i in range(len(streams))]
        ths += [threading.Thread(target=inf_client, args=(i,))
                for i in range(len(inf_results))]
        for t in ths:
            t.start()
        for t in ths:
            t.join()

        # ---- round-trip: headers == SSE events == access logs --------
        gen_traces, failovers = [], 0
        for i, res in enumerate(gen_results):
            if res is None or "error" in res:
                failures.append("gen stream %d transport error: %r"
                                % (i, res))
                continue
            hdr_tid = res["headers"].get("X-Trace-Id")
            done = [e for e in res["events"] if e.get("done")]
            errs = [e for e in res["events"] if "error" in e]
            if errs:
                failures.append("gen stream %d in-band error: %r"
                                % (i, errs[:1]))
            if not hdr_tid:
                failures.append("gen stream %d missing X-Trace-Id" % i)
                continue
            if not done or done[0].get("trace_id") != hdr_tid:
                failures.append(
                    "gen stream %d trace id did not round-trip through "
                    "the SSE done event: header=%r done=%r"
                    % (i, hdr_tid, done[:1])
                )
            gen_traces.append(hdr_tid)
            if res["comments"]:
                failovers += 1
        inf_traces = []
        for i, res in enumerate(inf_results):
            if res is None or "error" in res or res["status"] != 200:
                failures.append("infer %d failed: %r" % (i, res))
                continue
            tid = res["headers"].get("X-Trace-Id")
            if not tid:
                failures.append("infer %d missing X-Trace-Id" % i)
                continue
            inf_traces.append(tid)
        if failovers == 0:
            failures.append(
                "no stream failed over (the chaos kill never hit a "
                "pinned stream)"
            )
        report["traffic"] = {
            "streams": len(streams), "failovers_seen": failovers,
            "infer_ok": len(inf_traces),
        }

        # the handler writes its log line AFTER the client saw the
        # response end — give the lines a moment to land
        want = set(gen_traces + inf_traces)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            router_lines = _read_jsonl(router_log)
            logged = {r.get("trace_id") for r in router_lines}
            if want <= logged:
                break
            time.sleep(0.1)
        missing = [t for t in gen_traces + inf_traces if t not in logged]
        if missing:
            failures.append(
                "router access log missing %d/%d trace ids"
                % (len(missing), len(gen_traces) + len(inf_traces))
            )
        if not any(r.get("backend") for r in router_lines):
            failures.append("router access log lines carry no backend")
        fo_logged = sum(r.get("failovers", 0) for r in router_lines)
        if failovers and not fo_logged:
            failures.append("router access log counted no failovers")
        gw_lines = _read_jsonl(gateway_log)
        gw_logged = {r.get("trace_id") for r in gw_lines}
        gw_missing = [t for t in inf_traces if t not in gw_logged]
        if gw_missing:
            failures.append(
                "gateway access log missing %d infer trace ids"
                % len(gw_missing)
            )

        # ---- wait out crash detection + pool recovery ----------------
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if any(e.get("event") == "replica_crash"
                   for e in fleet_mod.load_events(workdir)):
                break
            time.sleep(0.1)
        else:
            failures.append("no replica_crash event after the kill")
        try:
            ctrl.wait_ready(timeout=120)
        except Exception as e:  # noqa: BLE001
            failures.append("pool never recovered: %r" % e)

        # ---- direct-request p50 (overhead denominator) ---------------
        live = [i for i in ctrl.replica_info() if i["state"] == "ready"]
        direct = []
        if live:
            durl = "http://127.0.0.1:%d/v1/infer" % live[0]["gateway_port"]
            for _ in range(20):
                t0 = time.perf_counter()
                st, _b, _h = _post(durl, inf_body, timeout=30)
                if st == 200:
                    direct.append((time.perf_counter() - t0) * 1e3)
                time.sleep(0.01)
        p50 = _percentile(direct, 50) if direct else 0.0

        # ---- pull + merge the fleet trace ----------------------------
        exp = _obs_exporter.global_exporter()
        pulls = []
        if exp is None or exp.port is None:
            failures.append("controller exporter never started")
        else:
            pulls.append(fleet_trace.pull_trace(
                "http://127.0.0.1:%d" % exp.port, label="controller"))
        pulled_live = set()
        for info in ctrl.replica_info():
            port = info.get("metrics_port")
            if info["state"] != "ready" or not port:
                continue
            try:
                pulls.append(fleet_trace.pull_trace(
                    "http://127.0.0.1:%d" % port,
                    label="replica_%s" % info["id"]))
                pulled_live.add(int(info["id"]))
            except Exception as e:  # noqa: BLE001
                failures.append("live pull of replica %s failed: %r"
                                % (info["id"], e))
        # dead (and any unpulled) processes merge from their black-box
        # dumps — the chaos victim's segment lives ONLY there
        for label, path in fleet_trace.find_trace_dumps(
                os.path.join(workdir, "obs")):
            rid = label.split("/")[0].replace("replica_", "")
            if rid.isdigit() and int(rid) in pulled_live:
                continue
            if label.startswith("controller"):
                continue
            pulls.append(fleet_trace.load_trace_dump(path, label=label))
        t_merge = time.perf_counter()
        merged = fleet_trace.merge(pulls)
        merge_ms = (time.perf_counter() - t_merge) * 1e3
        out_path = os.path.join(tmp, "fleet_trace.json")
        fleet_trace.write_merged(out_path, merged)
        trees = merged["trees"]

        # every driven request: ONE connected cross-process tree whose
        # parents time-contain their children after alignment
        connected = contained = linked2 = 0
        for tid in gen_traces + inf_traces:
            tree = trees.get(tid)
            if tree is None:
                failures.append("trace %s absent from the merge" % tid)
                continue
            if not tree["connected"]:
                failures.append(
                    "trace %s is not a single connected tree "
                    "(root=%r, %d spans, %d orphans)"
                    % (tid, tree["root"], len(tree["nodes"]),
                       tree["orphans"])
                )
            else:
                connected += 1
            if len(tree["processes"]) >= 2:
                linked2 += 1
            viol = fleet_trace.containment_violations(tree,
                                                      slack_s=_SLACK_S)
            if viol:
                failures.append(
                    "trace %s containment violations after alignment: "
                    "%r" % (tid, viol[:3])
                )
            else:
                contained += 1
        # the failover generations: the router's instant event naming
        # the seam in every one, and — for generations killed truly
        # MID-stream (tokens already emitted on the victim, i.e. the
        # instant's resume_at > 0; a stream that died while still
        # prefilling has no victim-side spans to show by construction)
        # — BOTH replicas' segments under the one trace_id
        fo_traces = [
            t for t in gen_traces
            if trees.get(t) is not None
            and any(i["name"] == "generate_failover"
                    for i in trees[t]["instants"])
        ]
        if failovers and not fo_traces:
            failures.append(
                "no generate_failover instant event in any merged tree"
            )
        midstream = 0
        for t in fo_traces:
            tree = trees[t]
            inst = [i for i in tree["instants"]
                    if i["name"] == "generate_failover"][0]
            if not (inst["args"].get("from_backend")
                    and inst["args"].get("to_backend")):
                failures.append(
                    "failover instant lacks from/to backends: %r"
                    % inst["args"]
                )
            if not inst["args"].get("resume_at"):
                continue
            midstream += 1
            replica_procs = {p for p in tree["processes"]
                             if "replica" in str(p)}
            if len(replica_procs) < 2:
                failures.append(
                    "mid-stream failover trace %s holds %d replica "
                    "segments, wanted both (processes=%r)"
                    % (t, len(replica_procs), sorted(tree["processes"]))
                )
        if failovers and not midstream:
            failures.append(
                "no failover happened truly mid-stream (resume_at > 0)"
            )
        spans_per_req = (
            sum(len(trees[t]["nodes"]) + len(trees[t]["ticks"])
                for t in inf_traces if t in trees)
            / max(len(inf_traces), 1)
        )
        report["merge"] = {
            "processes": len(pulls),
            "traces": len(trees),
            "driven": len(gen_traces) + len(inf_traces),
            "connected": connected,
            "contained": contained,
            "cross_process": linked2,
            "failover_traces": len(fo_traces),
            "midstream_failovers": midstream,
            "orphan_spans": merged["orphan_spans"],
            "requests_linked": merged["requests_linked"],
            "merged_spans": len(merged["spans"]),
            "merge_ms": round(merge_ms, 1),
        }

        # ---- strict gate with tracing armed --------------------------
        steady = scraped = 0
        for info in ctrl.replica_info():
            port = info.get("metrics_port")
            if not port or info["state"] != "ready":
                continue
            try:
                with urllib.request.urlopen(
                    "http://127.0.0.1:%d/metrics" % port, timeout=5
                ) as r:
                    parsed = _reg.parse_prometheus(r.read().decode("utf-8"))
                scraped += 1
                steady += int(parsed.get(
                    ("serving_steady_recompiles", ""), 0))
            except Exception as e:  # noqa: BLE001
                failures.append("metrics scrape failed: %r" % e)
        if not scraped:
            failures.append("no replica metrics scraped")
        if steady != 0:
            failures.append(
                "%d steady-state recompiles with tracing armed" % steady
            )
        report["strict"] = {"replicas_scraped": scraped,
                            "steady_recompiles": steady}

        # ---- overhead gate -------------------------------------------
        if not direct:
            failures.append("no direct requests for the overhead "
                            "denominator")
        else:
            _measure_overhead(report, failures, p50,
                              max(spans_per_req, 1.0))
    finally:
        try:
            ctrl.stop()
        except Exception as e:  # noqa: BLE001
            failures.append("controller stop failed: %r" % e)

    # ---- flight recorder -> slowest-requests table -------------------
    try:
        with open(os.path.join(workdir, "fleet_report.json")) as f:
            fr = json.load(f)
        slowest = fr.get("slowest_requests") or []
        report["flight"] = {
            "slowest_rows": len(slowest),
            "with_trace_id": sum(1 for r in slowest
                                 if r.get("trace_id")),
        }
        if not slowest:
            failures.append("fleet_report has no slowest_requests table")
        elif not any(r.get("trace_id") for r in slowest):
            failures.append("slowest_requests rows carry no trace ids")
    except (OSError, ValueError) as e:
        failures.append("fleet_report.json unreadable: %r" % e)

    import shutil

    shutil.rmtree(tmp, ignore_errors=True)
    report["pass"] = not failures
    report["failures"] = failures
    if verbose:
        print(json.dumps(report, indent=1), file=sys.stderr)
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="tier-1 budget subset")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    report = run_probe(fast=args.fast, verbose=args.verbose)
    print("REPORT " + json.dumps(report, sort_keys=True), flush=True)
    print("PROBE PASS" if report["pass"]
          else "PROBE FAIL: %s" % "; ".join(report["failures"]))
    return 0 if report["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
