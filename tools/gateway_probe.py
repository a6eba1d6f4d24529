"""CPU-runnable closed-loop probe for the HTTP serving gateway.

Drives ``paddle_tpu/serving/gateway.py`` — the network front door over
the whole serving stack (micro-batcher + bucket ladder + KV-cache
decode engine + strict compile gate) — end to end over real sockets,
and asserts the gateway acceptance bars:

- CONCURRENCY + PARITY: >= 8 concurrent HTTP clients mixing
  ``POST /v1/infer`` and chunked-SSE ``POST /v1/generate`` all get
  results equal to the in-process APIs (token-exact for generation;
  bit-exact through the JSON tensor codec for inference — every float32
  survives the double round-trip);
- ZERO RECOMPILES: the whole HTTP storm runs under the armed PR 7
  strict gate (``FLAGS_serving_strict_compiles``) with
  ``serving_steady_recompiles`` unchanged — the network layer adds no
  compiled surface;
- BACKPRESSURE MAPPING: a rate-limited tenant's burst returns 429 with
  a ``Retry-After`` header (shed at admission), a microsecond deadline
  returns 504 (shed at dispatch), and the two land in distinct
  counters;
- OBSERVABILITY: per-tenant ``gateway_*`` counters/histograms
  round-trip through the PR 5 exporter's ``/metrics`` (HTTP scrape +
  ``parse_prometheus``), ``gateway_request`` spans surface on
  ``/trace``, and the JSONL access log carries one line per request
  with unique request ids;
- GRACEFUL DRAIN: a real ``SIGTERM`` mid-stream flips ``/readyz``
  NOT-READY (shared preemption latch), every in-flight SSE stream
  completes in full, and only then does the listener close.

The probe also measures the HTTP hop's added latency vs the in-process
``infer()`` / ``generate()`` calls (the PERF.md gateway-overhead
numbers).

Run directly (prints one REPORT json line + PROBE PASS/FAIL)::

    JAX_PLATFORMS=cpu python tools/gateway_probe.py --fast

or via tests/test_gateway.py, which runs --fast as a tier-1 gate (in a
subprocess — the probe SIGTERMs itself).
"""

import argparse
import json
import os
import signal
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPORT_SCHEMA_VERSION = 1


def build_classifier(dirname, dim=32, hidden=64, classes=8, seed=0):
    """Init + save a small classifier inference model (the /v1/infer
    workload); returns an example single-row input."""
    import numpy as np

    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[dim], dtype="float32")
        h = fluid.layers.fc(x, size=hidden, act="relu", name="gwp_fc1")
        out = fluid.layers.softmax(
            fluid.layers.fc(h, size=classes, name="gwp_cls")
        )
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        fluid.io.save_inference_model(
            dirname, ["x"], [out], exe, main_program=main
        )
    return np.random.RandomState(seed).rand(1, dim).astype("float32")


def _post(url, body, headers=None, timeout=60):
    """(status, parsed json body, headers) — HTTPError unwrapped."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode("utf-8"),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _sse(url, body, headers=None, timeout=120, on_token=None):
    """POST and consume a chunked SSE stream: returns (tokens, done).
    ``on_token`` fires per token as it arrives (tests hook it to act
    mid-stream). Shared with tests/test_gateway.py — one copy of the
    SSE framing/assembly logic."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode("utf-8"),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    toks, done = [], None
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for line in r:
            line = line.decode("utf-8").strip()
            if not line.startswith("data: "):
                continue
            obj = json.loads(line[len("data: "):])
            if "token" in obj:
                toks.append(obj["token"])
                if on_token is not None:
                    on_token(obj["token"])
            else:
                done = obj
    return toks, done


def _percentile(samples, p):
    import numpy as np

    return round(float(np.percentile(np.asarray(samples), p)), 3)


def run_probe(fast=True, verbose=False):
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import inference, serving
    from paddle_tpu.fluid import flags as _flags
    from paddle_tpu.fluid import profiler
    from paddle_tpu.models import gpt
    from paddle_tpu.observability import exporter as obs_exporter
    from paddle_tpu.observability import registry as obs_registry
    from paddle_tpu.serving.decode import DecodeEngine
    from paddle_tpu.serving.gateway import decode_tensor, encode_tensor

    # strict gate + a real /metrics listener: the probe's entire HTTP
    # storm must hold 0 steady-state recompiles AND be scrapeable
    _flags.set_flags({
        "FLAGS_serving_strict_compiles": True,
        "FLAGS_obs_http_port": 0,
    })

    report = {"schema_version": REPORT_SCHEMA_VERSION, "fast": bool(fast)}
    failures = []
    max_len = 48
    clients = 8
    infer_reqs = 8 if fast else 20
    gen_max_new = 10 if fast else 16

    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = max_len
    with fluid.unique_name.guard():
        infer_prog, startup, _n, _l = gpt.build_gpt_infer(cfg, max_len)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    engine = DecodeEngine(cfg, scope=scope, slots=clients, max_len=max_len,
                          prefill_buckets=[16, max_len],
                          param_program=infer_prog)

    tmp = tempfile.mkdtemp(prefix="gateway_probe_")
    access_path = os.path.join(tmp, "access.jsonl")
    xd = build_classifier(os.path.join(tmp, "model"))
    pred = inference.create_paddle_predictor(
        inference.AnalysisConfig(os.path.join(tmp, "model"))
    )
    server = serving.InferenceServer(
        pred, max_batch_size=8, batch_timeout_ms=5.0, queue_depth=64,
        num_workers=1, decode_engine=engine,
    ).start(warmup_inputs=[xd])
    gw = serving.Gateway(server, port=0, access_log=access_path).start()
    base = "http://127.0.0.1:%d" % gw.port

    rs = np.random.RandomState(11)
    prompts = [list(map(int, rs.randint(0, cfg.vocab_size, n)))
               for n in (2, 5, 9, 14)]

    try:
        # ---- in-process oracles (the APIs the gateway must match) ----
        expect_infer = server.infer([xd], deadline_ms=30000)
        expect_tokens = {
            tuple(p): server.generate(p, max_new_tokens=gen_max_new)
            .tokens(timeout=120)
            for p in prompts
        }
        c_warm = profiler.get_counters()

        # ---- concurrency + parity: 8 HTTP clients, mixed endpoints ----
        errors = []

        def infer_client(tenant):
            try:
                for _ in range(infer_reqs):
                    st, body, _ = _post(
                        base + "/v1/infer",
                        {"inputs": [encode_tensor(xd)],
                         "deadline_ms": 30000},
                        headers={"X-Tenant-Id": tenant},
                    )
                    assert st == 200, (st, body)
                    got = [decode_tensor(t) for t in body["outputs"]]
                    assert len(got) == len(expect_infer)
                    for g, e in zip(got, expect_infer):
                        # float32 -> double -> json -> float32 is exact
                        assert np.array_equal(g, np.asarray(e)), "drift"
            except Exception as e:  # noqa: BLE001 - surfaced via errors
                errors.append(e)

        def gen_client(tenant, prompt):
            try:
                toks, done = _sse(
                    base + "/v1/generate",
                    {"prompt_ids": prompt, "max_new_tokens": gen_max_new},
                    headers={"X-Tenant-Id": tenant},
                )
                assert toks == expect_tokens[tuple(prompt)], \
                    (toks, expect_tokens[tuple(prompt)])
                assert done and done.get("done") and \
                    done.get("finish_reason") in ("length", "eos"), done
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = []
        for i in range(clients // 2):
            threads.append(threading.Thread(
                target=infer_client, args=("tenant_a",)))
            threads.append(threading.Thread(
                target=gen_client, args=("tenant_b", prompts[i % 4])))
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        storm_s = time.perf_counter() - t0
        report["http"] = {
            "clients": len(threads),
            "infer_requests": (clients // 2) * infer_reqs,
            "generate_streams": clients // 2,
            "errors": len(errors),
            "wall_s": round(storm_s, 2),
        }
        if errors:
            failures.append("%d client errors: %r" % (len(errors),
                                                      errors[:3]))

        # ---- strict gate: the HTTP layer added zero recompiles ----
        c_now = profiler.get_counters()
        steady = (c_now.get("serving_steady_recompiles", 0)
                  - c_warm.get("serving_steady_recompiles", 0))
        report["strict"] = {"steady_recompiles": int(steady),
                           "gate_armed": True}
        if steady != 0:
            failures.append("%d steady-state recompiles" % steady)

        # ---- HTTP-hop overhead vs the in-process APIs ----
        inproc, overhttp = [], []
        for _ in range(30):
            t1 = time.perf_counter()
            server.infer([xd], deadline_ms=30000)
            inproc.append((time.perf_counter() - t1) * 1e3)
        for _ in range(30):
            t1 = time.perf_counter()
            st, _b, _h = _post(base + "/v1/infer",
                               {"inputs": [encode_tensor(xd)],
                                "deadline_ms": 30000})
            assert st == 200
            overhttp.append((time.perf_counter() - t1) * 1e3)
        t1 = time.perf_counter()
        server.generate(prompts[1], max_new_tokens=gen_max_new)\
            .tokens(timeout=120)
        gen_inproc_ms = (time.perf_counter() - t1) * 1e3
        t1 = time.perf_counter()
        _sse(base + "/v1/generate",
             {"prompt_ids": prompts[1], "max_new_tokens": gen_max_new})
        gen_http_ms = (time.perf_counter() - t1) * 1e3
        report["overhead"] = {
            "inproc_infer_p50_ms": _percentile(inproc, 50),
            "inproc_infer_p99_ms": _percentile(inproc, 99),
            "http_infer_p50_ms": _percentile(overhttp, 50),
            "http_infer_p99_ms": _percentile(overhttp, 99),
            "inproc_generate_ms": round(gen_inproc_ms, 3),
            "http_generate_ms": round(gen_http_ms, 3),
            "tokens_per_stream": gen_max_new,
        }

        # ---- gauges scraped while the main gateway owns them: the
        # rate-limited gateway below will take over the shared gauge
        # names, and its ownership-scoped stop() removes them (the same
        # succession semantics the serving_queue_depth gauge has) ----
        exp = obs_exporter.global_exporter()
        with urllib.request.urlopen(exp.url("/metrics"), timeout=10) as r:
            flat_live = {
                k[0] for k in obs_registry.parse_prometheus(
                    r.read().decode("utf-8"))
            }
        gauges_ok = ("gateway_inflight" in flat_live
                     and "gateway_draining" in flat_live)

        # ---- overload: a rate-limited tenant's burst -> 429 ----
        gw_limited = serving.Gateway(
            server, port=0, rate_limit_rps=0.5, rate_burst=1,
        ).start()
        try:
            lim = "http://127.0.0.1:%d" % gw_limited.port
            st1, _, _ = _post(lim + "/v1/infer",
                              {"inputs": [encode_tensor(xd)]},
                              headers={"X-Tenant-Id": "bursty"})
            st2, body2, hdr2 = _post(lim + "/v1/infer",
                                     {"inputs": [encode_tensor(xd)]},
                                     headers={"X-Tenant-Id": "bursty"})
            report["overload"] = {
                "first_status": st1, "second_status": st2,
                "reason": body2.get("reason"),
                "retry_after_s": hdr2.get("Retry-After"),
                "retry_after_ms": body2.get("retry_after_ms"),
            }
            if not (st1 == 200 and st2 == 429
                    and body2.get("reason") == "ratelimit"
                    and int(hdr2.get("Retry-After", 0)) >= 1):
                failures.append("overload mapping wrong: %r"
                                % report["overload"])
        finally:
            gw_limited.stop()

        # ---- deadline: shed at dispatch -> 504 ----
        st, body, _ = _post(base + "/v1/infer",
                            {"inputs": [encode_tensor(xd)],
                             "deadline_ms": 0.001})
        report["deadline"] = {"status": st, "reason": body.get("reason")}
        if st != 504 or body.get("reason") != "deadline":
            failures.append("deadline mapping wrong: %r"
                            % report["deadline"])

        # ---- metrics + spans + access log round-trip ----
        with urllib.request.urlopen(exp.url("/metrics"), timeout=10) as r:
            scraped = obs_registry.parse_prometheus(
                r.read().decode("utf-8")
            )
        flat = {k[0] for k in scraped}
        need = [
            "gateway_requests", "gateway_shed_admission",
            "gateway_shed_dispatch", "gateway_stream_tokens",
            "gateway_tenant_requests_tenant_a",
            "gateway_tenant_requests_tenant_b",
            "gateway_tenant_shed_bursty",
            "gateway_latency_ms_count", "gateway_ttft_ms_count",
            "gateway_tenant_latency_ms_tenant_a_count",
        ]
        missing = [m for m in need if m not in flat]
        if not gauges_ok:
            missing.append("gateway_inflight/gateway_draining gauges")
        sheds_distinct = (
            scraped.get(("gateway_shed_admission", ""), 0) >= 1
            and scraped.get(("gateway_shed_dispatch", ""), 0) >= 1
        )
        with urllib.request.urlopen(exp.url("/trace"), timeout=10) as r:
            trace = json.loads(r.read())
        gw_spans = [e for e in trace["traceEvents"]
                    if e.get("name") == "gateway_request"]
        with open(access_path) as f:
            log_lines = [json.loads(ln) for ln in f if ln.strip()]
        rids = [ln["request_id"] for ln in log_lines]
        report["observability"] = {
            "metrics_missing": missing,
            "sheds_distinct": bool(sheds_distinct),
            "gateway_request_spans": len(gw_spans),
            "access_log_lines": len(log_lines),
            "access_log_ids_unique": len(set(rids)) == len(rids),
        }
        if missing:
            failures.append("metrics missing on /metrics: %r" % missing)
        if not sheds_distinct:
            failures.append("admission/dispatch sheds not distinct")
        if not gw_spans:
            failures.append("no gateway_request spans on /trace")
        if not log_lines or len(set(rids)) != len(rids):
            failures.append("access log incomplete or ids not unique")

        # ---- SIGTERM mid-stream: drain before the listener closes ----
        drain_tokens = 30 if fast else 40
        got = {}
        drain_errors = []

        def drain_client(i):
            try:
                toks, done = _sse(
                    base + "/v1/generate",
                    {"prompt_ids": prompts[i % 4],
                     "max_new_tokens": drain_tokens},
                )
                got[i] = (toks, done)
            except Exception as e:  # noqa: BLE001
                drain_errors.append(e)

        # the engine's count of decided tokens: the gateway's own
        # (gateway_stream_tokens) rises every 16 tokens of a stream and
        # when it ends, and these streams are shorter
        tok_base = profiler.get_counter("decode_tokens")
        streams = [threading.Thread(target=drain_client, args=(i,))
                   for i in range(4)]
        for t in streams:
            t.start()
        # SIGTERM only once every stream is demonstrably mid-flight: all
        # 4 admitted (the gateway's inflight accounting) AND tokens
        # already flowing — otherwise a not-yet-admitted client
        # would correctly get the drain 503 and fail the completeness
        # check for the wrong reason
        wait_deadline = time.monotonic() + 60
        while time.monotonic() < wait_deadline and (
            gw.admission.total_inflight < 4
            or profiler.get_counter("decode_tokens") <= tok_base
        ):
            time.sleep(0.01)
        gw.install_sigterm()
        os.kill(os.getpid(), signal.SIGTERM)
        # readiness must flip NOT-READY while the drain holds the
        # listener open for the in-flight streams
        readyz_during = None
        try:
            with urllib.request.urlopen(base + "/readyz", timeout=5) as r:
                readyz_during = r.status
        except urllib.error.HTTPError as e:
            readyz_during = e.code
        except (urllib.error.URLError, OSError):
            readyz_during = "closed"
        for t in streams:
            t.join(timeout=120)
        for _ in range(200):
            if gw.port is None:
                break
            time.sleep(0.05)
        closed = gw.port is None
        complete = (not drain_errors and len(got) == 4 and all(
            len(toks) == drain_tokens and done and done.get("done")
            for toks, done in got.values()
        ))
        report["drain"] = {
            "streams": 4,
            "streams_complete": bool(complete),
            "readyz_during_drain": readyz_during,
            "listener_closed": bool(closed),
            "errors": len(drain_errors),
        }
        if not complete:
            failures.append("drain lost in-flight streams: %r"
                            % (drain_errors[:2],))
        if not closed:
            failures.append("listener still open after drain")
        if readyz_during not in (503, "closed"):
            failures.append("readyz stayed ready during drain: %r"
                            % readyz_during)
    finally:
        gw.stop()
        server.stop()
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
