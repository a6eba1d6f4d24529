"""Headline benchmarks: ResNet-50 training throughput (BASELINE.md metric
1) and BERT-base fine-tune throughput (metric 2) on one chip.

Prints one JSON line per metric, ResNet-50 (the headline) first:
  {"metric": "resnet50_train_throughput", "value", "unit", "vs_baseline", ...}
  {"metric": "bert_base_finetune_throughput", ...}
  {"metric": "gpt2_small_lm_throughput", ...}   (bonus; only when banked)

``vs_baseline`` compares against the reference's V100+NCCL path. The
reference publishes no numbers in-repo (BASELINE.md), so the baseline
constants below are the commonly reported PaddlePaddle-era V100 figures
(~360 images/sec ResNet-50 fp32, ~40 seq/s BERT-base seq128); the
north-star target is >=0.9x.

Architecture: the parent process never imports jax (a chip belongs to
one process at a time, and the parent holds none). It spawns one child
process per rung with a hard wall-clock timeout; on expiry the whole child
process group is SIGKILLed. Every child runs on ``fluid.TPUPlace(0)`` — a
child that finds no TPU fails with kind ``no_tpu`` and the parent exits
non-zero at once, printing no result line: a number on this file's stdout
was measured on a chip in this run, never replayed from the bank and never
taken on the CPU.

- Cheap-first ladder: batch 64 first (small compile), then 256 -> 1024
  only after a success. The best result per metric is emitted at the end.
- Every child turns on the persistent XLA compilation cache through
  ``paddle_tpu.compile_cache`` (``JAX_COMPILATION_CACHE_DIR`` where set,
  else ``<checkout>/.jax_cache``).
- The child emits "HB <phase> ..." heartbeat lines on stderr at every
  phase transition (build / startup / warmup / step k/N); the parent
  relays them with elapsed timestamps.
- Successful measurements are also recorded in BENCH_BANK.json with their
  git sha and UTC timestamp (``bank_write``); the bank is a record, not a
  source of emitted lines.
"""

import json
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

V100_RESNET50_FP32_IMG_PER_SEC = 360.0
METRIC = "resnet50_train_throughput"
UNIT = "images/sec/chip"

# --------------------------------------------------------------------------
# persistent results-bank: every successful chip measurement is recorded
# in the committed BENCH_BANK.json with its git sha and UTC timestamp
# (bank-the-best per slot). The 2026-07-31 entries are the only comparators
# ROADMAP queue 1 has; nothing is emitted from the bank.
# --------------------------------------------------------------------------

BANK_PATH = os.environ.get(
    "BENCH_BANK_PATH",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_BANK.json"),
)


def best_window_rate(samples, min_window_s):
    """Best (events/sec) over any sample window spanning at least
    ``min_window_s``, from a monotone list of (t, cumulative_count)
    pairs; falls back to the full span when no window is long enough.
    The load-robust throughput estimator of the decode, SPMD and
    serving-load probes: external load only ever subtracts throughput,
    so the max window is the undisturbed steady-state figure without the
    admission ramp / drain tail. The O(n^2) pairwise scan is fine for
    the sample counts involved (sub-second polling over seconds-long
    runs — hundreds of samples)."""
    best = 0.0
    for i in range(len(samples)):
        for j in range(i + 1, len(samples)):
            dt = samples[j][0] - samples[i][0]
            if dt >= min_window_s:
                best = max(best, (samples[j][1] - samples[i][1]) / dt)
    if best == 0.0 and len(samples) >= 2:
        dt = samples[-1][0] - samples[0][0]
        best = (samples[-1][1] - samples[0][1]) / max(dt, 1e-6)
    return best


def load_bank():
    try:
        with open(BANK_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _bank_entry(line):
    """Bank entry from an emit line: keep the measurement facts, drop the
    run-relative fields (vs_baseline is recomputed at emit time)."""
    keep = ("metric", "value", "unit", "batch", "device", "seq_len",
            "remat", "flash_attention", "hostfeed", "plan_hit_rate",
            "h2d_overlapped", "serving", "offline_rps", "p99_ms",
            "batch_fill", "bucket_hit_rate", "clients",
            # decode (BENCH_DECODE=1) rung facts: tokens/sec/user is the
            # banked value; the aggregate rate and engine geometry ride
            # along for context
            "decode", "streams", "tok_per_sec", "max_len", "max_new",
            # prefix rung (gpt_decode_prefix): prefix_cache is the
            # bank_best guard flag; TTFT + share/hit-rate are the facts
            # the rung exists to bank
            "prefix_cache", "ttft_ms", "prefix_share", "prefix_hits",
            "prefix_hit_rate", "cached_prefix_tokens",
            # decode engine v2 rungs: gpt_decode_paged banks the seq-4k
            # block-table rate with its pool-byte budget (the claim is
            # "longer streams at UNCHANGED pool bytes"); gpt_decode_spec
            # banks the speculative rate with its width-1 baseline,
            # controlled drafter accuracy, and measured acceptance
            "paged", "paged_block", "pool_blocks", "pool_bytes",
            "pool_anchor_len", "oom_sheds",
            "spec", "spec_tokens", "spec_speedup", "spec_acceptance",
            "spec_parity", "draft_accuracy", "baseline_tok_per_sec_user",
            # tensor-parallel rung (gpt_decode_tp): tp is the bank_best
            # guard flag; tp_degree is the mesh width the rate was
            # measured at (a TP=2 rate is a different machine budget —
            # it must never replace the single-device decode headline)
            "tp", "tp_degree",
            # per-rung cost census (observability/xla_stats): the
            # compiled step's FLOP/HBM-byte budget banks alongside the
            # throughput so PERF.md's bytes-budget table has provenance
            # and future perf PRs have a regression baseline;
            # census_source says where the numbers came from
            # ("live_census" vs a hand-recorded hlo_scan artifact)
            "flops", "bytes_accessed", "out_bytes", "census_source")
    return {k: line[k] for k in keep if k in line}


def bank_write(slot, entry):
    """Record a successful TPU measurement under ``slot`` (bank-the-best:
    a slower re-measurement never overwrites a faster banked one).
    Locked read-modify-write: two bench runs may bank concurrently.
    Returns True if the bank changed."""
    import fcntl

    with open(BANK_PATH + ".lock", "w") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)
        bank = load_bank()
        prev = bank.get(slot)
        if prev is not None and prev.get("value", 0.0) >= entry["value"]:
            return False
        try:
            sha = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True,
                text=True,
                cwd=os.path.dirname(os.path.abspath(__file__)),
                timeout=10,
            ).stdout.strip() or "unknown"
        except (OSError, subprocess.SubprocessError):
            sha = "unknown"
        # a faster run whose census was unavailable (census flag off, or
        # headline_census failed) must not erase the slot's banked
        # flops/bytes baseline — PERF.md's bytes-budget table depends on
        # it surviving every re-bank. Carry is ALL-or-nothing: splicing
        # one prior field into a fresh partial census would bank a
        # mixed-run baseline under a single census_source label
        census_fields = ("flops", "bytes_accessed", "out_bytes")
        carried = {}
        if prev is not None and not any(k in entry for k in census_fields):
            carried = {
                k: prev[k]
                for k in census_fields + ("census_source",)
                if k in prev
            }
        bank[slot] = dict(
            entry,
            git_sha=sha,
            measured_at=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            **carried,
        )
        tmp = BANK_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(bank, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, BANK_PATH)
    return True


def bank_best(prefix):
    """Best banked TPU entry whose slot starts with ``prefix`` (or None).
    Host-fed rungs are a SEPARATE convention (the measured rate includes
    host decode/H2D): a prefix match must never promote one to a
    device-resident headline — ask for them explicitly via a prefix
    containing 'hostfeed'. Serving rungs (BENCH_SERVING=1: requests/sec
    through the dynamic-batching runtime, a different metric entirely)
    are guarded the same way — only a prefix containing 'serving' sees
    them. Decode rungs (tokens/sec/user) need 'decode' in the prefix,
    and the BENCH_DECODE prefix-cache rung (tokens/sec/user at ~90%
    prefix share — an amortized metric a cold-prompt decode headline
    must never inherit) additionally needs 'prefix'. The tensor-parallel
    rung (gpt_decode_tp: the same per-user rate but spread over a TP
    mesh — a different machine budget) is likewise only visible to a
    prefix containing 'tp'."""
    cands = [
        (slot, e)
        for slot, e in load_bank().items()
        if slot.startswith(prefix) and e.get("device") == "tpu"
        and ("hostfeed" in prefix or not e.get("hostfeed"))
        and ("serving" in prefix or not e.get("serving"))
        and ("decode" in prefix or not e.get("decode"))
        and ("prefix" in prefix or not e.get("prefix_cache"))
        and ("paged" in prefix or not e.get("paged"))
        and ("spec" in prefix or not e.get("spec"))
        and ("tp" in prefix or not e.get("tp"))
    ]
    if not cands:
        return None, None
    return max(cands, key=lambda kv: kv[1].get("value", 0.0))


# --------------------------------------------------------------------------
# child: one benchmark attempt (fixed config, no retries — parent owns those)
# --------------------------------------------------------------------------


def _hb(msg):
    print("HB %s" % msg, file=sys.stderr, flush=True)


def _child_fail(kind, msg):
    """Report a classified failure to the parent and exit nonzero."""
    print("CHILDERR " + json.dumps({"kind": kind, "msg": str(msg)[:300]}), flush=True)
    sys.exit(1)


def chip_start():
    """First thing every child does: the compile cache, then chip 0.
    Returns ``fluid.TPUPlace(0)``; a process with no TPU fails with kind
    ``no_tpu``, on which the parent ends the whole run."""
    from paddle_tpu import compile_cache

    compile_cache.enable()
    import paddle_tpu.fluid as fluid

    place = fluid.TPUPlace(0)
    try:
        fluid.core.get_jax_device(place)
    except RuntimeError as e:
        _child_fail("no_tpu", e)
    return place


def serving_child_main(cfg):
    """BENCH_SERVING=1 rung: offline-batch vs dynamic-batch serving
    throughput + p99 on the GPT-2 export. One request = one seq_len
    sequence; 'offline' runs pre-stacked full batches through
    predictor.run (the upper bound dynamic batching chases), 'dynamic'
    drives the InferenceServer with closed-loop concurrent clients.
    Banked under the 'gpt_serving' slot, never promoted to a headline
    (bank_best guards on the serving flag, same as the hostfeed rung)."""
    import tempfile
    import threading

    place = chip_start()

    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import inference, serving
    from paddle_tpu.models.gpt import GPTConfig, build_gpt_infer

    seq_len = cfg.get("seq_len", 128)
    max_batch = cfg.get("batch", 8)
    clients = cfg.get("clients", 2 * max_batch)
    gcfg = GPTConfig(
        vocab_size=cfg.get("vocab", 50257),
        hidden_size=cfg.get("hidden", 768),
        num_layers=cfg.get("layers", 12),
        num_heads=cfg.get("heads", 12),
        intermediate_size=cfg.get("hidden", 768) * 4,
        is_test=True,
    )
    t0 = time.time()
    _hb("build start (GPT infer graph + export)")
    main_prog, startup, feed_names, logits = build_gpt_infer(gcfg, seq_len)
    scope = fluid.core.Scope()
    exe = fluid.Executor(place)
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
    # a GPT-2-small export is ~0.5 GB: clean it up even on failure, or
    # repeated runs fill /tmp on a long-lived TPU host
    import shutil

    export_dir = tempfile.mkdtemp(prefix="bench_serving_")
    try:
        with fluid.scope_guard(scope):
            fluid.io.save_inference_model(
                export_dir, feed_names,
                [main_prog.global_block().var(logits.name)], exe,
                main_program=main_prog,
            )
        _hb("build ok %.1fs" % (time.time() - t0))
        _serving_measure(cfg, inference, serving, np, export_dir, gcfg,
                         seq_len, max_batch, clients)
    finally:
        shutil.rmtree(export_dir, ignore_errors=True)


def _serving_measure(cfg, inference, serving, np, export_dir, gcfg,
                     seq_len, max_batch, clients):
    """Measurement body of the serving rung (export_dir cleanup owned by
    serving_child_main)."""
    import threading

    def chip_predictor():
        config = inference.AnalysisConfig(export_dir)
        config.enable_use_gpu(device_id=0)  # TPUPlace(0), or it raises
        return inference.create_paddle_predictor(config)

    rs = np.random.RandomState(0)
    one = [
        rs.randint(0, gcfg.vocab_size, (1, seq_len, 1)).astype("int64"),
        np.arange(seq_len, dtype="int64").reshape(1, seq_len, 1),
        np.ones((1, seq_len, 1), dtype="float32"),
    ]
    stacked = [np.repeat(a, max_batch, axis=0) for a in one]

    t0 = time.time()
    _hb("offline warmup start (batch-%d compile)" % max_batch)
    offline_pred = chip_predictor()
    offline_pred.run(stacked)
    _hb("offline warmup ok %.1fs" % (time.time() - t0))
    steps = cfg.get("steps", 10)
    t0 = time.perf_counter()
    for _ in range(steps):
        offline_pred.run(stacked)
    offline_rps = steps * max_batch / (time.perf_counter() - t0)
    _hb("offline ok %.1f req/s" % offline_rps)

    t0 = time.time()
    _hb("server warmup start (bucket ladder compiles)")
    server_pred = chip_predictor()
    server = serving.InferenceServer(
        server_pred, max_batch_size=max_batch,
        batch_timeout_ms=cfg.get("batch_timeout_ms", 8.0),
        queue_depth=4 * clients, num_workers=cfg.get("workers", 1),
    ).start(warmup_inputs=one)
    _hb("server warmup ok %.1fs" % (time.time() - t0))

    per_client = cfg.get("requests_per_client", 2 * steps)
    errors = []

    def client_loop():
        try:
            for _ in range(per_client):
                server.infer(one, deadline_ms=120000)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client_loop) for _ in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    stats = server.stats()
    server.stop()
    if errors:
        _child_fail("other", "serving clients failed: %r" % errors[:2])
    rps = clients * per_client / dt
    _hb("dynamic ok %.1f req/s fill=%.2f" % (rps, stats.batch_fill_ratio))
    print("RESULT " + json.dumps({
        "rps": rps,
        "offline_rps": offline_rps,
        "p99_ms": stats.latency_ms["p99"],
        "batch_fill": stats.batch_fill_ratio,
        "bucket_hit_rate": stats.bucket_hit_rate,
        "plan_misses_after_warm": stats.plan_cache_misses,
        "clients": clients,
        "device": "tpu",
    }), flush=True)


def child_main(cfg):
    if cfg.get("serving"):
        return serving_child_main(cfg)
    place = chip_start()

    import jax
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import resnet

    dev = fluid.core.get_jax_device(place)
    batch = cfg["batch"]
    steps = cfg["steps"]
    warmup = cfg["warmup"]
    depth = cfg["depth"]
    image_size = cfg["image_size"]

    t0 = time.time()
    _hb("build start (program construction)")
    main_prog, startup, feeds, loss, acc = resnet.build_resnet_train(
        depth=depth,
        class_num=1000,
        image_size=image_size,
        use_amp=cfg["amp"],
        recompute=bool(cfg.get("remat")),
    )
    _hb("build ok %.1fs" % (time.time() - t0))

    t0 = time.time()
    _hb("startup start (param init compile+run)")
    exe = fluid.Executor(place)
    exe.run(startup)
    _hb("startup ok %.1fs" % (time.time() - t0))

    rs = np.random.RandomState(0)
    hostfeed = bool(cfg.get("hostfeed"))
    if hostfeed:
        # host-fed mode (BENCH_HOSTFEED=1): every batch is GENERATED on
        # the host and travels through the double-buffered io_pipeline, so
        # the measured rate includes host decode + H2D — overlapped behind
        # compute by the pipeline instead of serialized before each step.
        # This is the rung that proves the overlap claim on hardware; the
        # device-resident mode below stays the headline convention.
        from paddle_tpu.fluid import profiler as _profiler

        n_batches = warmup + 2 + steps

        def _host_batches():
            hrs = np.random.RandomState(1)
            for _ in range(n_batches):
                yield {
                    "img": hrs.rand(batch, 3, image_size, image_size)
                    .astype("float32"),
                    "label": hrs.randint(0, 1000, (batch, 1))
                    .astype("int64"),
                }

        loader = fluid.DataLoader.from_generator(
            capacity=4, use_double_buffer=True
        )
        loader.set_batch_generator(_host_batches, places=[place])
        feed_iter = iter(loader)

        def next_feed():
            return next(feed_iter)

        _hb("hostfeed pipeline ready (double-buffered)")
    else:
        # pre-stage the batch on device: this mode measures training-step
        # compute (the reference's synthetic-data convention), not host
        # link bandwidth
        feed = {
            "img": jax.device_put(
                rs.rand(batch, 3, image_size, image_size).astype("float32"),
                dev,
            ),
            "label": jax.device_put(
                rs.randint(0, 1000, (batch, 1)).astype("int64"), dev
            ),
        }

        def next_feed():
            return feed

    t0 = time.time()
    _hb("warmup start (%d steps, includes main-graph compile)" % warmup)
    for i in range(warmup):
        exe.run(main_prog, feed=next_feed(), fetch_list=[loss])
        _hb("warmup step %d/%d done %.1fs" % (i + 1, warmup, time.time() - t0))
    # the executor cache key includes the fetch list, so the fetch-free
    # variant used by the timed loop must be compiled here, not inside it;
    # the follow-up fetching run DRAINS the async queue so none of that
    # work leaks into the timed window
    exe.run(main_prog, feed=next_feed(), fetch_list=[])
    exe.run(main_prog, feed=next_feed(), fetch_list=[loss])
    _hb("warmup fetch-free variant done %.1fs" % (time.time() - t0))

    c0 = _profiler.get_counters() if hostfeed else {}
    _hb("timed run start (%d steps)" % steps)
    t0 = time.perf_counter()
    l = None
    for i in range(steps):
        # fetch the loss only on the final step: fetching synchronizes
        # host<->device every iteration
        fetches = [loss] if i == steps - 1 else []
        out = exe.run(main_prog, feed=next_feed(), fetch_list=fetches)
        if fetches:
            (l,) = out
    lval = float(np.asarray(l).ravel()[0])
    dt = time.perf_counter() - t0
    assert np.isfinite(lval), "non-finite loss %r" % lval
    ips = batch * steps / dt
    _hb("timed run ok %.2fs loss=%.4f ips=%.1f" % (dt, lval, ips))

    result = {"ips": ips, "device": "tpu", "loss": lval}
    # bank the rung's cost census: the executor recorded cost analysis +
    # HLO op counts for every executable it compiled this run (free at
    # compile time); the heaviest program key IS the training step
    try:
        from paddle_tpu.observability import xla_stats as _xla_stats

        _xla_stats.attach_headline_census(result)
    except Exception as e:  # census must never sink a measurement
        _hb("census unavailable: %s" % e)
    if hostfeed:
        # steady-state plan hit rate over the timed window (delta vs the
        # pre-loop snapshot); the staging count covers the whole run —
        # the pipeline legitimately runs ahead during warmup
        c = _profiler.get_counters()
        hits = c.get("executor_plan_cache_hits", 0) - c0.get(
            "executor_plan_cache_hits", 0
        )
        misses = c.get("executor_plan_cache_misses", 0) - c0.get(
            "executor_plan_cache_misses", 0
        )
        result["hostfeed"] = True
        result["plan_hit_rate"] = round(hits / max(hits + misses, 1), 4)
        result["h2d_overlapped"] = c.get("io_pipeline_h2d_batches", 0)
    print("RESULT " + json.dumps(result), flush=True)


def _child_entry(cfg, child=None):
    """Run one child rung (``child`` lets bench_bert.py / bench_gpt.py
    share the failure classification the parent reads)."""
    try:
        (child or child_main)(cfg)
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 - classify for the parent
        s = str(e)
        if "RESOURCE_EXHAUSTED" in s or "Out of memory" in s or "out of memory" in s:
            kind = "oom"
        elif "UNAVAILABLE" in s or "Unavailable" in s or "DEADLINE_EXCEEDED" in s:
            kind = "transient"
        else:
            kind = "other"
        import traceback

        traceback.print_exc(file=sys.stderr)
        _child_fail(kind, s)


# --------------------------------------------------------------------------
# parent: attempt schedule, hard timeouts, heartbeat relay
# --------------------------------------------------------------------------


def _base_cfg():
    return {
        "steps": int(os.environ.get("BENCH_STEPS", "20")),
        "warmup": int(os.environ.get("BENCH_WARMUP", "3")),
        "depth": int(os.environ.get("BENCH_DEPTH", "50")),
        "image_size": int(os.environ.get("BENCH_IMG", "224")),
        "amp": os.environ.get("BENCH_AMP", "1") == "1",
        # rematerialize residual-block activations (PERF.md lever 1):
        # trades recompute FLOPs for the bandwidth-dominant activation
        # writes on the HBM-bound step
        "remat": os.environ.get("BENCH_REMAT", "0") == "1",
        # host-fed rung: batches generated on the host per step and
        # streamed through the double-buffered io_pipeline (the overlap
        # lever); the default stays the device-resident convention
        "hostfeed": os.environ.get("BENCH_HOSTFEED", "0") == "1",
    }


def _run_attempt(label, cfg, timeout, deadline, script=None):
    """Spawn one child attempt; kill its whole process group on timeout.
    Returns (result_dict_or_None, kind, error_str). kind in
    {"", "killed", "oom", "transient", "other", "skipped"}; a child that
    found no TPU (kind "no_tpu") ends the whole run here — nothing this
    harness measures can carry on without the chip.
    ``script`` lets sibling harnesses (bench_bert.py) reuse this exact
    streaming-relay + kill-timer machinery with their own --child entry."""
    budget = min(timeout, deadline - time.time())
    if budget < 30:
        return None, "skipped", "skipped: <30s left in budget"
    t0 = time.time()
    print(
        "bench[%s]: starting (hard timeout %.0fs)" % (label, budget),
        file=sys.stderr,
        flush=True,
    )
    proc = subprocess.Popen(
        [
            sys.executable,
            script or os.path.abspath(__file__),
            "--child",
            json.dumps(cfg),
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        start_new_session=True,  # own process group => killable even if wedged in C++
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    result, childerr, lines = None, None, []
    killed = False

    import threading

    def _kill():
        nonlocal killed
        killed = True
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass

    timer = threading.Timer(budget, _kill)
    timer.daemon = True
    timer.start()
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line.startswith("RESULT "):
                try:
                    result = json.loads(line[len("RESULT ") :])
                except ValueError:
                    lines.append(line)
            elif line.startswith("CHILDERR "):
                try:
                    childerr = json.loads(line[len("CHILDERR ") :])
                except ValueError:
                    lines.append(line)
            else:
                lines.append(line)
                # relay heartbeats (and any backend noise) with timestamps
                print(
                    "bench[%s +%.0fs]: %s" % (label, time.time() - t0, line[:300]),
                    file=sys.stderr,
                    flush=True,
                )
        proc.wait()
    finally:
        timer.cancel()
    if result is not None:
        # a valid result beats a kill flag set in the exit race window
        return result, "", ""
    if childerr is not None:
        if childerr.get("kind") == "no_tpu":
            sys.exit("bench[%s]: no TPU — %s" % (label, childerr.get("msg")))
        return None, childerr.get("kind", "other"), childerr.get("msg", "")
    if killed:
        last = lines[-1] if lines else "(no output)"
        return (
            None,
            "killed",
            "killed at %.0fs hard timeout; last: %s" % (budget, last),
        )
    last = next(
        (l for l in reversed(lines) if "Error" in l or "error" in l),
        lines[-1] if lines else "(no output)",
    )
    return (
        None,
        "other",
        "exit rc=%d without result; last: %s" % (proc.returncode, last[:300]),
    )


def _emit(out):
    print(json.dumps(out), flush=True)


# Per-seq-len V100 fp32 BERT-base fine-tune baselines (BASELINE.md metric
# 2 provenance note): seq128 is the commonly reported ~40 seq/s figure;
# seq384 (the SQuAD convention) is FLOPs-scaled from it — per-sequence
# transformer FLOPs scale as S*(24*H^2 + 4*S*H), giving a 3.16x ratio
# between seq384 and seq128 for H=768, hence 40/3.16 = 12.7 seq/s.
V100_BERT_BASE_SEQ_PER_SEC = {128: 40.0, 384: 12.7}
BERT_METRIC = "bert_base_finetune_throughput"
BERT_UNIT = "sequences/sec/chip"


def _bert_script():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_bert.py")


def _resnet_line(result, batch):
    line = {
        "metric": METRIC,
        "value": round(result["ips"], 2),
        "unit": UNIT,
        "vs_baseline": round(result["ips"] / V100_RESNET50_FP32_IMG_PER_SEC, 3),
        "batch": batch,
        "device": result["device"],
    }
    if result.get("hostfeed"):
        line["hostfeed"] = True
        line["plan_hit_rate"] = result.get("plan_hit_rate")
        line["h2d_overlapped"] = result.get("h2d_overlapped")
    for k in ("flops", "bytes_accessed", "out_bytes"):
        if result.get(k) is not None:
            line[k] = result[k]
            line["census_source"] = "live_census"
    return line


def _bert_line(result, batch, seq_len, flash=False):
    baseline = V100_BERT_BASE_SEQ_PER_SEC.get(seq_len)
    line = {
        "metric": BERT_METRIC,
        "value": round(result["sps"], 2),
        "unit": BERT_UNIT,
        # null for a seq len with no documented baseline constant
        "vs_baseline": round(result["sps"] / baseline, 3) if baseline else None,
        "batch": batch,
        "seq_len": seq_len,
        "device": result["device"],
    }
    if flash:
        line["flash_attention"] = True
    elif any(result.get(k) is not None
             for k in ("flops", "bytes_accessed", "out_bytes")):
        # dense path only: XLA cost analysis cannot see inside the flash
        # Pallas custom call, so a flash census would undercount — a
        # poisoned bytes baseline is worse than none (PERF.md round-5)
        for k in ("flops", "bytes_accessed", "out_bytes"):
            if result.get(k) is not None:
                line[k] = result[k]
        line["census_source"] = "live_census"
    return line


def parent_main():
    total = float(os.environ.get("BENCH_TIMEOUT", "1500"))
    hard_deadline = time.time() + total - 60.0
    base = _base_cfg()

    best = {"resnet": None, "bert": None}  # best emit line per metric

    def note_fail(label, kind, err):
        print(
            "bench[%s]: FAILED — [%s] %s" % (label, kind, err),
            file=sys.stderr,
            flush=True,
        )

    def try_resnet_tpu(batch, slot, remat=None):
        cfg = dict(base, batch=batch)
        if remat is not None:
            cfg["remat"] = remat
        label = "tpu-b%d%s%s" % (
            batch,
            "-remat" if cfg.get("remat") else "",
            "-hostfeed" if cfg.get("hostfeed") else "",
        )
        result, kind, err = _run_attempt(label, cfg, slot, hard_deadline)
        if result is None:
            note_fail(label, kind, err)
            return False
        line = _resnet_line(result, batch)
        if cfg.get("remat"):
            line["remat"] = True
        bank_write(
            "resnet50"
            + ("_remat" if cfg.get("remat") else "")
            + ("_hostfeed" if cfg.get("hostfeed") else ""),
            _bank_entry(line),
        )
        # keep the best: a slower later success (e.g. a bigger batch that
        # thrashes) never replaces a faster line
        if best["resnet"] is None or line["value"] > best["resnet"]["value"]:
            best["resnet"] = line
        return True

    def try_bert_tpu(slot, batch=64, seq_len=128, flash=False):
        cfg = dict(
            batch=batch,
            steps=10,
            warmup=2,
            seq_len=seq_len,
            flash=flash,
        )
        label = "bert-tpu-b%d-s%d%s" % (batch, seq_len, "-flash" if flash else "")
        result, kind, err = _run_attempt(
            label, cfg, slot, hard_deadline, script=_bert_script()
        )
        if result is None:
            note_fail(label, kind, err)
            return False
        line = _bert_line(result, batch, seq_len, flash)
        bank_write(
            "bert_seq%d%s" % (seq_len, "_flash" if flash else ""),
            _bank_entry(line),
        )
        prev = best["bert"]
        # a seq-384 number (the defensible SQuAD config) always beats a
        # seq-128 rung; within a seq len, keep the best
        if (
            prev is None
            or seq_len > prev.get("seq_len", 0)
            or (seq_len == prev.get("seq_len") and line["value"] > prev["value"])
        ):
            best["bert"] = line
        return True

    def try_serving_tpu(slot):
        """BENCH_SERVING=1 rung: bank the dynamic-batching serving
        throughput on the GPT-2 export under 'gpt_serving'. Bank-only
        (never an emit line): requests/sec through the serving runtime is
        a different convention from the headline tokens/sec metrics."""
        cfg = {
            "serving": True,
            "batch": int(os.environ.get("BENCH_SERVING_BATCH", "8")),
            "seq_len": int(os.environ.get("BENCH_SERVING_SEQ", "128")),
            "layers": int(os.environ.get("BENCH_SERVING_LAYERS", "12")),
            "hidden": int(os.environ.get("BENCH_SERVING_HIDDEN", "768")),
            "heads": int(os.environ.get("BENCH_SERVING_HEADS", "12")),
            "vocab": int(os.environ.get("BENCH_SERVING_VOCAB", "50257")),
            "steps": int(os.environ.get("BENCH_SERVING_STEPS", "10")),
        }
        label = "serving-gpt-b%d-s%d" % (cfg["batch"], cfg["seq_len"])
        result, kind, err = _run_attempt(label, cfg, slot, hard_deadline)
        if result is None:
            note_fail(label, kind, err)
            return False
        # routed through _bank_entry so the banked fields can
        # never drift from its serving keep-list
        bank_write("gpt_serving", _bank_entry({
            "metric": "gpt2_serving_throughput",
            "value": round(result["rps"], 2),
            "unit": "requests/sec/chip",
            "batch": cfg["batch"],
            "seq_len": cfg["seq_len"],
            "device": "tpu",
            "serving": True,
            "offline_rps": round(result["offline_rps"], 2),
            "p99_ms": result.get("p99_ms"),
            "batch_fill": result.get("batch_fill"),
            "bucket_hit_rate": result.get("bucket_hit_rate"),
            "clients": result.get("clients"),
        }))
        return True

    # compile-slot budget per batch
    slot_for = {64: 260.0, 256: 240.0, 1024: 280.0}

    # ---- phase A: cheap-first ResNet ladder — b64, then escalate ----
    if try_resnet_tpu(64, slot_for[64]):
        for b in (256, 1024):
            if not try_resnet_tpu(b, slot_for[b]):
                break
    # ---- phase B: BERT — the cheap seq-128 rung, then the defensible
    # SQuAD-convention seq-384 config ----
    if try_bert_tpu(260.0, batch=64, seq_len=128):
        try_bert_tpu(280.0, batch=24, seq_len=384)

    # ---- phase B2: opt-in serving rung (BENCH_SERVING=1; bank-only) ----
    if os.environ.get("BENCH_SERVING", "0") == "1":
        try_serving_tpu(300.0)

    # ---- phase C: variants of what succeeded, while the window lasts:
    # remat at the best ResNet batch (a DIFFERENT HLO, so a full compile
    # slot), flash attention at the best BERT config ----
    if best["resnet"] is not None and not base["remat"]:
        b = best["resnet"]["batch"]
        try_resnet_tpu(b, slot_for.get(b, 280.0), remat=True)
    if best["bert"] is not None:
        try_bert_tpu(
            280.0,
            batch=best["bert"]["batch"],
            seq_len=best["bert"]["seq_len"],
            flash=True,
        )

    # ---- emit: resnet (headline) first, bert second. A metric with no
    # measurement from THIS run has no line, and the exit code says so ----
    rc = 0
    for metric in ("resnet", "bert"):
        if best[metric] is not None:
            _emit(best[metric])
        else:
            print("bench: no %s measurement" % metric, file=sys.stderr,
                  flush=True)
            rc = 1
    return rc


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        _child_entry(json.loads(sys.argv[2]))
    else:
        sys.exit(parent_main())
