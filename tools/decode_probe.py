"""CPU-runnable closed-loop probe for the autoregressive decode runtime.

Drives the paged KV cache + continuous-batching engine
(paddle_tpu/serving/decode.py) — with prefix caching and chunked
prefill armed — against `gpt._reference_generate` (the
full-forward-per-token loop every GPT completion paid before this
subsystem existed) and asserts the decode acceptance bars:

- PARITY: engine output token-exact vs the oracle across prompt lengths,
  an EOS stop mid-stream, max-new-token truncation, and slot reuse after
  retirement (more requests than slots, churned through the pool);
- THROUGHPUT: >= 10x generated tokens/sec over the per-token-recompute
  baseline with 8 concurrent streams (the baseline serializes on the one
  device whatever its client concurrency, so its serial rate IS its
  8-stream rate);
- PREFIX CACHE (ISSUE 12): at a high prefix share (64 of 72 prompt
  tokens cached), a hit admission shares the cached blocks by a table
  edit instead of recomputing them, and BOTH paths stay token-exact vs
  the oracle (the hit and miss TTFTs are reported, not gated);
- CHUNKED PREFILL (ISSUE 12): while a max-length prompt admits as
  bucket-shaped windows, live streams' inter-token p99 stays under the
  one-window counterfactual (the whole prompt in one window + one step
  — the stall a non-chunked admit inflicts), and the chunked prompt
  itself is token-exact;
- EVICTION CHURN: distinct prefixes overflowing the bounded block store
  force LRU evictions; an admission whose prefix was evicted falls
  through to the full-prefill path, still token-exact;
- ZERO RECOMPILES: with the PR 7 strict gate armed
  (`FLAGS_serving_strict_compiles`), the WHOLE schedule above — churned
  admissions/retirements, prefix hits, misses, evictions, chunked
  admits — finishes with `serving_steady_recompiles` unchanged: no
  compiled shape depends on slot liveness, block placement, or window
  offset;
- SPECULATION (ISSUE 16): a second engine with k=4 draft/verify runs
  the same parity gauntlet — miss, zero-copy prefix hit, chunked
  windows, resume, store eviction — token-exact vs the oracle, with
  the verify path exercised by the low-acceptance n-gram drafter
  (constant rejection rollback) AND by a recorded-continuation replay
  drafter at 90% accuracy, which must beat the same engine at verify
  width 1 on the identical workload; the whole schedule adds ZERO
  steady-state recompiles (tables/positions are runtime data);
- METRICS: every decode_*/serving_slot_* counter/histogram/gauge —
  including the TTFT/inter-token histograms and prefix-cache counters —
  renders on the PR 5 exporter registry.

Run directly (prints one REPORT json line + PROBE PASS/FAIL)::

    JAX_PLATFORMS=cpu python tools/decode_probe.py --fast

or via tests/test_decode.py, which runs --fast as a tier-1 gate.
"""

import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPORT_SCHEMA_VERSION = 3


def run_probe(fast=True, verbose=False):
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import flags as _flags
    from paddle_tpu.fluid import profiler
    from paddle_tpu.models import gpt
    from paddle_tpu.observability import registry as obs_registry
    from paddle_tpu.serving.decode import DecodeEngine, DecodeSession

    _flags.set_flags({"FLAGS_serving_strict_compiles": True})

    slots = 8
    # one length for --fast too: on the CPU backend the step's scatter and
    # gather through the table cost ~4 ms at this width whatever the
    # length, so at 96 the 10x bar had no margin (9.3-10.2x measured)
    max_len = 160
    block = 32
    prefill_chunk = 16
    # sized so device compute (not per-run host dispatch) dominates both
    # loops — the regime the 10x bar is about; still compiles in seconds
    # on the CPU backend
    cfg = gpt.GPTConfig.tiny(
        hidden_dropout=0.0, attention_dropout=0.0,
        hidden_size=256, num_layers=2, intermediate_size=768,
    )
    cfg.max_position_embeddings = max_len
    # 12-block store: big enough for the shared-prefix trial, small
    # enough that the eviction trial's distinct prefixes overflow it
    prefix_mb = 12 * gpt.paged_block_bytes(cfg, block) / 2.0 ** 20

    with fluid.unique_name.guard():
        infer, startup, _names, logits = gpt.build_gpt_infer(cfg, max_len)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)

    def oracle(prompt):
        return gpt._reference_generate(
            exe, infer, logits, cfg, prompt, max_len, scope=scope
        )

    report = {"schema_version": REPORT_SCHEMA_VERSION, "fast": bool(fast),
              "slots": slots, "max_len": max_len,
              "block_size": block, "prefill_chunk": prefill_chunk}
    failures = []

    # ---- oracle outputs for parity (compiles the [1, max_len] program) ----
    rs = np.random.RandomState(7)
    prompts = [list(rs.randint(0, cfg.vocab_size, n))
               for n in (1, 7, 12)]
    oracle_out = {tuple(p): oracle(p) for p in prompts}

    # ---- engine up (warmup compiles the window bucket, the block copy
    # and the decode step) ----
    engine = DecodeEngine(
        cfg, scope=scope, slots=slots, max_len=max_len,
        prefill_buckets=[prefill_chunk], param_program=infer,
        block_size=block, prefix_cache_mb=prefix_mb,
        prefill_chunk=prefill_chunk,
    ).start()
    try:
        c_warm = profiler.get_counters()

        # ---- parity: prompt lengths ----
        parity = {}
        for p in prompts:
            got = engine.generate(p).result(timeout=120)
            parity["len_%d" % len(p)] = got == oracle_out[tuple(p)]
        # EOS mid-stream: stop at (and including) a token the greedy
        # stream is known to emit a few steps in
        p = prompts[1]
        gen = oracle_out[tuple(p)][len(p):]
        eos = gen[3]
        stream = engine.generate(p, eos_id=eos)
        got = stream.tokens(timeout=120)
        parity["eos_midstream"] = (
            got == gen[: gen.index(eos) + 1]
            and stream.finish_reason == "eos"
        )
        # max-length truncation
        stream = engine.generate(p, max_new_tokens=5)
        parity["max_new_truncation"] = (
            stream.tokens(timeout=120) == gen[:5]
            and stream.finish_reason == "length"
        )
        # slot reuse after retirement: 2x slots sequential short requests
        # through the same pool, every one token-exact
        reuse_ok = True
        for i in range(2 * slots):
            p = prompts[i % len(prompts)]
            got = engine.generate(p, max_new_tokens=4).tokens(timeout=120)
            reuse_ok = reuse_ok and (
                got == oracle_out[tuple(p)][len(p):len(p) + 4]
            )
        parity["slot_reuse"] = reuse_ok
        report["parity"] = parity
        if not all(parity.values()):
            failures.append("parity: %r" % parity)

        # ---- prefix cache: shared-system-prompt trial. One miss
        # admission populates the index; hit admissions share the cached
        # 64-token prefix's blocks and prefill only the 8-token suffix:
        # both paths stay token-exact ----
        shared = list(rs.randint(0, cfg.vocab_size, 2 * block))
        miss_p = shared + list(rs.randint(0, cfg.vocab_size, 8))
        s_miss = engine.generate(miss_p, max_new_tokens=6)
        miss_toks = s_miss.tokens(timeout=120)
        miss_parity = miss_toks == oracle(miss_p)[len(miss_p):][:6]
        hit_ttfts, hit_parity, hit_cached = [], True, True
        for i in range(3):
            p = shared + list(rs.randint(0, cfg.vocab_size, 8))
            s = engine.generate(p, max_new_tokens=6)
            toks = s.tokens(timeout=120)
            if i == 0:  # one oracle check keeps the trial cheap
                hit_parity = toks == oracle(p)[len(p):][:6]
            hit_ttfts.append(s.ttft_ms)
            hit_cached = hit_cached and (
                s.cached_prefix_tokens == len(shared)
            )
        ttft_hit = sorted(hit_ttfts)[1]  # median of 3
        gain = s_miss.ttft_ms / max(ttft_hit, 1e-9)
        st = engine.stats()
        report["prefix"] = {
            "shared_tokens": len(shared),
            "prompt_tokens": len(miss_p),
            "ttft_miss_ms": round(s_miss.ttft_ms, 2),
            "ttft_hit_ms": round(ttft_hit, 2),
            "ttft_gain": round(gain, 2),
            "miss_parity": bool(miss_parity),
            "hit_parity": bool(hit_parity),
            "hit_cached_tokens_ok": bool(hit_cached),
            "hits": st["prefix_hits"],
            "cached_tokens": st["prefix_cached_tokens"],
        }
        if not (miss_parity and hit_parity and hit_cached):
            failures.append(
                "prefix parity: miss=%s hit=%s cached_ok=%s"
                % (miss_parity, hit_parity, hit_cached)
            )

        # ---- chunked prefill: long-prompt interleave trial. Counter-
        # factual bound: a NON-chunked admit stalls every live stream
        # for (the whole prompt in one window + one fused step) between
        # two of its tokens; chunked admission must keep the live p99
        # inter-token gap under that. Load-robust: best of 2 rounds
        # (external load on the shared 2-core box only ever adds). The
        # one-window program belongs to a 1-slot session of its own: the
        # engine, capped at the chunk, never builds it ----
        whole = DecodeSession(
            cfg, scope=scope, slots=1, max_len=max_len,
            prefill_buckets=[max_len], block_size=block, spec_tokens=0,
        )
        own = list(range(1, whole.max_blocks + 1))
        mono = []
        for _ in range(4):  # the first call compiles
            t0 = time.perf_counter()
            whole.paged_window(own, list(rs.randint(
                0, cfg.vocab_size, max_len - 8)), 0)
            mono.append((time.perf_counter() - t0) * 1e3)
        mono_ms = sorted(mono[1:])[1]

        def interleave_round():
            live = [engine.generate(list(rs.randint(0, cfg.vocab_size, 4)),
                                    max_new_tokens=60) for _ in range(3)]
            stamps = [[] for _ in live]
            threads = [
                threading.Thread(
                    target=lambda i=i, s=s: [stamps[i].append(
                        time.monotonic()) for _ in s]
                )
                for i, s in enumerate(live)
            ]
            for t in threads:
                t.start()
            while min(len(v) for v in stamps) < 3:
                time.sleep(0.005)
            t_sub = time.monotonic()
            long_p = list(rs.randint(0, cfg.vocab_size, max_len - 8))
            s_long = engine.generate(long_p, max_new_tokens=4)
            long_toks = s_long.tokens(timeout=120)
            t_first = t_sub + s_long.ttft_ms / 1e3
            for t in threads:
                t.join()
            base_gaps, admit_gaps = [], []
            for v in stamps:
                for a, b in zip(v, v[1:]):
                    (admit_gaps if t_sub <= b <= t_first + 1e-3
                     else base_gaps).append((b - a) * 1e3)
            admit_gaps.sort()
            base_gaps.sort()
            p99 = admit_gaps[int(len(admit_gaps) * 0.99)] \
                if admit_gaps else float("inf")
            base = base_gaps[len(base_gaps) // 2] if base_gaps else 0.0
            return p99, base, long_p, long_toks, len(admit_gaps)

        best = None
        for _ in range(2):
            p99, base, long_p, long_toks, n_gaps = interleave_round()
            if best is None or p99 < best[0]:
                best = (p99, base, long_p, long_toks, n_gaps)
        p99, base, long_p, long_toks, n_gaps = best
        bound = mono_ms + base
        long_parity = long_toks == oracle(long_p)[len(long_p):][:4]
        report["chunked"] = {
            "long_prompt_tokens": len(long_p),
            "one_window_prefill_ms": round(mono_ms, 2),
            "baseline_gap_ms": round(base, 2),
            "intertoken_p99_ms": round(p99, 2),
            "bound_ms": round(bound, 2),
            "admit_gaps": n_gaps,
            "long_parity": bool(long_parity),
        }
        if not long_parity:
            failures.append("chunked long-prompt parity failed")
        if n_gaps < 3:
            failures.append(
                "chunked admit produced only %d live gaps — streams did "
                "not interleave" % n_gaps
            )
        if p99 >= bound:
            failures.append(
                "intertoken p99 %.1fms >= one-window counterfactual "
                "%.1fms while a max-length prompt admitted" % (p99, bound)
            )

        # ---- eviction churn: 8 distinct 64-token prefixes publish 16
        # blocks into the 12-block store — LRU must evict; an admission
        # whose prefix was evicted falls through to full prefill ----
        ev0 = profiler.get_counters().get("decode_prefix_evictions", 0)
        first_pre = list(rs.randint(0, cfg.vocab_size, 2 * block))
        churn_prefixes = [first_pre] + [
            list(rs.randint(0, cfg.vocab_size, 2 * block))
            for _ in range(7)
        ]
        evict_streams = [
            engine.generate(pre + [int(i)], max_new_tokens=2)
            for i, pre in enumerate(churn_prefixes)
        ]
        for s in evict_streams:
            s.tokens(timeout=120)
        evictions = (profiler.get_counters()
                     .get("decode_prefix_evictions", 0) - ev0)
        # the FIRST prefix is the LRU victim by now: re-admitting it is
        # a miss that must still be token-exact
        re_p = first_pre + [0]
        re_toks = engine.generate(re_p, max_new_tokens=4)\
            .tokens(timeout=120)
        evict_parity = re_toks == oracle(re_p)[len(re_p):][:4]
        report["evictions"] = {
            "evictions": int(evictions),
            "evicted_readmit_parity": bool(evict_parity),
            "store": engine.stats().get("prefix_store"),
        }
        if evictions < 1:
            failures.append("eviction churn produced no evictions")
        if not evict_parity:
            failures.append("post-eviction readmission parity failed")

        # ---- churn + throughput: 8 concurrent streams, requests
        # admitted/retired mid-flight under the strict gate. The shared
        # 2-core driver box drifts under external load (same finding as
        # serving_load_probe.py), so load-robust estimators: the
        # baseline takes the BEST of repeated short rounds (load only
        # ever subtracts throughput), and decode takes the best
        # >=0.7 s sliding window over the live decode_tokens counter —
        # the steady-state rate with every prefill stall inside the
        # window counted, without the admission ramp / drain tail ----
        churn_errors = 0
        base_prompt = list(rs.randint(0, cfg.vocab_size, max_len - 40))
        baseline_tps = 0.0

        def baseline_round():
            t0 = time.perf_counter()
            oracle(base_prompt)  # 40 full-forward tokens
            return 40 / (time.perf_counter() - t0)

        def tokens_now():
            return profiler.get_counters().get("decode_tokens", 0)

        baseline_tps = max(baseline_tps, baseline_round())
        n_requests = 36 if fast else 48
        churn = []
        for i in range(n_requests):
            p = prompts[i % len(prompts)]
            # staggered lengths churn the retirement order
            churn.append(engine.generate(
                p, max_new_tokens=24 + 8 * (i % 4)
            ))
        samples = [(time.perf_counter(), tokens_now())]
        while not all(s.done for s in churn):
            time.sleep(0.05)
            samples.append((time.perf_counter(), tokens_now()))
        samples.append((time.perf_counter(), tokens_now()))
        decode_tokens_total = 0
        for s in churn:
            try:
                decode_tokens_total += len(s.tokens(timeout=300))
            except Exception:  # noqa: BLE001 - counted, fails the probe
                churn_errors += 1
        from bench import best_window_rate

        decode_tps = best_window_rate(samples, 0.7)
        baseline_tps = max(baseline_tps, baseline_round())
        c_end = profiler.get_counters()
        # the steady-recompile delta covers EVERYTHING since warmup:
        # parity, prefix hits/misses, chunked admits, evictions, churn
        steady = (c_end.get("serving_steady_recompiles", 0)
                  - c_warm.get("serving_steady_recompiles", 0))
        speedup = decode_tps / baseline_tps
        report["throughput"] = {
            "streams": slots,
            "requests": n_requests,
            "decode_tokens": decode_tokens_total,
            "decode_tps": round(decode_tps, 1),
            "baseline_tps": round(baseline_tps, 1),
            "speedup": round(speedup, 2),
        }
        report["strict"] = {
            "steady_recompiles": int(steady),
            "churn_errors": churn_errors,
            "gate_armed": True,
        }
        if churn_errors:
            failures.append("%d churned streams failed" % churn_errors)
        if steady != 0:
            failures.append("%d steady-state recompiles" % steady)
        if speedup < 10.0:
            failures.append("speedup %.2f < 10x" % speedup)

        # ---- speculation (ISSUE 16) ----
        # A second engine on the same params: block 16, chunked windows (chunk 16), a 4-block
        # zero-copy prefix store, and the k=4 speculative verify with a
        # swappable drafter. max_len shrinks by k-1 so verify positions
        # stay inside the model's position table.
        from paddle_tpu.serving.decode import _ngram_draft

        draft = {"fn": _ngram_draft}
        engine2 = DecodeEngine(
            cfg, scope=scope, slots=slots, max_len=max_len - 3,
            param_program=infer, block_size=16, spec_tokens=4,
            prefill_chunk=prefill_chunk,
            prefix_cache_mb=4 * gpt.paged_block_bytes(cfg, 16) / 2.0 ** 20,
            drafter=lambda h, k: draft["fn"](h, k),
        ).start()
        spec_warm = profiler.get_counters()
        paged_parity = {}
        # miss + chunked: a 40-token prompt tiles as 16/16/8 windows
        p_long = list(rs.randint(0, cfg.vocab_size, 40))
        full_long = oracle(p_long)
        s = engine2.generate(p_long, max_new_tokens=6)
        paged_parity["miss"] = (
            s.tokens(timeout=120) == full_long[40:46]
            and s.cached_prefix_tokens == 0
        )
        paged_parity["chunked_windows"] = s.admit_windows == 3
        # zero-copy hit: 2 whole blocks of the same prompt
        s = engine2.generate(p_long, max_new_tokens=6)
        paged_parity["hit"] = (
            s.tokens(timeout=120) == full_long[40:46]
            and s.cached_prefix_tokens == 32
        )
        # resume: re-prefill prompt + suffix, continue token-exact
        s = engine2.generate(p_long, max_new_tokens=6,
                             resume_tokens=full_long[40:43])
        paged_parity["resume"] = s.tokens(timeout=120) == full_long[43:46]
        # eviction churn: 8 distinct 40-token prompts publish 16 blocks
        # into the 4-block store; the first prompt's re-admission falls
        # through to full prefill, still exact
        ev_p = [list(rs.randint(0, cfg.vocab_size, 40)) for _ in range(8)]
        for q in ev_p:
            engine2.generate(q, max_new_tokens=2).tokens(timeout=120)
        paged_parity["evictions"] = engine2.pindex.evictions >= 1
        s = engine2.generate(ev_p[0], max_new_tokens=4)
        paged_parity["evicted_readmit"] = (
            s.tokens(timeout=120)
            == oracle(ev_p[0])[40:44]
        )
        report["paged_parity"] = {k: bool(v)
                                  for k, v in paged_parity.items()}
        if not all(paged_parity.values()):
            failures.append("paged parity: %r" % paged_parity)

        # speculative speedup: identical workload through the SAME
        # engine at verify width 1 and at full width, drafting the
        # width-1 run's recorded continuations at 90% accuracy — greedy
        # determinism makes the recordings the exact future, so the
        # ratio isolates speculation (same paged step, same pool, same
        # gathers) and prices fused verify + rollback at that
        # acceptance.
        # Load-robust like the 10x bar: best sliding window both sides.
        spec_pool = [list(rs.randint(0, cfg.vocab_size, 12))
                     for _ in range(6)]
        n_spec = 32 if fast else 40
        spec_new = 72  # decode-dominated rounds: 12+72 < max_len-3

        def spec_round(eng):
            hs = [eng.generate(spec_pool[i % len(spec_pool)],
                               max_new_tokens=spec_new)
                  for i in range(n_spec)]
            samples = [(time.perf_counter(), tokens_now())]
            while not all(h.done for h in hs):
                time.sleep(0.02)
                samples.append((time.perf_counter(), tokens_now()))
            samples.append((time.perf_counter(), tokens_now()))
            for h in hs:
                h.tokens(timeout=300)
            return best_window_rate(samples, 0.5), hs

        engine2.set_spec_width(1)
        base_tps, base_hs = spec_round(engine2)
        recorded = {}
        for h in base_hs:
            recorded[tuple(h.prompt_ids)] = (
                list(h.prompt_ids) + h.tokens(timeout=10)
            )
        engine2.set_spec_width(4)
        drs = np.random.RandomState(11)

        def replay_draft(hist, k):
            fullc = recorded.get(tuple(hist[:12]))
            if fullc is None:
                return [0] * k
            d = list(fullc[len(hist):len(hist) + k])
            d += [0] * (k - len(d))
            return [t if drs.random_sample() < 0.9
                    else (int(t) + 1) % cfg.vocab_size for t in d]

        draft["fn"] = replay_draft
        spec_tps, spec_hs = spec_round(engine2)
        spec_parity = all(
            list(h.prompt_ids) + h.tokens(timeout=10)
            == recorded[tuple(h.prompt_ids)]
            for h in spec_hs
        )
        st2 = engine2.stats()
        spec_gain = spec_tps / max(base_tps, 1e-9)
        spec_steady = (profiler.get_counters()
                     .get("serving_steady_recompiles", 0)
                     - spec_warm.get("serving_steady_recompiles", 0))
        report["spec"] = {
            "base_tps": round(base_tps, 1),
            "spec_tps": round(spec_tps, 1),
            "spec_gain": round(spec_gain, 2),
            "spec_parity": bool(spec_parity),
            "acceptance": round(st2.get("spec_acceptance", 0.0), 3),
            "drafted": st2["spec_drafted"],
            "accepted": st2["spec_accepted"],
            "steady_recompiles": int(spec_steady),
            "pool": st2["paged"],
        }
        if not spec_parity:
            failures.append("spec streams diverged from the width-1 run")
        if st2.get("spec_acceptance", 0.0) <= 0.5:
            failures.append(
                "spec acceptance %.3f <= 0.5 at 90%% draft accuracy"
                % st2.get("spec_acceptance", 0.0)
            )
        # CPU bar: the width-k verify tick pays ~2x the width-1 tick
        # here (per-token forward compute is not free on host), so the
        # host-side ceiling at ~0.75 acceptance is ~1.6x; the >= 2x
        # acceptance criterion is carried by the accelerator bench rung
        # (gpt_decode_spec), where verify FLOPs ride idle MXU capacity.
        if spec_gain < 1.3:
            failures.append(
                "speedup from speculation %.2fx < 1.3x over the same "
                "engine at width 1 on the identical workload"
                % spec_gain
            )
        if spec_steady != 0:
            failures.append(
                "%d steady-state recompiles in the paged/spec schedule"
                % spec_steady
            )

        # ---- metrics on the exporter registry ----
        rendered = obs_registry.render_prometheus()
        gauges = obs_registry.gauge_values()
        need = ("decode_tokens", "decode_steps", "decode_prefills",
                "decode_requests", "decode_step_ms", "decode_prefill_ms",
                "decode_ttft_ms", "decode_intertoken_ms",
                "decode_prefix_hits", "decode_prefix_misses",
                "decode_prefix_cached_tokens", "decode_prefix_evictions",
                "decode_spec_drafted", "decode_spec_accepted",
                "serving_slot_admissions", "serving_slot_retirements")
        missing = [m for m in need if m not in rendered]
        for g in ("serving_slot_occupancy", "decode_queue_depth",
                  "decode_blocks_free", "decode_blocks_shared",
                  "decode_spec_acceptance"):
            if g not in gauges:
                missing.append(g)
        report["metrics"] = {"missing": missing}
        if missing:
            failures.append("metrics missing: %r" % missing)
    finally:
        engine.stop()
        if "engine2" in locals():
            engine2.stop()

    report["pass"] = not failures
    report["failures"] = failures
    if verbose:
        print(json.dumps(report, indent=1), file=sys.stderr)
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="tier-1 budget subset (< 30 s)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    report = run_probe(fast=args.fast, verbose=args.verbose)
    print("REPORT " + json.dumps(report, sort_keys=True), flush=True)
    print("PROBE PASS" if report["pass"]
          else "PROBE FAIL: %s" % "; ".join(report["failures"]))
    return 0 if report["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
