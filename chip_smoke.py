#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls, at GPT-2-small's full width (weights random, made from --seed):

  train   gpt.build_gpt_lm_train (stock GPTConfig, seq 1024, batch 8, bf16
          AMP, flash attention) on fluid.Executor(TPUPlace(0)): startup + 3
          steps on one fixed batch.
  serve   build_gpt_infer -> save_inference_model -> AnalysisPredictor ->
          DecodeEngine(slots 8, max_len 1024, block 16, flash on, so the
          T = 1 step is the paged Pallas kernel) -> InferenceServer ->
          Gateway; 4 concurrent POST /v1/generate SSE streams, judged
          token by token against the dense full-forward program under
          teacher forcing on the same device.
  resnet  resnet.build_resnet_train depth 50, batch 64, bf16 AMP, 3 steps
          (the NHWC conv lowering exists only on the TPU backend).

Each phase prints one JSON line; a failed check makes the exit code
non-zero and no exception is stepped over. The last line of stdout is
exactly {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
None of the numbers printed here is a speed.

  python chip_smoke.py             one chip; fails at once without a TPU
  python chip_smoke.py --chips 4   only what exists across chips, each
                                   beside its one-device comparison:
                                   with_mesh DP=4 + FSDP training, and a
                                   DecodeEngine(tp=4)
  python chip_smoke.py --rehearse [--chips 4]
                                   the same control flow on the CPU at toy
                                   widths, Pallas kernels in interpret
                                   mode; never prints "ok": true
"""

import argparse
import copy
import gc
import json
import os
import sys
import tempfile
import threading
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# Teacher-forced acceptance: a served token passes when the oracle's logit
# for it is within SERVE_TOL of the oracle's maximum at that position. On
# the chip fp32 matmuls run on the MXU at default precision, so engine and
# oracle logits differ in the low bits and a near-tie may flip. Largest
# gap seen on TPU v5 lite (PR 21): 0.0 on one chip (128 of 128 tokens the
# oracle's argmax), 8.98e-4 under tp=4 (127 of 128); the bound is ~5x that.
# The rehearsal holds the CPU, which is bit-exact, to 0.
SERVE_TOL = 5e-3
# |loss(mesh) - loss(device 0)| per step, DP=4 + FSDP against one device.
# Measured on four TPU v5 lite chips (PR 21): 4.96e-5; the bound is 10x.
MESH_LOSS_TOL = 5e-4

FULL = dict(
    gpt={}, seq=1024, batch=8, slots=8, max_len=1024, block=16,
    prompt=(32, 128), new_tokens=32, mesh_batch=16,
    resnet=dict(depth=50, class_num=1000, image_size=224), resnet_batch=64,
)
TOY = dict(
    gpt=dict(vocab_size=211, hidden_size=64, num_layers=2, num_heads=4,
             intermediate_size=128, max_position_embeddings=64),
    seq=32, batch=4, slots=4, max_len=64, block=4,
    prompt=(4, 16), new_tokens=8, mesh_batch=8,
    resnet=dict(depth=18, class_num=10, image_size=32), resnet_batch=4,
)


class Run(object):
    """What every phase needs: sizes, the seed, the place, and the
    check/report plumbing."""

    def __init__(self, sizes, seed, rehearse):
        import jax

        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import native

        self.size = sizes
        self.seed = seed
        self.rehearse = rehearse
        self.devices = jax.devices()
        self.on_tpu = self.devices[0].platform == "tpu"
        self.place = fluid.TPUPlace(0) if self.on_tpu else fluid.CPUPlace()
        self.device = fluid.core.get_jax_device(self.place)
        self.native = native.available()

    def gpt_cfg(self):
        from paddle_tpu.models import gpt

        cfg = gpt.GPTConfig(hidden_dropout=0.0, attention_dropout=0.0,
                            use_flash_attention=True, **self.size["gpt"])
        # the rehearsal has no Mosaic: same kernels, Pallas interpreter
        cfg.flash_interpret = self.rehearse
        return cfg

    def engine(self, cfg, scope, infer, **kw):
        """The paged decode engine every serving phase runs, on this
        run's place, reading ``infer``'s parameters from ``scope``."""
        from paddle_tpu.serving.decode import DecodeEngine

        return DecodeEngine(
            cfg, place=self.place, scope=scope, slots=self.size["slots"],
            max_len=self.size["max_len"], block_size=self.size["block"],
            prefill_buckets=[self.size["prompt"][1]], param_program=infer,
            **kw)

    def report(self, phase, failures, **facts):
        """The phase's one JSON line; a failed check ends the run."""
        stats = self.device.memory_stats() or {}
        print(json.dumps(dict(
            phase=phase, ok=not failures,
            device_kind=self.device.device_kind,
            peak_bytes_in_use=stats.get("peak_bytes_in_use"),
            native_lib=self.native, **facts,
        )), flush=True)
        if failures:
            sys.exit("chip_smoke: phase %s failed: %s"
                     % (phase, "; ".join(failures)))


def _compile_s():
    from paddle_tpu.observability import xla_stats

    return xla_stats.summary()["compile_ms_total"] / 1e3


def _compile_records(program):
    """xla_stats compile records (one per executable) of one Program."""
    from paddle_tpu.observability import xla_stats

    label = xla_stats.program_label(program)
    return [r for r in xla_stats.get_records()
            if r["kind"] == "compile" and r["key"]["program"] == label]


def _step_records(engine):
    """Compile records of the paged engine's T = 1 step (under tp the
    session holds it wrapped in a CompiledProgram)."""
    step = engine.session._paged_step[1][0]
    return _compile_records(getattr(step, "program", step))


def _pallas_calls(records):
    return sum((r["census"] or {}).get("pallas_calls", 0) for r in records)


def _off_device(scope, program, device):
    """Names of the program's parameters whose array is not wholly on
    ``device``."""
    import jax

    bad = []
    for v in program.list_vars():
        arr = scope.get(v.name) if v.is_parameter else None
        if arr is not None and not (isinstance(arr, jax.Array)
                                    and arr.devices() == {device}):
            bad.append(v.name)
    return bad


def _lm_batch(cfg, batch, seq, seed):
    import numpy as np

    rs = np.random.RandomState(seed)
    return {
        "ids": rs.randint(0, cfg.vocab_size, (batch, seq, 1)).astype("int64"),
        "pos_ids": np.tile(np.arange(seq)[None, :, None], (batch, 1, 1))
        .astype("int64"),
        "input_mask": np.ones((batch, seq, 1), "float32"),
    }


def _train_steps(exe, program, startup, feed, loss, steps):
    """Seeded startup + ``steps`` steps in a fresh scope; returns (losses,
    xla compile count after each step, scope)."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import profiler

    scope = fluid.core.Scope()
    exe.run(startup, scope=scope)
    losses, compiles = [], []
    for _ in range(steps):
        (lv,) = exe.run(program, feed=feed, fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
        compiles.append(profiler.get_counters().get("xla_compiles", 0))
    return losses, compiles, scope


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_train(run):
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import gpt

    cfg = run.gpt_cfg()
    with fluid.unique_name.guard():
        main, startup, _feeds, loss = gpt.build_gpt_lm_train(
            cfg, run.size["seq"], learning_rate=3e-4, use_amp=True)
    main.random_seed = startup.random_seed = run.seed
    t0 = _compile_s()
    losses, compiles, scope = _train_steps(
        fluid.Executor(run.place), main, startup,
        _lm_batch(cfg, run.size["batch"], run.size["seq"], run.seed),
        loss, steps=3)
    records = _compile_records(main)
    calls = _pallas_calls(records)
    fail = []
    if not np.all(np.isfinite(losses)):
        fail.append("non-finite loss")
    if not losses[2] < losses[0]:
        fail.append("loss did not fall over 3 steps")
    off = _off_device(scope, main, run.device)
    if off:
        fail.append("parameters off %s: %s" % (run.device, off[:3]))
    if len(records) != 1 or compiles[2] != compiles[0]:
        fail.append("want one compile and none in steps 2-3, got %d "
                    "record(s), counter %r" % (len(records), compiles))
    # forward, dq and dkv kernel per layer: fewer means a kernel gave way
    # to the reference (the interpreter leaves no custom call to count)
    if run.on_tpu and calls < 3 * cfg.num_layers:
        fail.append("%d Pallas custom calls in the step, want >= %d"
                    % (calls, 3 * cfg.num_layers))
    run.report("train", fail, losses=losses, pallas_calls=calls,
               compile_s=round(_compile_s() - t0, 1))


def _sse_generate(url, prompt, new_tokens):
    """POST /v1/generate and assemble the SSE stream -> (tokens, done)."""
    req = urllib.request.Request(
        url, headers={"Content-Type": "application/json"},
        data=json.dumps({"prompt_ids": prompt,
                         "max_new_tokens": new_tokens}).encode())
    toks, done = [], None
    with urllib.request.urlopen(req, timeout=600) as resp:
        for line in resp:
            line = line.decode().strip()
            if line.startswith("data: "):
                event = json.loads(line[len("data: "):])
                if "token" in event:
                    toks.append(event["token"])
                else:
                    done = event
    return toks, done


def _prompts(run, cfg, n):
    import numpy as np

    rs = np.random.RandomState(run.seed + 1)
    lo, hi = run.size["prompt"]
    return [[int(t) for t in rs.randint(0, cfg.vocab_size,
                                        rs.randint(lo, hi + 1))]
            for _ in range(n)]


def _oracle(run, cfg, length):
    """The reference the served tokens are held to: the DENSE full-forward
    program (no kernel, no cache), seeded like the served model.
    -> (program, feed names, logits var, exe, scope with its params)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import gpt

    dense = copy.copy(cfg)
    dense.use_flash_attention = False
    with fluid.unique_name.guard():
        infer, startup, feed_names, logits = gpt.build_gpt_infer(
            dense, length)
    startup.random_seed = run.seed
    exe = fluid.Executor(run.place)
    scope = fluid.core.Scope()
    exe.run(startup, scope=scope)
    return infer, feed_names, logits, exe, scope


def _teacher_forced(oracle, length, streams):
    """Feed each stream's prompt + emitted tokens through the oracle once.
    -> (tokens that are the oracle's argmax, largest gap between the
    oracle's maximum logit and its logit for the emitted token)."""
    import numpy as np

    infer, _names, logits, exe, scope = oracle
    n = len(streams)
    ids = np.zeros((n, length, 1), "int64")
    mask = np.zeros((n, length, 1), "float32")
    for b, (prompt, toks) in enumerate(streams):
        full = prompt + toks
        ids[b, :len(full), 0] = full
        mask[b, :len(full), 0] = 1.0
    pos = np.tile(np.arange(length)[None, :, None], (n, 1, 1)).astype("int64")
    (lv,) = exe.run(infer, feed={"ids": ids, "pos_ids": pos,
                                 "input_mask": mask},
                    fetch_list=[logits], scope=scope)
    lv = np.asarray(lv, "float32")
    exact, worst = 0, 0.0
    for b, (prompt, toks) in enumerate(streams):
        for i, tok in enumerate(toks):
            row = lv[b, len(prompt) - 1 + i]
            exact += int(tok == int(row.argmax()))
            worst = max(worst, float(row.max() - row[tok]))
    return exact, worst


def phase_serve(run):
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import inference, serving
    from paddle_tpu.fluid import flags, profiler
    from paddle_tpu.observability import exporter

    cfg = run.gpt_cfg()
    new_tokens = run.size["new_tokens"]
    length = run.size["prompt"][1] + new_tokens
    prompts = _prompts(run, cfg, 4)
    oracle = _oracle(run, cfg, length)
    infer, feed_names, logits, exe, scope = oracle
    t0 = _compile_s()
    tol = 0.0 if run.rehearse else SERVE_TOL
    flags.set_flags({"FLAGS_serving_strict_compiles": True,
                     "FLAGS_obs_http_port": 0})
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as model_dir:
        # the stack a user deploys: export -> predictor (/v1/infer) +
        # decode engine (/v1/generate) -> server -> gateway
        with fluid.scope_guard(scope):
            fluid.io.save_inference_model(
                model_dir, feed_names, [logits], exe, main_program=infer)
        predictor = inference.create_paddle_predictor(
            inference.AnalysisConfig(model_dir))
        engine = run.engine(cfg, scope, infer)
        example = [np.zeros((1, length, 1), "int64"),
                   np.arange(length, dtype="int64").reshape(1, length, 1),
                   np.ones((1, length, 1), "float32")]
        server = serving.InferenceServer(
            predictor, max_batch_size=1, num_workers=1,
            decode_engine=engine).start(warmup_inputs=example)
        gateway = serving.Gateway(server, port=0).start()
        try:
            compile_s = _compile_s() - t0
            before = profiler.get_counters()
            url = "http://127.0.0.1:%d/v1/generate" % gateway.port
            results = [None] * len(prompts)

            def client(i):
                results[i] = _sse_generate(url, prompts[i], new_tokens)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            after = profiler.get_counters()
            with urllib.request.urlopen(
                    exporter.global_exporter().url("/metrics"),
                    timeout=30) as resp:
                metrics = resp.read().decode()
            step_records = _step_records(engine)
        finally:
            gateway.stop()
            server.stop()
            exporter.stop_global()
            flags.set_flags({"FLAGS_serving_strict_compiles": False,
                             "FLAGS_obs_http_port": -1})
    fail = []
    if any(r is None for r in results):
        fail.append("a stream did not finish")
        results = [r for r in results if r is not None]
    for toks, done in results:
        if not done or done.get("finish_reason") != "length" \
                or len(toks) != new_tokens:
            fail.append("stream ended %r after %d tokens" % (done, len(toks)))
    steady = {k: after.get(k, 0) - before.get(k, 0)
              for k in ("xla_compiles", "serving_steady_recompiles")}
    if any(steady.values()):
        fail.append("compiles after start(): %r" % steady)
    series = {line.split("{")[0].split(" ")[0]
              for line in metrics.splitlines() if not line.startswith("#")}
    want = {"decode_tokens", "decode_steps", "decode_blocks_free"}
    if run.on_tpu:  # the CPU backend has no memory_stats()
        want |= {"xla_mem_bytes_in_use", "xla_mem_peak_bytes_in_use"}
    if not want <= series:
        fail.append("/metrics lacks %s" % sorted(want - series))
    calls = _pallas_calls(step_records)
    if run.on_tpu and calls < cfg.num_layers:
        fail.append("%d Pallas custom calls in the T=1 step, want >= %d"
                    % (calls, cfg.num_layers))
    off = _off_device(scope, infer, run.device)
    if off:
        fail.append("parameters off %s: %s" % (run.device, off[:3]))
    streams = [(prompts[i], toks) for i, (toks, _d) in enumerate(results)]
    exact, gap = _teacher_forced(oracle, length, streams)
    if gap > tol:
        fail.append("oracle gap %.6g exceeds tol %.6g" % (gap, tol))
    run.report("serve", fail, tokens=sum(len(t) for _p, t in streams),
               exact_tokens=exact, max_gap=gap, tol=tol,
               pallas_calls=calls, steady_compiles=steady["xla_compiles"],
               compile_s=round(compile_s, 1))


def phase_resnet(run):
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import resnet

    spec, batch = run.size["resnet"], run.size["resnet_batch"]
    with fluid.unique_name.guard():
        main, startup, _feeds, loss, _acc = resnet.build_resnet_train(
            use_amp=True, **spec)
    main.random_seed = startup.random_seed = run.seed
    rs = np.random.RandomState(run.seed)
    feed = {
        "img": rs.rand(batch, 3, spec["image_size"], spec["image_size"])
        .astype("float32"),
        "label": rs.randint(0, spec["class_num"], (batch, 1)).astype("int64"),
    }
    t0 = _compile_s()
    losses, compiles, scope = _train_steps(
        fluid.Executor(run.place), main, startup, feed, loss, steps=3)
    fail = []
    if not np.all(np.isfinite(losses)):
        fail.append("non-finite loss")
    off = _off_device(scope, main, run.device)
    if off:
        fail.append("parameters off %s: %s" % (run.device, off[:3]))
    if len(_compile_records(main)) != 1 or compiles[2] != compiles[0]:
        fail.append("want one compile and none in steps 2-3, counter %r"
                    % (compiles,))
    run.report("resnet", fail, losses=losses,
               compile_s=round(_compile_s() - t0, 1))


# ---------------------------------------------------------------------------
# four chips: only what exists across chips, beside what it is compared with
# ---------------------------------------------------------------------------

def _spread(scope, names):
    """(devices holding a shard of any named var, count of vars split over
    every one of the 4 devices)."""
    devices, split = set(), 0
    for name in names:
        arr = scope.get(name)
        if arr is None or not hasattr(arr, "addressable_shards"):
            continue
        held = {s.device for s in arr.addressable_shards}
        devices |= held
        if len(held) == 4 and not arr.is_fully_replicated:
            split += 1
    return devices, split


def _idle_devices(run):
    """Devices of the first four reporting no bytes in use (TPU only)."""
    return [str(d) for d in run.devices[:4]
            if d.memory_stats() is not None
            and not d.memory_stats().get("bytes_in_use", 0)]


def phase_mesh_train(run):
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import compiler
    from paddle_tpu.models import gpt

    cfg = run.gpt_cfg()
    with fluid.unique_name.guard():
        main, startup, _feeds, loss = gpt.build_gpt_lm_train(
            cfg, run.size["seq"], learning_rate=3e-4, use_amp=True)
    main.random_seed = startup.random_seed = run.seed
    feed = _lm_batch(cfg, run.size["mesh_batch"], run.size["seq"], run.seed)
    exe = fluid.Executor(run.place)
    t0 = _compile_s()
    # the same program, seed and batch: device 0 alone, then the mesh
    base, _c, scope = _train_steps(exe, main, startup, feed, loss, 2)
    del scope
    gc.collect()
    on_mesh = compiler.CompiledProgram(main).with_mesh(
        loss_name=loss.name, mesh_axes={"data": 4}, fsdp=True)
    got, _c, scope = _train_steps(exe, on_mesh, startup, feed, loss, 2)
    state = [v.name for v in main.list_vars()
             if getattr(v, "persistable", False)]
    devices, split = _spread(scope, state)
    gap = float(np.max(np.abs(np.subtract(got, base))))
    fail = []
    if not np.all(np.isfinite(got + base)):
        fail.append("non-finite loss")
    if gap > MESH_LOSS_TOL:
        fail.append("loss gap %.6g exceeds tol %.6g" % (gap, MESH_LOSS_TOL))
    if len(devices) != 4 or not split:
        fail.append("parameters/optimizer state on %d device(s), %d var(s) "
                    "split four ways" % (len(devices), split))
    if _idle_devices(run):
        fail.append("no bytes in use on %s" % _idle_devices(run))
    calls = _pallas_calls(_compile_records(main))
    # both runs compile the step: 3 kernels per layer each
    if run.on_tpu and calls < 6 * cfg.num_layers:
        fail.append("%d Pallas custom calls over both steps, want >= %d"
                    % (calls, 6 * cfg.num_layers))
    run.report("mesh_train", fail, losses_device0=base, losses_mesh=got,
               max_loss_gap=gap, tol=MESH_LOSS_TOL, vars_split_4way=split,
               pallas_calls=calls, compile_s=round(_compile_s() - t0, 1))


def phase_mesh_serve(run):
    from paddle_tpu.models import gpt

    cfg = run.gpt_cfg()
    new_tokens = run.size["new_tokens"]
    length = run.size["prompt"][1] + new_tokens
    prompts = _prompts(run, cfg, 4)
    tol = 0.0 if run.rehearse else SERVE_TOL
    t0 = _compile_s()
    fail, facts = [], {}
    streams_by_tp = {}
    for tp in (1, 4):
        # own seeded scope each: the tp engine commits its params to the
        # mesh, which must not move the one-device engine's
        infer, _names, _logits, _exe, scope = _oracle(run, cfg, length)
        engine = run.engine(cfg, scope, infer, tp=tp).start()
        try:
            handles = [engine.generate(p, max_new_tokens=new_tokens)
                       for p in prompts]
            streams_by_tp[tp] = [
                (p, [int(t) for t in h.tokens(timeout=900)])
                for p, h in zip(prompts, handles)]
            if tp == 4:
                sess = engine.session
                pools = [n for kv in gpt.paged_pool_names(
                    cfg, sess.pool_blocks, sess.block_size) for n in kv]
                params = [v.name for v in infer.list_vars()
                          if v.is_parameter]
                for what, names in (("KV pools", pools), ("params", params)):
                    devices, split = _spread(scope, names)
                    facts[what.split()[-1] + "_split_4way"] = split
                    if len(devices) != 4 or not split:
                        fail.append("%s on %d device(s), %d split four ways"
                                    % (what, len(devices), split))
                if _idle_devices(run):
                    fail.append("no bytes in use on %s" % _idle_devices(run))
                calls = _pallas_calls(_step_records(engine))
                if run.on_tpu and calls < cfg.num_layers:
                    fail.append("%d Pallas custom calls in the tp=4 T=1 "
                                "step, want >= %d" % (calls, cfg.num_layers))
        finally:
            engine.stop()
        del engine, scope
        gc.collect()
    oracle = _oracle(run, cfg, length)
    for tp, streams in sorted(streams_by_tp.items()):
        if any(len(t) != new_tokens for _p, t in streams):
            fail.append("tp=%d: a stream is short" % tp)
        exact, gap = _teacher_forced(oracle, length, streams)
        facts["tp%d_exact_tokens" % tp] = exact
        facts["tp%d_max_gap" % tp] = gap
        if gap > tol:
            fail.append("tp=%d oracle gap %.6g exceeds tol %.6g"
                        % (tp, gap, tol))
    facts["tokens_equal_tp1_tp4"] = sum(
        int(a == b)
        for (_p, t1), (_q, t4) in zip(streams_by_tp[1], streams_by_tp[4])
        for a, b in zip(t1, t4))
    run.report("mesh_serve", fail, tokens=4 * new_tokens, tol=tol,
               compile_s=round(_compile_s() - t0, 1), **facts)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy widths, kernels in interpret mode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # platform and cache are settled before anything touches a backend
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4 and "xla_force_host_platform_device_count" \
                not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, HERE)
    from paddle_tpu import compile_cache

    compile_cache.enable()
    import jax

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        sys.exit("chip_smoke: jax.devices()[0] is %r, not a TPU — nothing "
                 "ran (rehearse on the CPU with --rehearse)" % (devices[0],))
    if len(devices) < args.chips:
        sys.exit("chip_smoke: --chips %d but jax sees %d device(s)"
                 % (args.chips, len(devices)))

    run = Run(TOY if args.rehearse else FULL, args.seed, args.rehearse)
    phases = ((phase_train, phase_serve, phase_resnet) if args.chips == 1
              else (phase_mesh_train, phase_mesh_serve))
    for phase in phases:
        phase(run)
        gc.collect()  # drop the phase's device state before the next

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if args.rehearse:
        # a rehearsal is not a chip run and never says "ok": true
        print(json.dumps({"ok": False, "rehearsal": "passed",
                          "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
