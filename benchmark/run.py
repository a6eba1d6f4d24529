#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process sets up (build, seeded weights, every compile, warm-up,
ramp), opens a window of ``--seconds``, and prints as its last line of
standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, ``breakdown`` in a traced run, and last
``checks``: every number that decided ``correct`` beside its limit.
Everything else goes to earlier lines (a serving cell's ``pace`` line
among them: ticks, windows, the load generator's clocks). Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result: there is
no CPU fallback.

  python3 benchmark/run.py --workload <name> --rehearse

runs the same control flow on the CPU at toy widths, Pallas kernels in
interpret mode, four virtual devices for a four-chip cell. It prints no
device metric and never ``"correct": true``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def log(kind, **facts):
    """An earlier line: one JSON object on standard output."""
    print(json.dumps(dict(note=kind, **facts), default=str), flush=True)


class Context(object):
    """What a traffic kind is handed: the cell, the run's arguments, the
    place, and the harness's clocks and counters."""

    def __init__(self, cell, args, devices):
        import paddle_tpu.fluid as fluid

        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        self.devices = devices[:cell.chips]
        self.config, self.traffic = cell.config, cell.traffic
        if self.rehearse:
            self.config = cell.family.toy(self.config)
            self.traffic = cell.kind.toy(self.traffic)
        on_tpu = devices[0].platform == "tpu"
        self.place = fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace()
        self.setup_s = None
        self.note = log
        self.programs = []
        self._watch_compiles()

    def _watch_compiles(self):
        """Note each program the executor compiles with the bytes the
        compiler says it needs on a device (``memory_analysis()``): the
        size of a cell that does not depend on what the allocator
        reports. The executor hands ``xla_stats.on_xla_compile`` the
        executable; this listens there and changes nothing."""
        from paddle_tpu.observability import xla_stats

        told = xla_stats.on_xla_compile

        def listen(*a, **kw):
            compiled = kw.get("compiled")
            try:
                m = compiled.memory_analysis()
                self.programs.append({
                    "arguments": m.argument_size_in_bytes,
                    "outputs": m.output_size_in_bytes,
                    "temp": m.temp_size_in_bytes,
                    "aliased": m.alias_size_in_bytes,
                    "total": m.argument_size_in_bytes
                    + m.output_size_in_bytes + m.temp_size_in_bytes
                    - m.alias_size_in_bytes})
            except Exception as e:  # noqa: BLE001 - a note, not a gate
                self.programs.append({"error": repr(e)})
            return told(*a, **kw)

        xla_stats.on_xla_compile = listen

    def open_window(self):
        """Set-up ends here. -> the window's start (``perf_counter``)."""
        now = time.perf_counter()
        self.setup_s = now - T_START
        log("window_open", setup_s=self.setup_s)
        return now

    def counters(self):
        from paddle_tpu.fluid import profiler

        return profiler.get_counters()

    def counters_since(self, before,
                       names=("xla_compiles", "serving_steady_recompiles")):
        now = self.counters()
        return {k: now.get(k, 0) - before.get(k, 0) for k in names}

    def memory_peak(self):
        """Peak bytes on the fullest chip. ``memory_stats()`` counts the
        arrays the process holds, not the scratch space a running program
        takes (PERF.md, Findings PR 24: it read the same whatever the
        step), so the peak is what jax reports or, where that is more,
        the bytes held plus the largest program's temporaries as
        ``memory_analysis()`` gives them."""
        stats = [d.memory_stats() or {} for d in self.devices]
        log("memory_stats", per_device=[
            {k: s.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                   "largest_alloc_size", "bytes_limit")}
            for s in stats])
        sized = [p for p in self.programs if "total" in p]
        temp = 0
        if sized:
            largest = max(sized, key=lambda p: p["temp"])
            log("largest_program", **largest)
            temp = largest["temp"]
        peaks = [max(s["peak_bytes_in_use"], s.get("bytes_in_use", 0) + temp)
                 for s in stats if s.get("peak_bytes_in_use") is not None]
        return max(peaks) if peaks else None


def _fail(msg):
    sys.exit("benchmark/run.py: " + msg)


def start(args):
    """Settle platform, cache and chips before anything touches a
    backend. -> (cell, context); exits non-zero without the chips."""
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        _fail("no system under test beside %s: nothing ran" % BENCH_DIR)
    sys.path.insert(0, ROOT)
    from benchmark.harness import cells

    cell = cells.Cell(args.workload)
    if args.seconds is None:
        args.seconds = 2.0 if args.rehearse else cell.manifest["run_seconds"]

    # platform and cache are settled before anything touches a backend
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = "--xla_force_host_platform_device_count"
        if cell.chips > 1 and flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = "%s %s=%d" % (
                os.environ.get("XLA_FLAGS", ""), flag, cell.chips)
    from paddle_tpu import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        _fail("jax.devices()[0] is %r, not a TPU: nothing ran (rehearse "
              "on the CPU with --rehearse)" % (devices[0],))
    if len(devices) < cell.chips:
        _fail("workload %s needs %d chip(s), jax sees %d"
              % (cell.name, cell.chips, len(devices)))
    from benchmark.harness import peaks

    device_peaks = None if args.rehearse else peaks.peaks_for(
        devices[0].device_kind)
    log("start", workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, cache_dir=cache_dir,
        imports_s=time.perf_counter() - T_START)

    ctx = Context(cell, args, devices)
    ctx.peaks = device_peaks
    return cell, ctx


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell, ctx = start(args)
    from benchmark.harness import checks, reduce

    devices, device_peaks = ctx.devices, ctx.peaks
    facts = cell.kind.run(ctx)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": cell.chips,
              "memory_peak_bytes": facts["memory_peak_bytes"]}
    values = dict(facts["values"], setup_s=ctx.setup_s)
    breakdown = None
    if args.trace:
        t = [time.perf_counter()]
        evidence = reduce.Evidence(ctx, facts, device_peaks)
        t.append(time.perf_counter())
        log("trace_shape", **evidence.shape())
        t.append(time.perf_counter())
        values = reduce.read_layer_metrics(cell, evidence, log)
        t.append(time.perf_counter())
        device.update(evidence.device_times())
        breakdown = evidence.breakdown()
        t.append(time.perf_counter())
        evidence.discard()
        log("reduce_seconds", **dict(zip(
            ("load", "shape", "layer_metrics", "breakdown"),
            (b - a for a, b in zip(t, t[1:])))))
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values.get(m["name"]) is not None}

    ok = checks.correct(facts["checks"])
    numbers = checks.summary(facts["checks"])
    log("checks", reference_s=facts.get("reference_s"),
        **facts["checks"]["detail"])
    result = {"correct": ok, "attempted": facts["attempted"],
              "failed": facts["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        # a rehearsal is not a chip run: no device metric, never correct
        result = {"correct": False,
                  "rehearsal": "completed",
                  "attempted": facts["attempted"],
                  "failed": facts["failed"],
                  "metric_names": sorted(metrics), "device": device}
    result["checks"] = numbers
    for name, (value, limit) in sorted(numbers.items()):
        print("check %s = %r (limit %r)" % (name, value, limit),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
