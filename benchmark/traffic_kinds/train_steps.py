"""Training traffic: a fresh seeded batch every step, fed as a trainer
feeds it, one ``Executor.run`` a step with the loss fetched.

The traffic file gives ``seq_len``, ``batch`` and ``fields``: for each
field of a batch how it is drawn (``uniform_int`` below a configuration
key or a number; ``two_segments``: zeros then ones split at a random
point of each row; ``fixed_share``: ones in exactly ``share`` of the
places, zeros in the rest, shuffled) and its shape in terms of ``batch``
and ``seq_len``.
"""

import gc
import time

import numpy as np

from benchmark.harness import checks, tracing

CHECK_STEPS = 3


def toy(traffic):
    return dict(traffic, **traffic.get("toy", {}))


def batch_for(traffic, config, seed, step):
    """The batch of step ``step``: {field: int array}, from (seed, step)."""
    rng = np.random.default_rng([int(seed), int(step)])
    dims = {"batch": traffic["batch"], "seq_len": traffic["seq_len"]}
    out = {}
    for name in sorted(traffic["fields"]):
        spec = traffic["fields"][name]
        shape = tuple(dims[d] for d in spec["shape"])
        if spec["draw"] == "uniform_int":
            high = spec["high"]
            high = config[high] if isinstance(high, str) else high
            out[name] = rng.integers(0, high, shape, dtype=np.int64)
        elif spec["draw"] == "fixed_share":
            flat = np.zeros(int(np.prod(shape)), np.int64)
            flat[:int(round(spec["share"] * flat.size))] = 1
            out[name] = rng.permutation(flat).reshape(shape)
        elif spec["draw"] == "two_segments":
            cut = rng.integers(1, shape[-1], shape[:-1] + (1,))
            out[name] = (np.arange(shape[-1]) >= cut).astype(np.int64)
        else:
            raise ValueError("field %r: unknown draw %r"
                             % (name, spec["draw"]))
    return out


def first_steps(step, family, reference, config, traffic, seed):
    """Seeded weights in, then the first steps through the window's own
    call and feed. -> (the program's readings, the batches, seconds)"""
    times = {}
    t = time.perf_counter()
    params = reference.init_params(seed, config)
    step.set_params(params)
    times["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    batches = [batch_for(traffic, config, seed, i)
               for i in range(CHECK_STEPS)]
    got = {"losses": []}
    for i, batch in enumerate(batches):
        got["losses"].append(step.run(family.feed(batch)))
        if i == 0:
            times["first_step_s"] = time.perf_counter() - t
            got["gnorm"] = step.first_grad_norms()
    got["dnorm"] = step.delta_norms(params)
    times["check_steps_s"] = time.perf_counter() - t
    return got, batches, times


def reference_readings(reference, config, seed, batches, precision="highest"):
    """(losses, gnorm, dnorm) of the plain reference over the same rows."""
    return reference.train(
        config, reference.init_params(seed, config), batches,
        config["train"]["learning_rate"], precision=precision)


def run(ctx):
    """Set-up, window, then the comparison with the plain reference.
    -> the facts ``run.py`` turns into the result line."""
    cell, family, reference = ctx.cell, ctx.cell.family, ctx.cell.reference
    config, traffic = ctx.config, ctx.traffic

    t = time.perf_counter()
    step = family.build_train(config, traffic, ctx.place, ctx.rehearse)
    times = {"build_and_startup_s": time.perf_counter() - t}
    got, batches, more = first_steps(step, family, reference, config,
                                     traffic, ctx.seed)
    ctx.note("setup", **dict(times, **more))

    counters = ctx.counters()
    tokens_per_step = traffic["batch"] * traffic["seq_len"]
    tracer = tracing.MidWindow(ctx, traffic.get("trace_s", 3.0))
    steps, failed, i = 0, 0, CHECK_STEPS
    t0 = ctx.open_window()
    while True:
        now = time.perf_counter()
        if now - t0 >= ctx.seconds:
            break
        tracer.poll(now - t0)
        with tracer.annotate("bench_feed"):
            feed = family.feed(batch_for(traffic, config, ctx.seed, i))
        with tracer.annotate("bench_step"):
            loss = step.run(feed)  # fetching the loss waits for the step
        steps += 1
        failed += int(not np.isfinite(loss))
        i += 1
    t1 = time.perf_counter()
    tracer.finish()
    window = t1 - t0
    compiled = ctx.counters_since(counters)
    ctx.note("window", steps=steps, seconds=window, last_loss=loss,
             compiles_in_window=compiled)

    facts = {
        "attempted": steps, "failed": failed, "window": (t0, t1),
        "values": {"train_tok_per_s": steps * tokens_per_step / window},
        "steps": steps,
        "tracer": tracer,
        "memory_peak_bytes": ctx.memory_peak(),
    }
    step.close()
    del step
    gc.collect()

    # the plain reference follows the same three steps on the same rows
    t = time.perf_counter()
    ref = reference_readings(reference, config, ctx.seed, batches)
    facts["checks"] = checks.train(got, ref, cell.check_limits)
    facts["reference_s"] = time.perf_counter() - t
    return facts


def calibrate(ctx, seeds):
    """For each seed, in one process: the program's readings against the
    reference, and the control's (the reference one precision down, put
    in the program's place), each judged by the cell's committed limits
    and followed by its norms leaf by leaf. Training's readings need no
    window."""
    family, reference = ctx.cell.family, ctx.cell.reference
    config, traffic = ctx.config, ctx.traffic
    step = family.build_train(config, traffic, ctx.place, ctx.rehearse)
    seen = {}
    for seed in seeds:
        got, batches, _t = first_steps(step, family, reference, config,
                                       traffic, seed)
        seen[seed] = (got, batches)
    step.close()
    del step
    gc.collect()
    low = config["control_precision"]["train"]
    for seed in seeds:
        got, batches = seen[seed]
        ref = reference_readings(reference, config, seed, batches)
        ctl = reference_readings(reference, config, seed, batches, low)
        ctl = {"losses": ctl[0], "gnorm": ctl[1], "dnorm": ctl[2]}
        ctx.note("calibrate_leaves", seed=seed, who="reference",
                 gnorm=ref[1], dnorm=ref[2])
        for who, readings in (("program", got), ("control_" + low, ctl)):
            rows = checks.train(readings, ref, ctx.cell.check_limits)
            ctx.note("calibrate", seed=seed, who=who,
                     correct=checks.correct(rows),
                     **dict(checks.summary_values(rows), **rows["detail"]))
            ctx.note("calibrate_leaves", seed=seed, who=who,
                     gnorm=readings["gnorm"], dnorm=readings["dnorm"])
