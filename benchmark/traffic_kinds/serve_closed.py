"""Closed-loop serving traffic: ``clients`` callers, each sending its next
``POST /v1/generate`` (SSE) the moment the last one finished. The callers
run in a process of their own (``harness/loadgen.py``, started and
stopped by ``drive``), as a user's clients do: not on the server's
interpreter lock. Their records come back over a pipe once the window
and its tail are over.

The traffic file gives the prompt and output length distributions,
``size_set`` and ``schedule_seed``: that many (prompt length, output
length) pairs are laid out evenly over the two distributions and handed
out in an order, each pass through the set in a new one, that the
traffic file's ``schedule_seed`` fixes. ``--seed`` draws the token ids
(and the weights). So every seed offers the same work in the same order:
which long prompt stalls which streams decides a request's time per
token, and with a seeded order ``tpot_p90_ms`` repeated to 1 % for one
seed and differed by 10 % between seeds (PERF.md, Findings PR 24).
Greedy decoding.

The ramp is set-up: every client's first request is cut at a random
length of at most the shortest output (from ``schedule_seed`` too), so
the 64 streams do not retire in waves, and the window opens ``settle_s``
after the last client's first token of its SECOND request: a request cut
to a few tokens gives a time per token that is one stall over a few
gaps, and none of them ends inside the window.

``serve_tok_per_s`` counts every token by the share of its making that
lies inside the window (``tokens_in_window``): all streams step in one
tick, so whole tokens counted at their arrival move by a tick's worth,
1 % of a 40 s window, with where the window's edges fall between two
ticks. The window's end is aligned to nothing; the clients run on past
it until every stream has its next token, so that the tokens in the
making at the end can be shared out.
"""

import gc
import time

import numpy as np

from benchmark.harness import checks, loadgen, tracing
from benchmark.harness.loadgen import Plan, Record, size_set  # noqa: F401
from benchmark.harness.stats import median, percentile


def toy(traffic):
    return dict(traffic, **traffic.get("toy", {}))


def tpot_ms(rec):
    """Time per output token of one request: first to last token over the
    tokens after the first."""
    return 1e3 * (rec.times[-1] - rec.times[0]) / (len(rec.times) - 1)


def tokens_in_window(records, t0, t1):
    """Tokens served in [t0, t1], over all requests whether or not they
    ended there. A token is made between the one before it (for a
    request's first token: the POST) and its own arrival, and counts by
    the share of that time that lies inside the window: 1 for all but
    the tokens in the making at the window's two edges. A closed-loop
    client's requests follow one another without a pause, so these
    spans tile its time, and the sum does not depend on where between
    two ticks an edge falls."""
    total = 0.0
    for r in records:
        prev = r.sent
        for x in list(r.times):
            if x > t0 and prev < t1:
                if x > prev:
                    total += (min(x, t1) - max(prev, t0)) / (x - prev)
                elif x <= t1:
                    total += 1.0
            prev = x
    return total


ROWS_PER_BLOCK = 8


def served_gaps(reference, config, params, sample, dtype="highest"):
    """For every served token of the requests in ``sample``, how far its
    logit lies below the reference's best at that position (``dtype="bf16"``:
    of the token the lower precision puts first, the control). The
    reference runs once over each prompt + served tokens, in blocks of
    rows so that the logits fit."""
    width = max(len(r.prompt) + len(r.tokens) for r in sample)
    width = -(-width // 128) * 128
    gaps = []
    for at in range(0, len(sample), ROWS_PER_BLOCK):
        block = sample[at:at + ROWS_PER_BLOCK]
        ids = np.zeros((ROWS_PER_BLOCK, width), np.int64)
        for b, r in enumerate(block):
            row = r.prompt + r.tokens
            ids[b, :len(row)] = row
        got = np.asarray(reference.served_gaps(config, params, ids, dtype))
        for b, r in enumerate(block):
            first = len(r.prompt) - 1
            gaps += [float(g) for g in got[b, first:first + len(r.tokens)]]
    return gaps


def in_order(finished):
    """Every finished request is compared, in the order it was sent."""
    return sorted(finished, key=lambda r: r.idx)


def drive(ctx, stack, seed, seconds, trace_s=None):
    """Ramp (set-up), then a window of ``seconds``. The clients run in a
    child process (``loadgen.Child``): it is started here, killed here
    whatever happens, and hands back its records once the window and the
    tail are over. The window, the tracer, the counters and the spans
    are this process's.
    -> what the window left: records, clocks, counters, spans."""
    from paddle_tpu.observability import trace as program_trace

    config, traffic = ctx.config, ctx.traffic
    t = time.perf_counter()
    plan = Plan(traffic, config["vocab_size"], seed)
    load = loadgen.Child(traffic, config["vocab_size"], seed,
                         stack.host, stack.port)
    try:
        ctx.note("loadgen", pid=load.pid, **load.clock_facts())
        load.start()
        time.sleep(traffic["settle_s"])
        ramp_s = time.perf_counter() - t

        before = ctx.counters()
        tracer = tracing.MidWindow(ctx,
                                   trace_s or traffic.get("trace_s", 1.5))
        t0 = ctx.open_window()
        t1 = t0 + seconds
        overslept = []  # this loop's own, in the server's process
        while True:
            now = time.perf_counter()
            if now >= t1:
                break
            tracer.poll(now - t0)
            load.check()
            a, nap = time.perf_counter(), min(0.02, t1 - now)
            time.sleep(nap)
            overslept.append([a, time.perf_counter() - a - nap])
        tracer.finish()
        after = ctx.counters()
        # the window is over; wait for each stream's next token, which
        # was in the making at t1 (tokens_in_window)
        load.end(t1)
        tail_s = time.perf_counter() - t1
        left = load.stop()
        records = load.records(plan)
    finally:
        load.close()
    spans = [s for s in program_trace.get_spans()
             if s["end"] >= t0 and s["start"] <= t1]
    in_window = [r for r in records if r.ended is not None
                 and t0 <= r.ended <= t1]
    return {"records": records, "window": (t0, t1), "ramp_s": ramp_s,
            "tail_s": tail_s,
            "tracer": tracer, "spans": spans, "stuck": left["stuck"],
            "loadgen": dict(load.clock_facts(), loadgen_cpu_s=left["cpu_s"],
                            loadgen_wall_s=left["wall_s"]),
            "stalls": {"loadgen": left["stalls"], "window_loop": overslept},
            "counters": {k: after.get(k, 0) - before.get(k, 0)
                         for k in after},
            "finished": [r for r in in_window if r.ok],
            "failed": [r for r in in_window if not r.ok]}


def pace(got):
    """What pace the window ran at, for an earlier line of every run: a
    run far from its siblings can be explained after the fact. Ticks and
    prompt windows from the program's spans, the child's clocks, and how
    long after the engine made a request's first token (``decode_request``'s
    ``first_token``, the one stamp a token has on the server's side) the
    client had it: the flush, the wire and the client's read, on one
    clock; and the longest POST-to-first-token of the window with the
    count of those over a second (a connect that the server's listen
    backlog dropped is sent again after 1 s: ``loadgen.START_GAP_S``).
    The done event names the request, ``gateway_request`` ties the
    name to the trace the engine's record lies under. Whose stall it was,
    where a run lost seconds: the longest time between two decode ticks
    (the server), the longest time in which no client got a token, with
    where in the window it began, and the longest oversleep of a thread of
    the child (``loadgen.Stalls``) and of the window's own loop in the
    server's process: all four alike, and the machine stood still."""
    t0, t1 = got["window"]
    spans = [s for s in got["spans"] if s["start"] >= t0 and s["end"] <= t1]
    starts = sorted(s["start"] for s in spans if s["name"] == "decode_tick")
    gaps = [1e3 * (b - a) for a, b in zip(starts, starts[1:])]
    trace_of = {s["args"].get("request_id"): s["trace_id"] for s in spans
                if s["name"] == "gateway_request" and s.get("trace_id")}
    made = {s["trace_id"]: s["args"].get("first_token") for s in spans
            if s["name"] == "decode_request"}
    waits = []
    for r in got["finished"]:
        at = made.get(trace_of.get(r.done.get("request_id")))
        if at is not None and r.times:
            waits.append(1e3 * (r.times[0] - at))
    ttfts = [1e3 * (r.times[0] - r.sent) for r in got["records"]
             if r.times and t0 <= r.times[0] <= t1]
    heard = [t0] + sorted(x for r in got["records"] for x in r.times
                          if t0 <= x <= t1) + [t1]
    silence, silent_from = max((b - a, a) for a, b in zip(heard, heard[1:]))
    return dict(
        got["loadgen"],
        tick_period_ms_max=max(gaps, default=None),
        silence_ms_max=1e3 * silence,
        silence_at_s=silent_from - t0,
        loadgen_stall_ms_max=loadgen.longest_stall(
            got["stalls"]["loadgen"], t0, t1),
        window_loop_stall_ms_max=loadgen.longest_stall(
            got["stalls"]["window_loop"], t0, t1),
        ttft_ms_max=max(ttfts, default=None),
        ttft_over_1s=sum(x >= 1e3 for x in ttfts),
        tick_period_ms_p50=median(gaps) if gaps else None,
        ticks=sum(s["name"] == "engine_tick" and not s.get("instant")
                  for s in spans),
        decode_ticks=len(starts),
        prefill_windows=sum(s["name"] == "decode_paged_window"
                            for s in spans),
        requests_finished=len(got["finished"]),
        first_token_arrival_after_emit_ms_p90=(
            percentile(waits, 90) if waits else None),
        first_token_arrival_after_emit_ms_min=min(waits, default=None),
        first_tokens_matched=len(waits))


def run(ctx):
    cell, family, reference = ctx.cell, ctx.cell.family, ctx.cell.reference
    config, traffic = ctx.config, ctx.traffic
    times = {}

    t = time.perf_counter()
    params = reference.init_params(ctx.seed, config)
    times["weights_s"] = time.perf_counter() - t
    stack = family.build_serve(config, ctx.place, params, ctx.rehearse, times)
    del params
    try:
        got = drive(ctx, stack, ctx.seed, ctx.seconds)
    except BaseException:
        # no result: the stack's threads are not left to hold the exit up
        stack.close()
        raise
    ctx.note("setup", ramp_s=got["ramp_s"], **times)
    ctx.note("pace", **pace(got))

    t0, t1 = got["window"]
    finished, failed = got["finished"], got["failed"]
    # a request cut by the window's end still delivered tokens inside it
    arrivals = [x for r in got["records"] for x in r.times if t0 <= x <= t1]
    arrived = len(arrivals)
    served = tokens_in_window(got["records"], t0, t1)
    tpots = [tpot_ms(r) for r in finished if len(r.times) >= 2]
    compiled = {k: got["counters"].get(k, 0)
                for k in ("xla_compiles", "serving_steady_recompiles")}
    # all streams step in one tick, so tokens arrive in bursts of one a
    # stream: tokens_arrived moves by a whole tick where the last burst
    # crosses the window's end, tokens_served (the metric) does not
    ctx.note("window", seconds=t1 - t0, tokens_served=served,
             tokens_arrived=arrived,
             last_arrival_before_end_s=t1 - max(arrivals, default=t0),
             tail_s=got["tail_s"],
             requests_finished=len(finished), requests_failed=len(failed),
             tpot_samples=len(tpots), compiles_in_window=compiled,
             first_errors=[r.error or r.done for r in failed[:3]],
             clients_not_stopped=got["stuck"])
    facts = {
        "attempted": len(finished) + len(failed), "failed": len(failed),
        "window": (t0, t1),
        "values": {
            "serve_tok_per_s": served / (t1 - t0),
            "tpot_p90_ms": percentile(tpots, 90) if tpots else None,
        },
        "spans": got["spans"], "counters": got["counters"],
        "requests": got["records"], "tracer": got["tracer"],
    }
    stack.close()
    facts["memory_peak_bytes"] = ctx.memory_peak()
    del stack
    gc.collect()

    # the plain reference, once over each finished prompt + served tokens
    t = time.perf_counter()
    if not finished:
        facts["checks"] = {"rows": [], "detail": {"tokens_compared": 0}}
        return facts
    sample = in_order(finished)
    gaps = served_gaps(reference, config,
                       reference.init_params(ctx.seed, config), sample)
    facts["checks"] = checks.served(gaps, cell.check_limits)
    facts["reference_s"] = time.perf_counter() - t
    return facts


def calibrate(ctx, seeds):
    """For each seed, in one process and one engine: new seeded weights
    into the served scope, a short window at the cell's own load, the
    requests it finished; then, the engine freed, the program's gaps and the
    control's (the reference one precision down, at the same positions)."""
    family, reference = ctx.cell.family, ctx.cell.reference
    config, traffic = ctx.config, ctx.traffic
    params = reference.init_params(seeds[0], config)
    stack = family.build_serve(config, ctx.place, params, ctx.rehearse, {})
    del params
    samples = {}
    for seed in seeds:
        stack.set_params(reference.init_params(seed, config))
        got = drive(ctx, stack, seed, ctx.seconds)
        samples[seed] = in_order(got["finished"])
        ctx.note("calibrate_window", seed=seed,
                 finished=len(got["finished"]), failed=len(got["failed"]))
        ctx.note("pace", seed=seed, **pace(got))
        stack.wait_idle()
    stack.close()
    del stack
    gc.collect()
    low = config["control_precision"]["serve"]
    for seed in seeds:
        params = reference.init_params(seed, config)
        for who, dtype in (("program", "highest"), ("control_" + low, low)):
            rows = checks.served(
                served_gaps(reference, config, params, samples[seed], dtype),
                ctx.cell.check_limits)
            ctx.note("calibrate", seed=seed, who=who,
                     correct=checks.correct(rows),
                     **dict(checks.summary_values(rows), **rows["detail"]))
