"""Closed-loop serving traffic: ``clients`` callers, each sending its next
``POST /v1/generate`` (SSE) the moment the last one finished.

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
import http.client
import json
import math
import threading
import time

import numpy as np

from benchmark.harness import checks, tracing
from benchmark.harness.stats import percentile


def toy(traffic):
    return dict(traffic, **traffic.get("toy", {}))


def _quantile(spec, u):
    lo, hi = spec["lo"], spec["hi"]
    if spec["dist"] == "log_uniform":
        return int(round(math.exp(
            math.log(lo) + u * (math.log(hi) - math.log(lo)))))
    if spec["dist"] == "uniform":
        return int(round(lo + u * (hi - lo)))
    raise ValueError("unknown length distribution %r" % spec["dist"])


def size_set(traffic):
    """The fixed set of (prompt length, output length) pairs: evenly
    spaced quantiles of each distribution, paired by a shuffle that the
    traffic file's ``schedule_seed`` fixes."""
    n = traffic["size_set"]
    us = [(i + 0.5) / n for i in range(n)]
    prompts = [_quantile(traffic["prompt_len"], u) for u in us]
    outputs = [_quantile(traffic["output_len"], u) for u in us]
    order = np.random.default_rng(
        int(traffic["schedule_seed"])).permutation(n)
    return [(prompts[i], outputs[int(j)]) for i, j in enumerate(order)]


class Plan(object):
    """The seeded sequence of requests, handed out under a lock."""

    def __init__(self, traffic, vocab, seed):
        self.sizes = size_set(traffic)
        self.vocab = vocab
        self.seed = int(seed)
        self.schedule = int(traffic["schedule_seed"])
        self._lock = threading.Lock()
        self._next = 0
        self._orders = {}

    def _order(self, cycle):
        if cycle not in self._orders:
            self._orders[cycle] = np.random.default_rng(
                [self.schedule, 1, cycle]).permutation(len(self.sizes))
        return self._orders[cycle]

    def take(self):
        with self._lock:
            idx = self._next
            self._next += 1
            cycle, at = divmod(idx, len(self.sizes))
            plen, olen = self.sizes[int(self._order(cycle)[at])]
        ids = np.random.default_rng([self.seed, 2, idx]).integers(
            0, self.vocab, plen)
        return idx, [int(t) for t in ids], olen

    def first_cut(self, client, shortest):
        """Where a client's first request is cut: uniform in
        [1, shortest], the shortest output of the mix."""
        return int(np.random.default_rng(
            [self.schedule, 3, client]).integers(1, shortest + 1))


class Record(object):
    __slots__ = ("idx", "client", "prompt", "want", "sent", "times",
                 "tokens", "done", "status", "error", "ended")

    def __init__(self, idx, client, prompt, want):
        self.idx, self.client, self.prompt, self.want = (
            idx, client, prompt, want)
        self.sent, self.times, self.tokens = None, [], []
        self.done, self.status, self.error, self.ended = (
            None, None, None, None)

    @property
    def ok(self):
        return (self.status == 200 and self.done is not None
                and self.done.get("finish_reason") == "length"
                and len(self.tokens) == self.want)


class Clients(object):
    def __init__(self, stack, plan, n):
        self.stack, self.plan = stack, plan
        self.records = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        # set at a client's first token of its second request
        self.ramped = [threading.Event() for _ in range(n)]
        self.threads = [threading.Thread(target=self._client, args=(i,),
                                         name="bench-client-%d" % i,
                                         daemon=True) for i in range(n)]

    def start(self):
        for t in self.threads:
            t.start()

    def all_past(self, t):
        """Whether every client's newest request was sent after ``t`` or
        has a token that arrived after it."""
        with self._lock:
            newest = {r.client: r for r in self.records}
        return len(newest) == len(self.threads) and all(
            r.sent is not None and (r.sent > t
                                    or (r.times and r.times[-1] > t))
            for r in newest.values())

    def stop(self):
        self._stop.set()
        for t in self.threads:
            t.join(timeout=60)
        return [t.name for t in self.threads if t.is_alive()]

    def _client(self, i):
        sent = 0
        shortest = min(o for _p, o in self.plan.sizes)
        while not self._stop.is_set():
            idx, prompt, olen = self.plan.take()
            if sent == 0:
                olen = self.plan.first_cut(i, shortest)
            rec = Record(idx, i, prompt, olen)
            with self._lock:
                self.records.append(rec)
            sent += 1
            # ended stays None if the window's end cut the request
            self._send(rec, self.ramped[i] if sent == 2 else None)

    def _send(self, rec, on_first_token):
        body = json.dumps({"prompt_ids": rec.prompt,
                           "max_new_tokens": rec.want}).encode()
        conn = http.client.HTTPConnection(self.stack.host, self.stack.port,
                                          timeout=600)
        try:
            rec.sent = time.perf_counter()
            conn.request("POST", "/v1/generate", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec.status = resp.status
            if resp.status != 200:
                rec.error = resp.read(300).decode("utf-8", "replace")
                rec.ended = time.perf_counter()
                return
            for line in resp:
                if self._stop.is_set():
                    return
                if not line.startswith(b"data: "):
                    continue
                now = time.perf_counter()
                event = json.loads(line[6:])
                if "token" in event:
                    rec.times.append(now)
                    rec.tokens.append(int(event["token"]))
                    if on_first_token is not None:
                        on_first_token.set()
                elif event.get("done"):
                    rec.done = event
                    break
            rec.ended = time.perf_counter()
        except (OSError, http.client.HTTPException, ValueError) as e:
            if not self._stop.is_set():
                rec.error = repr(e)
                rec.ended = time.perf_counter()
        finally:
            conn.close()


# how long past the window's end the clients may run for their next token
TAIL_S = 5.0


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
    """Ramp (set-up), then a window of ``seconds``.
    -> what the window left: records, clocks, counters, spans."""
    from paddle_tpu.observability import trace as program_trace

    config, traffic = ctx.config, ctx.traffic
    t = time.perf_counter()
    plan = Plan(traffic, config["vocab_size"], seed)
    clients = Clients(stack, plan, traffic["clients"])
    clients.start()
    for ev in clients.ramped:
        if not ev.wait(timeout=300):
            raise RuntimeError("a client was not ramped in 300 s")
    time.sleep(traffic["settle_s"])
    ramp_s = time.perf_counter() - t

    before = ctx.counters()
    tracer = tracing.MidWindow(ctx, trace_s or traffic.get("trace_s", 1.5))
    t0 = ctx.open_window()
    t1 = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= t1:
            break
        tracer.poll(now - t0)
        time.sleep(min(0.02, t1 - now))
    tracer.finish()
    after = ctx.counters()
    # the window is over; wait for each stream's next token, which was
    # in the making at t1 (tokens_in_window)
    tail_end = t1 + TAIL_S
    while time.perf_counter() < tail_end and not clients.all_past(t1):
        time.sleep(0.02)
    tail_s = time.perf_counter() - t1
    stuck = clients.stop()
    spans = [s for s in program_trace.get_spans()
             if s["end"] >= t0 and s["start"] <= t1]
    records = list(clients.records)
    in_window = [r for r in records if r.ended is not None
                 and t0 <= r.ended <= t1]
    return {"records": records, "window": (t0, t1), "ramp_s": ramp_s,
            "tail_s": tail_s,
            "tracer": tracer, "spans": spans, "stuck": stuck,
            "counters": {k: after.get(k, 0) - before.get(k, 0)
                         for k in after},
            "finished": [r for r in in_window if r.ok],
            "failed": [r for r in in_window if not r.ok]}


def run(ctx):
    cell, family, reference = ctx.cell, ctx.cell.family, ctx.cell.reference
    config, traffic = ctx.config, ctx.traffic
    times = {}

    t = time.perf_counter()
    params = reference.init_params(ctx.seed, config)
    times["weights_s"] = time.perf_counter() - t
    stack = family.build_serve(config, ctx.place, params, ctx.rehearse, times)
    del params
    got = drive(ctx, stack, ctx.seed, ctx.seconds)
    ctx.note("setup", ramp_s=got["ramp_s"], **times)

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
