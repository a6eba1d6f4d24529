#!/usr/bin/env python3
"""The closed-loop load generator, in a process of its own.

No user's client shares an interpreter lock with the server, so the
clients of a serving cell do not either: ``serve_closed.drive`` starts
this file as a child of the run (``Child``, the parent's end, further
down) and the child runs the ``clients`` threads (``Clients``), each
sending its next ``POST /v1/generate`` (SSE) the moment the last one
finished, and keeps their ``Record``s. It imports the standard library
and numpy and nothing of the program, so it starts in a fraction of a
second and holds no chip.

The pipe carries one JSON object a line. The parent's first line is
``{"traffic", "vocab", "seed", "host", "port"}``; after the child's
``{"ready"}`` every line of the parent is an ``op`` and has one answer:

  ``clock``    the child's ``perf_counter`` now;
  ``start``    the clients start; the answer comes when every client has
               the first token of its second request (the ramp's end),
               or after ``RAMP_S`` without it;
  ``end``      with the window's end ``t1``: the answer comes once every
               client's newest request is past it or ``TAIL_S`` has gone;
  ``stop``     the clients stop; the answer names the threads still
               alive and gives the child's CPU and wall seconds and its
               longest oversleeps (``Stalls``);
  ``records``  every record: index, client, ``want``, ``sent``, ``times``,
               ``tokens``, ``done``, ``status``, ``error``, ``ended``.
               Prompts do not come back: ``Plan.prompt`` makes them again
               from ``(seed, idx)``.

The child leaves when its standard input closes, so it never outlives
the run. Both ends stamp with ``time.perf_counter()``, one clock for the
machine on Linux, and the parent checks that it is: ``Child.clock``.
"""

import collections
import http.client
import json
import math
import os
import queue
import subprocess
import sys
import threading
import time

import numpy as np

# how long the ramp may take, and how long past the window's end the
# clients may run for their next token
RAMP_S = 300.0
TAIL_S = 5.0
# how long the clients get to notice the stop, and what a read of the
# pipe after the window is allowed on top of what it waits for
STOP_S = 30.0
ANSWER_S = 60.0
# the offsets of the child's clock at start and at stop may differ by
# this (beyond what the two brackets leave open) before the run is refused
CLOCK_DRIFT_S = 0.5e-3
CLOCK_ROUNDS = 15
# the clients start this far apart: a server that listens with a backlog
# of 5 (``socketserver``'s default, the gateway's) drops the connects of a
# burst beyond it, and a dropped SYN is sent again after 1 s, then 3 s,
# then 7 s: 64 clients started at once ramped in 8 to 19 s, not in 2
START_GAP_S = 0.02
# a thread of the child sleeps this long at a time, and an oversleep of
# more than STALL_S is kept: how long this process stood still, and when
STALL_EVERY_S = 0.01
STALL_S = 0.05
STALLS_KEPT = 10


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

    def size_of(self, idx):
        """(prompt length, output length) of the ``idx``-th request."""
        cycle, at = divmod(idx, len(self.sizes))
        with self._lock:
            return self.sizes[int(self._order(cycle)[at])]

    def _ids(self, idx, length):
        ids = np.random.default_rng([self.seed, 2, idx]).integers(
            0, self.vocab, length)
        return [int(t) for t in ids]

    def prompt(self, idx):
        """The ``idx``-th request's prompt, from ``(seed, idx)`` alone."""
        return self._ids(idx, self.size_of(idx)[0])

    def take(self):
        with self._lock:
            idx = self._next
            self._next += 1
        plen, olen = self.size_of(idx)
        return idx, self._ids(idx, plen), olen

    def first_cut(self, client, shortest):
        """Where a client's first request is cut: uniform in
        [1, shortest], the shortest output of the mix."""
        return int(np.random.default_rng(
            [self.schedule, 3, client]).integers(1, shortest + 1))


class Record(object):
    __slots__ = ("idx", "client", "prompt", "want", "sent", "times",
                 "tokens", "done", "status", "error", "ended")
    # what crosses the pipe, in this order: all but the prompt
    WIRE = ("idx", "client", "want", "sent", "times", "tokens", "done",
            "status", "error", "ended")

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

    def to_wire(self):
        row = {k: getattr(self, k) for k in self.WIRE}
        # a client that did not stop may be between the two appends
        n = len(row["tokens"])
        row["times"], row["tokens"] = row["times"][:n], row["tokens"][:n]
        return [row[k] for k in self.WIRE]

    @classmethod
    def from_wire(cls, row, plan, offset=0.0):
        """The record the child kept, its prompt made again by ``plan``
        and its stamps moved by ``offset`` (the child's clock less the
        parent's) onto the parent's clock."""
        if len(row) != len(cls.WIRE):
            raise ValueError("a record of %d fields, not %d"
                             % (len(row), len(cls.WIRE)))
        got = dict(zip(cls.WIRE, row))
        rec = cls(int(got["idx"]), int(got["client"]),
                  plan.prompt(int(got["idx"])), int(got["want"]))
        if len(got["times"]) != len(got["tokens"]):
            raise ValueError("request %d: %d stamps for %d tokens" % (
                rec.idx, len(got["times"]), len(got["tokens"])))
        for k in ("sent", "ended"):
            if got[k] is not None:
                setattr(rec, k, float(got[k]) - offset)
        rec.times = [float(x) - offset for x in got["times"]]
        rec.tokens = [int(t) for t in got["tokens"]]
        rec.done, rec.status, rec.error = (
            got["done"], got["status"], got["error"])
        return rec


class Clients(object):
    def __init__(self, host, port, plan, n, clock=time.perf_counter):
        self.host, self.port, self.plan = host, port, plan
        self.clock = clock
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
            time.sleep(START_GAP_S)

    def wait_ramped(self, timeout):
        end = time.monotonic() + timeout
        return all(ev.wait(timeout=max(0.0, end - time.monotonic()))
                   for ev in self.ramped)

    def all_past(self, t):
        """Whether every client's newest request was sent after ``t`` or
        has a token that arrived after it."""
        with self._lock:
            newest = {r.client: r for r in self.records}
        return len(newest) == len(self.threads) and all(
            r.sent is not None and (r.sent > t
                                    or (r.times and r.times[-1] > t))
            for r in newest.values())

    def stop(self, timeout=STOP_S):
        self._stop.set()
        end = time.monotonic() + timeout
        for t in self.threads:
            t.join(timeout=max(0.0, end - time.monotonic()))
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
        clock = self.clock
        body = json.dumps({"prompt_ids": rec.prompt,
                           "max_new_tokens": rec.want}).encode()
        conn = http.client.HTTPConnection(self.host, self.port, timeout=600)
        try:
            rec.sent = clock()
            conn.request("POST", "/v1/generate", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec.status = resp.status
            if resp.status != 200:
                rec.error = resp.read(300).decode("utf-8", "replace")
                rec.ended = clock()
                return
            for line in resp:
                if self._stop.is_set():
                    return
                if not line.startswith(b"data: "):
                    continue
                now = clock()
                event = json.loads(line[6:])
                if "token" in event:
                    rec.times.append(now)
                    rec.tokens.append(int(event["token"]))
                    if on_first_token is not None:
                        on_first_token.set()
                elif event.get("done"):
                    rec.done = event
                    break
            rec.ended = clock()
        except (OSError, http.client.HTTPException, ValueError) as e:
            if not self._stop.is_set():
                rec.error = repr(e)
                rec.ended = clock()
        finally:
            conn.close()


class Stalls(object):
    """A thread that sleeps ``STALL_EVERY_S`` at a time and keeps its
    longest oversleeps, each with the clock's reading where it began. A
    host that stands still for a tenth of a second or for some seconds
    shows here, in a process the server shares no lock with: the pace
    line holds it against the server's longest tick and the clients'
    longest silence, so that a run far from its siblings says whose
    stall it was."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.kept = []  # (seconds overslept, the clock where the sleep began)
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="bench-stalls")

    def start(self):
        self._thread.start()

    def _watch(self):
        clock = self.clock
        while True:
            a = clock()
            time.sleep(STALL_EVERY_S)
            over = clock() - a - STALL_EVERY_S
            if over > STALL_S:
                self.kept = sorted(self.kept + [(over, a)],
                                   reverse=True)[:STALLS_KEPT]

    def longest(self):
        return [[at, over] for over, at in self.kept]


def longest_stall(stalls, t0, t1):
    """Of ``[[at, seconds], ...]`` the longest that began in [t0, t1], in
    milliseconds; None where none did (the child keeps only those over
    ``STALL_S``)."""
    inside = [over for at, over in stalls if t0 <= at <= t1]
    return 1e3 * max(inside) if inside else None


def main(clock=time.perf_counter):
    """The child: answer the parent's lines until its pipe closes."""
    # the pipe is this process's standard output: nothing else writes there
    pipe = os.fdopen(os.dup(sys.stdout.fileno()), "w")
    sys.stdout = sys.stderr

    def say(**facts):
        pipe.write(json.dumps(facts) + "\n")
        pipe.flush()

    spec = json.loads(sys.stdin.readline())
    plan = Plan(spec["traffic"], int(spec["vocab"]), int(spec["seed"]))
    clients = Clients(spec["host"], int(spec["port"]), plan,
                      int(spec["traffic"]["clients"]), clock)
    wall0, cpu0 = time.perf_counter(), time.process_time()
    stalls = Stalls(clock)
    stalls.start()
    say(ready=True, pid=os.getpid())
    for line in sys.stdin:
        msg = json.loads(line)
        op = msg["op"]
        if op == "clock":
            say(clock=clock())
        elif op == "start":
            clients.start()
            say(ramped=clients.wait_ramped(RAMP_S))
        elif op == "end":
            t1 = float(msg["t1"])
            while clock() < t1 + TAIL_S and not clients.all_past(t1):
                time.sleep(0.02)
            say(past=clients.all_past(t1))
        elif op == "stop":
            stuck = clients.stop()
            say(stopped=True, stuck=stuck,
                cpu_s=time.process_time() - cpu0,
                wall_s=time.perf_counter() - wall0,
                stalls=stalls.longest())
        elif op == "records":
            with clients._lock:
                rows = [r.to_wire() for r in clients.records]
            say(records=rows)
        else:
            raise ValueError("unknown op %r" % (op,))


class LoadError(RuntimeError):
    """The child died, did not answer in time, answered what does not
    parse, or its clock drifted: the run ends without a result."""


class Child(object):
    """The parent's end of the pipe. Every read has a limit; ``close``
    kills the child whatever happened and waits for it."""

    def __init__(self, traffic, vocab, seed, host, port, argv=None):
        self.proc = subprocess.Popen(
            argv or [sys.executable, os.path.abspath(__file__)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, bufsize=1)
        self.pid = self.proc.pid
        self._lines = queue.Queue()
        self.stderr_tail = collections.deque(maxlen=40)
        self._readers = [
            threading.Thread(target=self._read_out, daemon=True,
                             name="loadgen-pipe"),
            threading.Thread(target=self._read_err, daemon=True,
                             name="loadgen-stderr")]
        for t in self._readers:
            t.start()
        try:
            self._say(traffic=traffic, vocab=int(vocab), seed=int(seed),
                      host=host, port=int(port))
            self._answer("ready", ANSWER_S)
            # (the child's clock less this one's, the bracket's width)
            self.clock_start = self.clock()
            self.clock_stop = None
        except BaseException:
            self.close()
            raise
        # what is taken off the child's stamps: nothing where the two
        # clocks are one within the bracket, else the offset at start
        offset, bracket = self.clock_start
        self.offset = 0.0 if abs(offset) <= bracket else offset

    def _read_out(self):
        for line in self.proc.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def _read_err(self):
        for line in self.proc.stderr:
            self.stderr_tail.append(line.rstrip("\n"))

    def _fail(self, what):
        self.close()
        raise LoadError("load generator (pid %d, exit %s) %s; its last "
                        "lines of standard error:\n%s" % (
                            self.pid, self.proc.returncode, what,
                            "\n".join(self.stderr_tail) or "(none)"))

    def _say(self, **msg):
        try:
            self.proc.stdin.write(json.dumps(msg) + "\n")
            self.proc.stdin.flush()
        except (OSError, ValueError):
            self._fail("took no %r" % (msg.get("op", "first line"),))

    def _answer(self, key, timeout):
        """The child's next line, which has to carry ``key``."""
        try:
            line = self._lines.get(timeout=timeout)
        except queue.Empty:
            self._fail("gave no %r in %.0f s" % (key, timeout))
        if line is None:
            self._fail("closed its pipe before %r" % (key,))
        try:
            msg = json.loads(line)
            msg[key]
            return msg
        except (ValueError, KeyError, TypeError):
            self._fail("answered %r with a line that does not parse: %.200r"
                       % (key, line))

    def check(self):
        """Called from the window's loop: a dead child ends the run there
        and not at the window's end."""
        if self.proc.poll() is not None:
            self._fail("died")

    def clock(self, rounds=CLOCK_ROUNDS):
        """A reading of the child's clock between two of this one's,
        ``rounds`` times; of the tightest bracket -> (the child's reading
        less the bracket's middle, the bracket's width), seconds."""
        best = None
        for _ in range(rounds):
            a = time.perf_counter()
            self._say(op="clock")
            theirs = float(self._answer("clock", ANSWER_S)["clock"])
            b = time.perf_counter()
            if best is None or b - a < best[1]:
                best = (theirs - 0.5 * (a + b), b - a)
        return best

    def start(self):
        self._say(op="start")
        if not self._answer("ramped", RAMP_S + ANSWER_S)["ramped"]:
            self._fail("had a client that was not ramped in %.0f s" % RAMP_S)

    def end(self, t1):
        """-> whether every client's newest request is past ``t1`` (on
        this side's clock)."""
        self._say(op="end", t1=t1 + self.offset)
        return bool(self._answer("past", TAIL_S + ANSWER_S)["past"])

    def stop(self):
        """Stop the clients, read the child's clock again, and refuse a
        clock that moved against this one since the start.
        -> {"stuck", "cpu_s", "wall_s", "stalls"}, the stalls' stamps on
        this side's clock"""
        self._say(op="stop")
        left = self._answer("stopped", STOP_S + ANSWER_S)
        try:
            left["stalls"] = [[float(at) - self.offset, float(over)]
                              for at, over in left.get("stalls", [])]
        except (ValueError, TypeError):
            self._fail("answered 'stopped' with stalls that do not parse: "
                       "%.200r" % (left.get("stalls"),))
        self.clock_stop = self.clock()
        (o0, b0), (o1, b1) = self.clock_start, self.clock_stop
        if abs(o1 - o0) > CLOCK_DRIFT_S + 0.5 * (b0 + b1):
            self._fail("has a clock that moved against this process's by "
                       "%.3f ms between start (%.3f ms, bracket %.3f) and "
                       "stop (%.3f ms, bracket %.3f)" % (
                           1e3 * (o1 - o0), 1e3 * o0, 1e3 * b0, 1e3 * o1,
                           1e3 * b1))
        return left

    def records(self, plan):
        self._say(op="records")
        rows = self._answer("records", ANSWER_S)["records"]
        try:
            return [Record.from_wire(row, plan, self.offset) for row in rows]
        except (ValueError, KeyError, TypeError) as e:
            self._fail("handed back records that do not parse: %r" % (e,))

    def clock_facts(self):
        """For the pace line, milliseconds."""
        out = {"clock_offset_applied_ms": 1e3 * self.offset}
        for when, got in (("start", self.clock_start),
                          ("stop", self.clock_stop)):
            if got is not None:
                out["clock_offset_%s_ms" % when] = 1e3 * got[0]
                out["clock_bracket_%s_ms" % when] = 1e3 * got[1]
        return out

    def close(self):
        """Kill the child if it lives, wait for it and for the readers."""
        if self.proc.poll() is None:
            self.proc.kill()
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        for t in self._readers:
            if t is not threading.current_thread():
                t.join(timeout=10)
        for f in (self.proc.stdin, self.proc.stdout, self.proc.stderr):
            try:
                f.close()
            except OSError:
                pass


if __name__ == "__main__":
    main()
