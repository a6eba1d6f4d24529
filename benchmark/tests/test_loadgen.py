"""The load generator's child process (``harness/loadgen.py``): the same
plan on both sides of the pipe, records that survive it, one clock that
is checked, and a run that ends, non-zero and with nothing left behind,
when the child does not play its part. The server here is a few lines of
``http.server`` that stream what ``POST /v1/generate`` streams; the last
tests drive ``benchmark/run.py --rehearse`` itself.
"""

import http.server
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from benchmark.harness import cells, loadgen
from benchmark.tests.test_control_and_broken_path import _context
from benchmark.traffic_kinds import serve_closed

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LOADGEN = os.path.join(ROOT, "benchmark", "harness", "loadgen.py")
SHIFTED = os.path.join(HERE, "data", "loadgen_shifted.py")
SERVE_CELLS = ("gpt2s-serve-chat", "kanana2-serve-chat4k",
               "solar2-serve-reason4k")
BIG = 2**31 + 12345

IN_A_CHILD = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("loadgen_alone", sys.argv[1])
lg = importlib.util.module_from_spec(spec)
spec.loader.exec_module(lg)
traffic, vocab, seed, n = json.loads(sys.argv[2])
plan = lg.Plan(traffic, vocab, seed)
shortest = min(o for _p, o in plan.sizes)
print(json.dumps({
    "takes": [plan.take() for _ in range(n)],
    "cuts": [plan.first_cut(c, shortest) for c in range(traffic["clients"])],
    "program": sorted(m for m in sys.modules
                      if m.split(".")[0] in ("jax", "paddle_tpu",
                                             "benchmark"))}))
"""


@pytest.mark.parametrize("seed", [1, BIG])
@pytest.mark.parametrize("workload", SERVE_CELLS)
def test_the_childs_plan_is_the_parents(workload, seed, tmp_path):
    """Loaded by its path in a bare interpreter, as the child loads it,
    the file hands out the sequence this process's ``Plan`` hands out,
    over two passes through the size set, and the same first cuts; and it
    has imported nothing of the program."""
    cell = cells.Cell(workload)
    traffic, vocab = cell.traffic, cell.config["vocab_size"]
    n = 2 * traffic["size_set"] + 3
    out = subprocess.run(
        [sys.executable, "-c", IN_A_CHILD, LOADGEN,
         json.dumps([traffic, vocab, seed, n])],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode == 0, out.stderr[-2000:]
    theirs = json.loads(out.stdout.splitlines()[-1])
    assert theirs["program"] == []
    plan = serve_closed.Plan(traffic, vocab, seed)
    mine = [plan.take() for _ in range(n)]
    assert [tuple(t) for t in theirs["takes"]] == mine
    assert [i for i, _p, _o in mine] == list(range(n))
    shortest = min(o for _p, o in plan.sizes)
    assert theirs["cuts"] == [plan.first_cut(c, shortest)
                              for c in range(traffic["clients"])]
    # what the parent makes again from (seed, idx) is what was sent
    again = serve_closed.Plan(traffic, vocab, seed)
    assert all(again.prompt(i) == p for i, p, _o in mine)


def _plan(seed=7):
    cell = cells.Cell("gpt2s-serve-chat")
    return loadgen.Plan(serve_closed.toy(cell.traffic), 512, seed)


def _record(plan, idx, **fields):
    rec = loadgen.Record(idx, 3, plan.prompt(idx), 5)
    for k, v in fields.items():
        setattr(rec, k, v)
    return rec


@pytest.mark.parametrize("fields", [
    dict(sent=1234.000000123, times=[1234.5, 1234.5000001, 98765.4321],
         tokens=[5, 0, 511], status=200, ended=98765.5,
         done={"done": True, "finish_reason": "length", "tokens": 3,
               "request_id": "r-1", "ttft_ms": 0.1}),
    dict(sent=0.1, status=503, error="{\"error\": \"draining\"}",
         ended=0.2),
    dict(sent=2.0, times=[2.5], tokens=[7], status=200,
         error="ConnectionResetError(104, 'reset')", ended=2.6),
    dict(sent=2.0, times=[2.5, 2.75], tokens=[7, 8], status=200),  # cut
    dict(),   # taken from the plan, not yet sent
], ids=["done", "refused", "error", "cut", "unsent"])
def test_a_record_survives_the_pipe(fields):
    plan = _plan()
    rec = _record(plan, 11, **fields)
    row = json.loads(json.dumps(rec.to_wire()))
    back = loadgen.Record.from_wire(row, plan)
    for k in loadgen.Record.__slots__:
        assert getattr(back, k) == getattr(rec, k), k
    assert back.ok == rec.ok
    # the child's clock ahead by 100 s: every stamp comes back by as much
    moved = loadgen.Record.from_wire(row, plan, offset=100.0)
    assert moved.tokens == rec.tokens and moved.prompt == rec.prompt
    assert moved.times == [x - 100.0 for x in rec.times]
    for k in ("sent", "ended"):
        want = getattr(rec, k)
        assert getattr(moved, k) == (None if want is None else want - 100.0)


@pytest.mark.parametrize("spoil", [
    lambda row: row[:-1],
    lambda row: row[:4] + [row[4] + [1.0]] + row[5:],   # a stamp too many
    lambda row: ["x"] + row[1:],
], ids=["short", "stamps", "index"])
def test_a_record_that_does_not_parse_is_refused(spoil):
    plan = _plan()
    row = _record(plan, 2, sent=1.0, times=[1.5], tokens=[4]).to_wire()
    with pytest.raises((ValueError, TypeError)):
        loadgen.Record.from_wire(spoil(row), plan)


class _Sse(http.server.BaseHTTPRequestHandler):
    """What the gateway's ``POST /v1/generate`` puts on the wire."""
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        body = json.loads(self.rfile.read(
            int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        want = int(body["max_new_tokens"])
        events = [{"token": len(body["prompt_ids"]) + k}
                  for k in range(want)]
        events.append({"done": True, "finish_reason": "length",
                       "tokens": want})
        try:
            for event in events:
                data = b"data: " + json.dumps(event).encode() + b"\n\n"
                self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
                time.sleep(0.002)
            self.wfile.write(b"0\r\n\r\n")
        except OSError:
            pass

    def log_message(self, *a):
        pass


@pytest.fixture()
def sse_server():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Sse)
    server.daemon_threads = True
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server.server_address
    server.shutdown()
    server.server_close()


def _child(address, argv=None, seed=7):
    cell = cells.Cell("gpt2s-serve-chat")
    traffic = serve_closed.toy(cell.traffic)
    plan = loadgen.Plan(traffic, 512, seed)
    return plan, loadgen.Child(traffic, 512, seed, address[0], address[1],
                               argv=argv)


def _gone(pid):
    """No such process, or one that is only waiting to be reaped."""
    try:
        with open("/proc/%d/stat" % pid) as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except OSError:
        return True


def test_stalls_keep_an_oversleep_and_where_it_began():
    """A clock that jumps 0.4 s during the watcher's third sleep: kept,
    with the reading the sleep began at; found by the window it lies in."""
    calls, jumped, began = [0], [0.0], []

    def clock():
        calls[0] += 1
        now = time.perf_counter() + jumped[0]
        if calls[0] == 5:
            began.append(now)
        if calls[0] == 6:
            jumped[0] = 0.4
            now += 0.4
        return now

    stalls = loadgen.Stalls(clock)
    stalls.start()
    end = time.monotonic() + 10.0
    while calls[0] < 9 and time.monotonic() < end:
        time.sleep(0.01)
    kept = stalls.longest()
    at, over = max(kept, key=lambda row: row[1])
    assert at == began[0] and 0.4 <= over < 0.4 + 1.0
    assert len(kept) <= loadgen.STALLS_KEPT
    got = loadgen.longest_stall(kept, at - 1.0, at + 1.0)
    assert got == pytest.approx(1e3 * over)
    assert loadgen.longest_stall(kept, at - 2.0, at - 1.0) is None
    assert loadgen.longest_stall([], 0.0, 1.0) is None


@pytest.mark.parametrize("offset", [0.0, 1000.0, -250.5])
def test_one_window_through_the_child(sse_server, offset):
    """Start, ramp, window's end, stop, records: what the child kept lies
    on this process's clock, whatever base the child's clock has."""
    argv = [sys.executable, SHIFTED, repr(offset), "0.0"] if offset else None
    t_a = time.perf_counter()
    plan, load = _child(sse_server, argv)
    try:
        got, bracket = load.clock_start
        assert abs(got - offset) <= bracket and bracket < 0.05
        assert load.offset == (got if offset else 0.0)
        load.start()
        t1 = time.perf_counter() + 0.3
        time.sleep(0.3)
        load.check()
        assert load.end(t1) is True
        left = load.stop()
        records = load.records(plan)
    finally:
        load.close()
    t_b = time.perf_counter()
    assert _gone(load.pid)
    assert left["stuck"] == [] and left["wall_s"] > 0.3
    assert left["cpu_s"] >= 0.0
    # the child's oversleeps (none, as a rule) lie on this process's clock
    assert all(t_a <= at <= t_b and over > loadgen.STALL_S
               for at, over in left["stalls"])
    assert abs(load.clock_stop[0] - offset) <= load.clock_stop[1]
    facts = load.clock_facts()
    assert facts["clock_offset_applied_ms"] == 1e3 * load.offset
    assert {"clock_offset_start_ms", "clock_bracket_start_ms",
            "clock_offset_stop_ms", "clock_bracket_stop_ms"} <= set(facts)
    # every client finished requests, the first of each cut short
    done = [r for r in records if r.ok]
    assert {r.client for r in done} == set(range(4)) and len(done) > 8
    assert sorted(r.idx for r in records) == list(range(len(records)))
    shortest = min(o for _p, o in plan.sizes)
    for r in records:
        assert r.prompt == plan.prompt(r.idx)
        first = min(x.idx for x in records if x.client == r.client)
        assert r.want == (plan.first_cut(r.client, shortest)
                          if r.idx == first else plan.size_of(r.idx)[1])
        assert r.tokens == [len(r.prompt) + k for k in range(len(r.tokens))]
        stamps = [r.sent] + r.times + ([r.ended] if r.ended else [])
        assert stamps == sorted(stamps)
        assert t_a < stamps[0] and stamps[-1] < t_b
    # the clients start apart, not as one burst of connects
    firsts = sorted(min(r.sent for r in records if r.client == c)
                    for c in range(4))
    assert all(b - a >= 0.75 * loadgen.START_GAP_S
               for a, b in zip(firsts, firsts[1:]))
    # past the window's end: each client's newest request has a stamp there
    for c in range(4):
        newest = max((r for r in records if r.client == c),
                     key=lambda r: r.idx)
        assert newest.sent > t1 or newest.times[-1] > t1


def test_a_clock_that_drifts_fails_the_run(sse_server):
    """5 ms a second against this process's clock: more than 0.5 ms
    between the two readings, so ``stop`` refuses and the child is gone."""
    plan, load = _child(sse_server,
                        [sys.executable, SHIFTED, "3.0", "0.005"])
    try:
        load.start()
        time.sleep(0.5)
        with pytest.raises(loadgen.LoadError, match="moved against"):
            load.stop()
    finally:
        load.close()
    assert _gone(load.pid)


@pytest.mark.parametrize("how", ["killed", "garbage", "silent"])
def test_a_child_that_fails_ends_in_its_limit(sse_server, how, monkeypatch):
    """A child that dies, answers what does not parse, or does not
    answer: ``LoadError`` with its last lines of standard error, inside
    the read's limit, and no process left."""
    monkeypatch.setattr(loadgen, "ANSWER_S", 1.0)
    monkeypatch.setattr(loadgen, "RAMP_S", 0.5)
    scripts = {
        "garbage": "import sys; sys.stdin.readline(); "
                   "print('mind the gap', file=sys.stderr); "
                   "print('not json', flush=True); sys.stdin.read()",
        "silent": "import sys; print('asleep', file=sys.stderr, "
                  "flush=True); sys.stdin.read()",
    }
    t = time.perf_counter()
    if how == "killed":
        load = _child(sse_server)[1]
        load.start()
        os.kill(load.pid, 9)
        with pytest.raises(loadgen.LoadError, match="died|closed its pipe"):
            for _ in range(200):
                load.check()
                time.sleep(0.01)
            load.end(time.perf_counter())
    else:
        with pytest.raises(loadgen.LoadError) as e:
            _child(sse_server, [sys.executable, "-c", scripts[how]])
        assert {"garbage": "mind the gap", "silent": "asleep"}[how] \
            in str(e.value)
        assert ("does not parse" if how == "garbage" else "gave no") \
            in str(e.value)
    assert time.perf_counter() - t < 10.0
    out = subprocess.run(["ps", "-eo", "pid,args"], capture_output=True,
                         text=True).stdout
    assert not [line for line in out.splitlines()
                if "mind the gap" in line or "asleep" in line]


def test_no_client_thread_in_the_serving_process():
    """The rehearsal of a serving cell, in this process: its records come
    from the child, and no ``bench-client-*`` thread ever lives here."""
    seen, stop = set(), threading.Event()

    def watch():
        while not stop.is_set():
            seen.update(t.name for t in threading.enumerate())
            time.sleep(0.005)

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    notes = []
    ctx = _context("gpt2s-serve-chat", 5, 2.0)[1]
    ctx.note = lambda kind, **facts: notes.append((kind, facts))
    try:
        got = serve_closed.run(ctx)
    finally:
        stop.set()
        watcher.join()
    assert got["attempted"] > 0 and got["failed"] == 0
    assert "loadgen-pipe" in seen
    assert not [name for name in seen if name.startswith("bench-client")]
    assert {r.client for r in got["requests"]} == set(range(4))
    kinds = [k for k, _f in notes]
    assert kinds.index("loadgen") < kinds.index("pace") < \
        kinds.index("window")
    pace = dict(notes)["pace"]
    assert pace["ticks"] > 10 and pace["tick_period_ms_p50"] > 0
    assert pace["requests_finished"] == got["attempted"]
    assert pace["prefill_windows"] > 0 and pace["loadgen_wall_s"] > 2.0
    assert abs(pace["clock_offset_start_ms"]) < 1.0
    assert abs(pace["clock_offset_stop_ms"]) < 1.0
    # the client has a token after the engine made it, on one clock
    assert pace["first_tokens_matched"] > 0
    assert 0 < pace["first_token_arrival_after_emit_ms_min"] < 1e3
    # whose stall it was: the server's ticks, the clients' silence, the
    # child's own thread and the window's loop, side by side
    assert pace["tick_period_ms_max"] >= pace["tick_period_ms_p50"]
    assert 0 < pace["silence_ms_max"] <= 2e3
    assert 0 <= pace["silence_at_s"] <= 2.0
    assert pace["window_loop_stall_ms_max"] >= 0.0
    assert pace["loadgen_stall_ms_max"] is None \
        or pace["loadgen_stall_ms_max"] > 1e3 * loadgen.STALL_S
    assert _gone(dict(notes)["loadgen"]["pid"])


def _run_py(*args):
    return subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py")]
        + list(args), cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


@pytest.mark.parametrize("workload", SERVE_CELLS)
def test_rehearsal_completes_through_the_child(workload):
    proc = _run_py("--workload", workload, "--rehearse")
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-3000:]
    lines = [json.loads(line) for line in out.splitlines()
             if line.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] == "completed" and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["metric_names"] == ["serve_tok_per_s", "setup_s",
                                    "tpot_p90_ms"]
    notes = [d.get("note") for d in lines]
    assert notes.count("pace") == 1 and "pace" in notes[:-1]
    pace = lines[notes.index("pace")]
    assert pace["requests_finished"] == last["attempted"]
    assert _gone(lines[notes.index("loadgen")]["pid"])


def test_a_child_killed_mid_window_ends_the_run():
    """``run.py`` itself: the child is killed a second into a 60 s
    window; the run ends non-zero within seconds, says why on standard
    error, prints no result line and leaves no process."""
    proc = _run_py("--workload", "gpt2s-serve-chat", "--rehearse",
                   "--seconds", "60")
    pid, lines = None, []
    try:
        for line in proc.stdout:
            lines.append(line)
            if '"loadgen"' in line:
                pid = json.loads(line)["pid"]
            if '"window_open"' in line:
                break
        assert pid is not None, "".join(lines)[-2000:]
        time.sleep(1.0)
        t = time.perf_counter()
        os.kill(pid, 9)
        out, err = proc.communicate(timeout=60)
        took = time.perf_counter() - t
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode not in (0, None)
    assert took < 30.0
    assert "load generator (pid %d" % pid in err and "died" in err
    assert not [line for line in out.splitlines() if '"correct"' in line]
    assert _gone(pid)
