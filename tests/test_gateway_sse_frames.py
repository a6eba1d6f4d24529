"""What a served token costs between the engine's decision and the
client's socket (paddle_tpu/serving/gateway.py::_stream_sse,
serving/decode.py::GenerationStream): the bytes of the SSE body are those
of the three-write framing, a chunk is one send (a token event each, the
terminal event with the body's end), the two stream counters count what
reached the wire on every way out, and a chaos plan that dies at a token
count fires after the exact token."""

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.fluid import profiler
from paddle_tpu.models import gpt
from paddle_tpu.serving.decode import DecodeEngine, GenerationStream
from paddle_tpu.testing import chaos

MAX_LEN = 32


# -- the wire, as the handler sees it and as the client does ----------------
class _Wire(object):
    """Stands where the handler's unbuffered ``wfile`` stands (every
    write of it is a ``sendall``): records each write, and raises
    ``fail[1]`` in place of body write number ``fail[0]``."""

    def __init__(self, inner, writes, fail):
        self._inner, self._writes, self._fail = inner, writes, fail

    def write(self, data):
        body = [w for w in self._writes if not w.startswith(b"HTTP/1.1")]
        if self._fail is not None and len(body) == self._fail[0] \
                and not data.startswith(b"HTTP/1.1"):
            # as after a reset: the client's read ends here
            self._inner._sock.shutdown(socket.SHUT_RDWR)
            raise self._fail[1]
        self._writes.append(bytes(data))
        return self._inner.write(data)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _Gateway(object):
    """A gateway over ``server`` whose handlers write through ``_Wire``;
    ``body_writes()`` is what they wrote after the response's headers."""

    def __init__(self, server, tmp_path, fail=None):
        self.log = os.path.join(str(tmp_path), "access.jsonl")
        self.gw = serving.Gateway(server, port=0,
                                  access_log=self.log).start()
        self.writes = writes = []
        handler = self.gw._httpd.RequestHandlerClass
        setup = handler.setup

        def wired_setup(h):
            setup(h)
            h.wfile = _Wire(h.wfile, writes, fail)

        handler.setup = wired_setup
        self.port = self.gw.port

    def body_writes(self):
        return [w for w in self.writes if not w.startswith(b"HTTP/1.1")]

    def status(self, timeout=10):
        """The access log's line of the one generate request."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if os.path.exists(self.log):
                with open(self.log) as f:
                    lines = [json.loads(x) for x in f if x.strip()]
                lines = [x for x in lines
                         if x["endpoint"] == "/v1/generate"]
                if lines:
                    return lines[-1]
            time.sleep(0.01)
        raise AssertionError("no access-log line")

    def stop(self):
        self.gw.stop()


def _raw_post(port, body, timeout=60):
    """POST /v1/generate over a bare socket: (head, body bytes as sent),
    read to the body's last chunk or to the connection's end."""
    payload = json.dumps(body).encode()
    buf = b""
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout) as s:
        s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                  b"Content-Type: application/json\r\n"
                  b"Content-Length: %d\r\n\r\n" % len(payload) + payload)
        while True:
            head, _, sent = buf.partition(b"\r\n\r\n")
            if sent.endswith(b"0\r\n\r\n"):
                break
            try:
                got = s.recv(65536)
            except ConnectionError:
                break
            if not got:
                break
            buf += got
    return head, sent


def _chunks(body, whole=True):
    """The payloads of a chunked body's chunks, parsed strictly; with
    ``whole`` the body must end in the last chunk and nothing after."""
    out, at = [], 0
    while at < len(body):
        eol = body.index(b"\r\n", at)
        n = int(body[at:eol], 16)
        data = body[eol + 2:eol + 2 + n]
        assert len(data) == n
        assert body[eol + 2 + n:eol + 4 + n] == b"\r\n"
        at = eol + 4 + n
        if n == 0:
            assert at == len(body)
            return out, True
        out.append(data)
    assert not whole
    return out, False


def _three_write_framing(events):
    """What the parent's handler put on the wire for these events: a size
    line, the payload and a trailer a chunk, then the last chunk."""
    out = b""
    for data in events:
        out += b"%x\r\n" % len(data)
        out += data
        out += b"\r\n"
    return out + b"0\r\n\r\n"


def _token_event(tok):
    return ('data: {"token": %d}\n\n' % tok).encode("utf-8")


def _event(payload):
    assert payload.startswith(b"data: ") and payload.endswith(b"\n\n")
    return json.loads(payload[6:])


class _Counters(object):
    """How far the two stream counters rose since this was made."""

    def __init__(self):
        self.base = self._read()

    @staticmethod
    def _read():
        return (profiler.get_counter("gateway_stream_tokens"),
                profiler.get_counter("gateway_stream_sends"))

    def rose(self):
        now = self._read()
        return now[0] - self.base[0], now[1] - self.base[1]


class _Scripted(object):
    """A server whose ``generate`` hands out the stream it was given."""

    def __init__(self, stream):
        self.stream = stream
        self.taken = threading.Event()

    def generate(self, prompt, **kw):
        self.taken.set()
        return self.stream


def _stream(tokens=(), end=None):
    """A stream that already holds ``tokens``; ``end`` is ``"length"``
    for a finished one, an exception for a failed one."""
    s = GenerationStream([1, 2], max_new_tokens=64)
    for t in tokens:
        s._push(t)
    if isinstance(end, Exception):
        s._fail(end)
    elif end is not None:
        s._finish(end)
    return s


@pytest.fixture(scope="module")
def gen_server():
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = MAX_LEN
    with fluid.unique_name.guard():
        infer_prog, startup, _n, _l = gpt.build_gpt_infer(cfg, MAX_LEN)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    engine = DecodeEngine(cfg, scope=scope, slots=4, max_len=MAX_LEN,
                          prefill_buckets=[8, MAX_LEN],
                          param_program=infer_prog)

    class Predictor(object):
        def run(self, feeds):
            return [np.asarray(feeds[0])]

        def clone(self, share_plans=True):
            return self

    server = serving.InferenceServer(
        Predictor(), max_batch_size=4, batch_timeout_ms=2.0,
        num_workers=1, decode_engine=engine,
    ).start(warmup_inputs=[np.ones((1, 4), np.float32)])
    yield server
    server.stop()


# -- the bytes are the parent's ----------------------------------------------
SEEDED = {"temperature": 0.8, "top_k": 16, "seed": 77}


@pytest.mark.parametrize("sampling", [{}, SEEDED], ids=["greedy", "seeded"])
@pytest.mark.parametrize("n", [1, 2, 9])
def test_body_is_the_three_write_framing_byte_for_byte(gen_server, tmp_path,
                                                       n, sampling):
    prompt = [3, 7, 11]
    expect = gen_server.generate(prompt, max_new_tokens=n, **sampling)\
        .tokens(timeout=60)
    g = _Gateway(gen_server, tmp_path)
    try:
        head, body = _raw_post(g.port, dict(
            {"prompt_ids": prompt, "max_new_tokens": n}, **sampling))
    finally:
        g.stop()
    assert head.startswith(b"HTTP/1.1 200")
    assert b"Transfer-Encoding: chunked" in head
    payloads, ended = _chunks(body)
    assert ended and len(payloads) == n + 1
    done = _event(payloads[-1])
    assert done["done"] and done["tokens"] == n
    assert done["finish_reason"] == "length"
    events = [_token_event(t) for t in expect]
    events.append(
        ("data: %s\n\n" % json.dumps(done, sort_keys=True)).encode("utf-8"))
    assert body == _three_write_framing(events)


# -- a chunk is one send -------------------------------------------------------
def test_engine_stream_is_one_chunk_a_send_and_counted(gen_server, tmp_path):
    """Against the real engine: every send is one whole chunk, a token
    event each and last the done event with the body's end."""
    n = 12
    counters = _Counters()
    g = _Gateway(gen_server, tmp_path)
    try:
        _head, body = _raw_post(g.port, {"prompt_ids": [5, 9],
                                         "max_new_tokens": n})
        assert g.status()["status"] == 200
    finally:
        g.stop()
    writes = g.body_writes()
    assert b"".join(writes) == body and len(writes) == n + 1
    for w in writes[:-1]:
        payloads, ended = _chunks(w, whole=False)
        assert len(payloads) == 1 and not ended
        assert "token" in _event(payloads[0])
    last, ended = _chunks(writes[-1])
    assert ended and len(last) == 1 and _event(last[0])["done"]
    assert counters.rose() == (n, n + 1)


@pytest.mark.parametrize("n", [1, 5])
def test_one_send_a_token_event_and_one_for_done(tmp_path, n):
    """A token handed over alone is one send; the done event and the
    last chunk share one."""
    stream = _stream()
    server = _Scripted(stream)
    counters = _Counters()
    g = _Gateway(server, tmp_path)
    got = {}
    client = threading.Thread(
        target=lambda: got.update(
            body=_raw_post(g.port, {"prompt_ids": [1]})[1]))
    try:
        client.start()
        assert server.taken.wait(30)
        for i in range(n):
            stream._push(100 + i)
            end = time.monotonic() + 30
            while len(g.body_writes()) < i + 1:
                assert time.monotonic() < end
                time.sleep(0.002)
        stream._finish("length")
        client.join(timeout=30)
        assert not client.is_alive()
        assert g.status()["status"] == 200
    finally:
        g.stop()
    writes = g.body_writes()
    assert len(writes) == n + 1
    for i, w in enumerate(writes[:-1]):
        assert w == _three_write_framing([_token_event(100 + i)])[:-5]
    payloads, ended = _chunks(writes[-1])
    assert ended and len(payloads) == 1
    assert _event(payloads[0])["tokens"] == n
    assert got["body"] == b"".join(writes)
    assert counters.rose() == (n, n + 1)


# -- a reader that fell behind --------------------------------------------------
@pytest.mark.parametrize("k", [1, 3, 8])
def test_queued_tokens_are_k_sends_and_k_events_in_order(tmp_path, k):
    """Tokens that wait in the queue go out as they would have one by
    one: a chunk and a send each, read by the client in order."""
    toks = list(range(40, 40 + k))
    server = _Scripted(_stream(toks, end="length"))
    counters = _Counters()
    g = _Gateway(server, tmp_path)
    try:
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", g.port, timeout=30)
        conn.request("POST", "/v1/generate",
                     body=json.dumps({"prompt_ids": [1]}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        events = [json.loads(line[6:]) for line in resp
                  if line.startswith(b"data: ")]
        conn.close()
        assert g.status()["status"] == 200
    finally:
        g.stop()
    assert [e["token"] for e in events[:-1]] == toks
    assert events[-1]["done"] and events[-1]["tokens"] == k
    writes = g.body_writes()
    assert writes[:-1] == [_three_write_framing([_token_event(t)])[:-5]
                           for t in toks]
    assert counters.rose() == (k, k + 1)


# -- the counters, on every way out -------------------------------------------
WAYS_OUT = {
    # name: (tokens held, how the stream ends, body write that fails,
    #        request extras, status, reason, tokens on the wire, sends)
    "done": ([7, 8, 9], "length", None, {}, 200, None, 3, 4),
    "client_reset": ([7, 8, 9], "length",
                     (0, ConnectionResetError("reset")), {},
                     499, "client_disconnected", 0, 0),
    "client_reset_midstream": ([7, 8, 9], "length",
                               (2, ConnectionResetError("reset")), {},
                               499, "client_disconnected", 2, 2),
    "client_reset_on_done": ([7, 8, 9], "length",
                             (3, BrokenPipeError("pipe")), {},
                             499, "client_disconnected", 3, 3),
    "stalled_write": ([7, 8, 9], "length", (0, socket.timeout("stall")),
                      {}, 499, "client_stalled", 0, 0),
    "stalled_midstream": ([7, 8, 9], "length", (1, socket.timeout("stall")),
                          {}, 499, "client_stalled", 1, 1),
    "stalled_on_done": ([7, 8, 9], "length", (3, socket.timeout("stall")),
                        {}, 499, "client_stalled", 3, 3),
    "deadline": ([7, 8], None, None, {"deadline_ms": 150.0},
                 504, "deadline", 2, 3),
    "stream_failure": ([7, 8], RuntimeError("device fell over"), None, {},
                       500, "stream_error", 2, 3),
}


@pytest.mark.parametrize("way", sorted(WAYS_OUT))
def test_stream_counters_on_every_way_out(tmp_path, way):
    held, end, fail, extras, status, reason, on_wire, sends = WAYS_OUT[way]
    stream = _stream(held, end=end)
    counters = _Counters()
    g = _Gateway(_Scripted(stream), tmp_path, fail=fail)
    try:
        _head, body = _raw_post(g.port, dict({"prompt_ids": [1]}, **extras))
        line = g.status()
    finally:
        g.stop()
    assert line["status"] == status and line.get("reason") == reason
    if reason != "client_disconnected":   # that one is raised, not returned
        assert line.get("tokens", 0) == on_wire
    assert counters.rose() == (on_wire, sends)
    assert len(g.body_writes()) == sends
    if fail is not None:
        # nobody left to decode for, unless the stream had ended already
        assert stream._cancelled == (fail[0] < len(held))
        return
    # what ended the stream rides in band, in one send with the body's end
    payloads, ended = _chunks(body)
    assert ended
    assert [_event(p)["token"] for p in payloads[:-1]] == held
    last = _event(payloads[-1])
    assert last["emitted_count"] == on_wire
    if way == "deadline":
        assert last["error"] == "deadline" and stream._cancelled
    elif way == "stream_failure":
        assert "device fell over" in last["error"]
    else:
        assert last["done"]
    assert g.body_writes()[-1].endswith(b"\r\n0\r\n\r\n")


@pytest.mark.parametrize("traced", [True, False],
                         ids=["tracing_on", "tracing_off"])
@pytest.mark.parametrize("way", sorted(WAYS_OUT))
def test_handler_cpu_is_counted_on_every_way_out(tmp_path, monkeypatch, way,
                                                 traced):
    """``gateway_handler_cpu_us``: the CPU time the handler's thread held
    over the stream, in whole microseconds, bumped once, where the stream
    ends, on every way out of the writer: two reads of the thread's CPU
    clock a stream, however many tokens. It is a counter (``/metrics``),
    so it counts with the tracer off too."""
    held, end, fail, extras, status, _reason, _wire, _sends = WAYS_OUT[way]
    before = profiler.get_counter("gateway_handler_cpu_us")
    reads = []
    real = time.thread_time
    fluid.set_flags({"FLAGS_obs_trace": traced})
    try:
        g = _Gateway(_Scripted(_stream(held, end=end)), tmp_path, fail=fail)
        monkeypatch.setattr(time, "thread_time",
                            lambda: (reads.append(1), real())[1])
        try:
            _raw_post(g.port, dict({"prompt_ids": [1]}, **extras))
            assert g.status()["status"] == status
        finally:
            g.stop()
    finally:
        fluid.set_flags({"FLAGS_obs_trace": True})
    rose = profiler.get_counter("gateway_handler_cpu_us") - before
    # headers, a few events and a JSON dump: tens of microseconds at the
    # least, and nowhere near the second the deadline's wait lasts asleep
    assert 10 <= rose < 100_000
    assert len(reads) == 2


def test_counters_rise_while_a_long_stream_is_open(tmp_path):
    """``/metrics`` does not wait for a stream's end: the counters rise
    every ``_COUNT_EVERY`` tokens, and the end adds the rest."""
    from paddle_tpu.serving import gateway

    every = gateway._COUNT_EVERY
    stream = _stream(range(every + 3))
    server = _Scripted(stream)
    counters = _Counters()
    g = _Gateway(server, tmp_path)
    client = threading.Thread(
        target=lambda: _raw_post(g.port, {"prompt_ids": [1]}))
    try:
        client.start()
        end = time.monotonic() + 30
        while len(g.body_writes()) < every + 3:
            assert time.monotonic() < end
            time.sleep(0.002)
        assert counters.rose() == (every, every)
        stream._finish("length")
        client.join(timeout=30)
        assert not client.is_alive()
        assert g.status()["status"] == 200
    finally:
        g.stop()
    assert counters.rose() == (every + 3, every + 4)


# -- the stream's own contract -------------------------------------------------
def test_budget_of_the_whole_stream_raises_timeout():
    s = _stream([5])
    it = s.stream_tokens(timeout=0.05)
    assert next(it) == 5
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        next(it)
    assert time.monotonic() - t0 < 5
    # a budget already spent raises before it looks at the queue
    with pytest.raises(TimeoutError):
        next(_stream([5], end="length").stream_tokens(timeout=0.0))


def test_error_is_raised_after_the_tokens_before_it():
    s = _stream([1, 2, 3], end=RuntimeError("boom"))
    got = []
    with pytest.raises(RuntimeError, match="boom"):
        for tok in s.stream_tokens(timeout=5):
            got.append(tok)
    assert got == [1, 2, 3]
    assert s._q.empty()


def test_tokens_come_in_the_order_they_were_pushed():
    s = _stream([1, 2, 3])
    it = s.stream_tokens(timeout=5)
    assert [next(it) for _ in range(3)] == [1, 2, 3]
    s._push(4)
    assert next(it) == 4
    s._push(5)
    s._finish("eos")
    assert next(it) == 5
    with pytest.raises(StopIteration):
        next(it)
    assert list(_stream([], end="length").stream_tokens()) == []
    assert list(_stream([9], end="length")) == [9]


def test_hand_over_from_another_thread_loses_and_reorders_nothing():
    """One producer, one consumer, the interpreter handing the lock over
    every 10 microseconds: what is read is what was pushed."""
    import sys

    s = _stream()
    n = 5000

    def produce():
        for i in range(n):
            s._push(i)
        s._finish("length")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t = threading.Thread(target=produce)
        t.start()
        got = list(s.stream_tokens(timeout=60))
        t.join(timeout=60)
        assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert got == list(range(n))


# -- the chaos seam ------------------------------------------------------------
def test_die_after_tokens_fires_after_the_exact_token(tmp_path, monkeypatch):
    """The seam fires when exactly two tokens reached the wire, though
    the queue held all four."""
    toks = [11, 12, 13, 14]
    g = _Gateway(_Scripted(_stream(toks, end="length")), tmp_path)
    at_kill = []
    monkeypatch.setattr(
        chaos.os, "kill",
        lambda pid, sig: at_kill.append(list(g.body_writes())))
    chaos.clear()
    chaos.install(chaos.FaultPlan(die_after_tokens=2))
    counters = _Counters()
    try:
        _head, body = _raw_post(g.port, {"prompt_ids": [1]})
        assert g.status()["status"] == 200
    finally:
        chaos.clear()
        g.stop()
    assert len(at_kill) == 1
    assert at_kill[0] == [_three_write_framing([_token_event(t)])[:-5]
                          for t in toks[:2]]
    writes = g.body_writes()
    assert len(writes) == len(toks) + 1
    assert b"".join(writes) == body
    assert counters.rose() == (len(toks), len(toks) + 1)


def test_a_plan_armed_midstream_counts_later_streams_only(tmp_path,
                                                          monkeypatch):
    """A stream asks for the plan once, when it starts: the tokens of a
    stream that was open when the plan was armed are not counted, those
    of the next one are."""
    first = _stream([1])
    server = _Scripted(first)
    g = _Gateway(server, tmp_path)
    killed = []
    monkeypatch.setattr(chaos.os, "kill",
                        lambda pid, sig: killed.append(len(g.body_writes())))
    chaos.clear()
    client = threading.Thread(
        target=lambda: _raw_post(g.port, {"prompt_ids": [1]}))
    try:
        client.start()
        end = time.monotonic() + 30
        while len(g.body_writes()) < 1:
            assert time.monotonic() < end
            time.sleep(0.002)
        chaos.install(chaos.FaultPlan(die_after_tokens=1))
        first._push(2)
        first._finish("length")
        client.join(timeout=30)
        assert not client.is_alive() and killed == []
        server.stream = _stream([3, 4], end="length")
        _raw_post(g.port, {"prompt_ids": [1]})
        assert killed == [4]   # 2 tokens + done of the first, 1 of the second
    finally:
        chaos.clear()
        g.stop()
