"""``sse_sends_per_token``: the reader over the gateway's two stream
counters, on fixture counters, on a program that does not count its sends
(the parent of the PR that added the counter: nothing to read, nothing
raised), and on the rehearsal of a serving cell, whose handlers send a
token's chunk in one piece.
"""

import types

import pytest

from benchmark.harness import cells, checks
from benchmark.tests.test_control_and_broken_path import (_context,
                                                          _with_limits)
from benchmark.traffic_kinds import serve_closed

NAME = "sse_sends_per_token"
SENDS = "gateway_stream_sends"
TOKENS = "gateway_stream_tokens"
SERVE_CELLS = ("gpt2s-serve-chat", "kanana2-serve-chat4k",
               "solar2-serve-reason4k")


def _read(cell, counters):
    ev = types.SimpleNamespace(counters=counters, spans=[], requests=[],
                               window=(0.0, 40.0))
    return cells.Cell(cell).module("layer_metrics", NAME).read(ev)


@pytest.mark.parametrize("cell", SERVE_CELLS)
@pytest.mark.parametrize("counters,want", [
    ({TOKENS: 12800, SENDS: 38400}, 3.0),     # three writes a chunk
    ({TOKENS: 12800, SENDS: 13000}, 13000 / 12800),   # + a done a request
    ({TOKENS: 12800, SENDS: 6600}, 6600 / 12800),     # handlers fell behind
    ({TOKENS: 0, SENDS: 2}, None),            # streams that ended empty
    ({TOKENS: 0, SENDS: 0}, None),            # an idle window
    ({TOKENS: 12800, "decode_tokens": 12800}, None),   # the parent
    ({}, None),
])
def test_reader_on_fixture_counters(cell, counters, want):
    got = _read(cell, counters)
    assert got is None if want is None else got == pytest.approx(want)


def test_manifest_lists_it_for_the_serving_cells():
    (entry,) = [m for m in cells.manifest()["per_layer"]
                if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "sends", "better": "lower",
        "source": "program_counter", "layer": "request path",
        "moves": "tpot_p90_ms", "workloads": list(SERVE_CELLS)}


def test_rehearsal_sends_a_token_in_one_piece(monkeypatch):
    _with_limits(monkeypatch, {"served_logit_gap_mean": 1e-4})
    got = serve_closed.run(_context("gpt2s-serve-chat", 6, 2.0)[1])
    assert got["attempted"] > 0 and got["failed"] == 0
    assert checks.correct(got["checks"])
    c = got["counters"]
    # counted when a stream ends: the tokens of the requests that ended
    # in the window, whenever they were made
    assert c[TOKENS] > 50
    # a send a token and one a request's done event, of requests a few
    # tokens long here: well under the three sends of a chunk in pieces
    assert c[SENDS] <= c[TOKENS] + c["decode_requests"] + 6
    assert 0 < _read("gpt2s-serve-chat", c) < 2.0
