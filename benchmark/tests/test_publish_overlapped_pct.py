"""``publish_overlapped_pct``: the reader over the engine's two
publication counters, on fixture counters, on a program that has neither
(the parent of the PR that added them: nothing to read, nothing raised),
and on the rehearsal of a serving cell, where the counters have to add up
to the tokens the engine made.
"""

import types

import pytest

from benchmark.harness import cells, checks
from benchmark.tests.test_control_and_broken_path import (_context,
                                                          _with_limits)
from benchmark.traffic_kinds import serve_closed

NAME = "publish_overlapped_pct"
UNDER = "decode_tokens_published_overlapped"
BARE = "decode_tokens_published_exposed"
SERVE_CELLS = ("gpt2s-serve-chat", "kanana2-serve-chat4k",
               "solar2-serve-reason4k")


def _read(cell, counters):
    ev = types.SimpleNamespace(counters=counters, spans=[], requests=[],
                               window=(0.0, 40.0))
    return cells.Cell(cell).module("layer_metrics", NAME).read(ev)


@pytest.mark.parametrize("cell", SERVE_CELLS)
@pytest.mark.parametrize("counters,want", [
    ({"decode_tokens": 12800, UNDER: 12608, BARE: 192}, 98.5),
    ({"decode_tokens": 64, UNDER: 64}, 100.0),
    ({"decode_tokens": 64, BARE: 64}, 0.0),
    ({"decode_tokens": 0, UNDER: 0, BARE: 0}, None),   # an idle window
    ({"decode_tokens": 12800, "decode_steps": 200}, None),   # the parent
    ({}, None),
])
def test_reader_on_fixture_counters(cell, counters, want):
    got = _read(cell, counters)
    assert got is None if want is None else got == pytest.approx(want)


def test_manifest_lists_it_for_the_serving_cells():
    (entry,) = [m for m in cells.manifest()["per_layer"]
                if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_tok_per_s", "workloads": list(SERVE_CELLS)}


def test_rehearsal_counts_every_token_once(monkeypatch):
    _with_limits(monkeypatch, {"served_logit_gap_mean": 1e-4})
    got = serve_closed.run(_context("gpt2s-serve-chat", 6, 2.0)[1])
    assert got["attempted"] > 0 and got["failed"] == 0
    assert checks.correct(got["checks"])
    c = got["counters"]
    assert c["decode_tokens"] > 50
    # a token is counted where it is made and again, a tick later, where
    # it is handed to its stream: the two counts are equal over the tokens
    # whose making and handing-over both lie inside the window, and the
    # window's two edges each cut through at most one tick's tokens, one
    # a stream
    streams = cells.Cell("gpt2s-serve-chat").traffic["toy"]["clients"]
    assert abs(c[UNDER] + c[BARE] - c["decode_tokens"]) <= streams
    # four closed-loop clients keep a stream active at nearly every tick
    assert _read("gpt2s-serve-chat", c) > 80.0
