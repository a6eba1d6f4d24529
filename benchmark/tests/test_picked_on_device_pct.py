"""``picked_on_device_pct``: the reader over the engine's two pick
counters, on fixture counters, on a program that has neither (the parent
of the PR that added them: nothing to read, nothing raised), and on the
rehearsal of a serving cell, whose greedy clients leave every step token
picked on the device and one token a request (its window's) to neither
counter.
"""

import types

import pytest

from benchmark.harness import cells, checks
from benchmark.tests.test_control_and_broken_path import (_context,
                                                          _with_limits)
from benchmark.traffic_kinds import serve_closed

NAME = "picked_on_device_pct"
DEVICE = "decode_picks_on_device"
HOST = "decode_picks_on_host"
SERVE_CELLS = ("gpt2s-serve-chat", "kanana2-serve-chat4k",
               "solar2-serve-reason4k")


def _read(cell, counters):
    ev = types.SimpleNamespace(counters=counters, spans=[], requests=[],
                               window=(0.0, 40.0))
    return cells.Cell(cell).module("layer_metrics", NAME).read(ev)


@pytest.mark.parametrize("cell", SERVE_CELLS)
@pytest.mark.parametrize("counters,want", [
    ({"decode_tokens": 12800, DEVICE: 12600}, 100.0),
    ({"decode_tokens": 12800, DEVICE: 9450, HOST: 3150}, 75.0),
    ({"decode_tokens": 64, HOST: 63}, 0.0),
    ({"decode_tokens": 0, DEVICE: 0, HOST: 0}, None),   # an idle window
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
        "source": "program_counter", "layer": "programs",
        "moves": "tpot_p90_ms", "workloads": list(SERVE_CELLS)}


def test_rehearsal_picks_every_step_token_on_the_device(monkeypatch):
    _with_limits(monkeypatch, {"served_logit_gap_mean": 1e-4})
    got = serve_closed.run(_context("gpt2s-serve-chat", 6, 2.0)[1])
    assert got["attempted"] > 0 and got["failed"] == 0
    assert checks.correct(got["checks"])
    c = got["counters"]
    assert c["decode_tokens"] > 50
    assert c.get(HOST, 0) == 0
    # the other tokens are first tokens, picked from a window's row
    assert 0 < c["decode_tokens"] - c[DEVICE] <= c["decode_prefills"]
    assert _read("gpt2s-serve-chat", c) == 100.0
