"""The readers of ``executor_marshal``'s ``reused`` note and of the serve
step's marshal time, on made-up spans: each gives its value, and None
(never 0) on a program whose marshal notes no ``reused`` or that opens
no such span (the parent of the PR that added them)."""

import pytest

from benchmark.harness import cells
from benchmark.tests.test_program_spans import LOOP, evidence, read, span

TRAIN_CELLS = ("gpt2s-train-s1024", "bert-base-train-s384",
               "gpt2l-train-fsdp4")
SERVE_CELLS = ("gpt2s-serve-chat", "kanana2-serve-chat4k",
               "solar2-serve-reason4k")
REUSED_TRAIN = "exec_values_reused_pct.train"
REUSED_SERVE = "exec_values_reused_pct.serve"
MARSHAL_SERVE = "exec_marshal_ms.serve"


def run_with_marshals(t, notes, tid=LOOP, each=0.001):
    """One ``executor_run`` at ``t`` whose segments' marshal phases last
    ``each`` seconds and carry ``notes``."""
    marks = []
    for i, note in enumerate(notes):
        at = t + 0.004 * i
        marks.append(["executor_marshal", at, dict(note, segment=i)])
        marks.append(["executor_dispatch", at + each, {"segment": i}])
    end = t + 0.004 * len(notes)
    marks.append(["executor_writeback", end - 0.001, {"values": 3}])
    return span("executor_run", t, end, tid, prepare_ms=0.2, plan_hit=True,
                phases=marks)


def train_steps(reused):
    """Three steps of 985 values, three of them feeds; the first looks
    everything up."""
    notes = [{"values": 985, "placed": 985},
             {"values": 985, "placed": 3}, {"values": 985, "placed": 3}]
    if reused:
        for note, n in zip(notes, (0, 982, 982)):
            note["reused"] = n
    return [run_with_marshals(10.0 + i, [note], tid=1)
            for i, note in enumerate(notes)]


def serve_ticks(reused=True):
    """Three T = 1 steps (1, 2 and 6 ms of marshal) and, between the
    second and the third, a prefill window whose run is no step's: its
    values count toward the share, its marshal time toward no step."""
    def note(values, placed, n):
        return dict({"values": values, "placed": placed},
                    **({"reused": n} if reused else {}))

    out = []
    for t, each, n in ((10.0, 0.001, 176), (10.1, 0.002, 176),
                       (10.3, 0.006, 152)):
        out.append(span("decode_paged_step", t - 0.001, t + 0.02, LOOP,
                        width=1))
        out.append(run_with_marshals(t, [note(180, 4, n)], each=each))
        out.append(span("executor_fetch", t + 0.005, t + 0.019, LOOP))
    out.append(span("decode_paged_window", 10.2, 10.25, LOOP, bucket=512))
    out.append(run_with_marshals(10.201, [note(160, 6, 130)], each=0.003))
    return out


def test_reused_share_of_the_train_steps():
    got = read(REUSED_TRAIN, evidence(train_steps(reused=True)))
    assert got == pytest.approx(100.0 * (982 + 982) / (3 * 985))
    steady = evidence(train_steps(reused=True), window=(10.5, 100.0))
    assert read(REUSED_TRAIN, steady) == pytest.approx(100.0 * 982 / 985)


def test_reused_share_over_every_program_of_the_engine():
    got = read(REUSED_SERVE, evidence(serve_ticks()))
    assert got == pytest.approx(
        100.0 * (176 + 176 + 152 + 130) / (3 * 180 + 160))


def test_marshal_of_the_step_is_summed_a_step_and_leaves_the_windows_out():
    assert read(MARSHAL_SERVE, evidence(serve_ticks())) == pytest.approx(2.0)
    # two segments a step: their marshals add up
    two = [span("decode_paged_step", 9.999, 10.02, LOOP, width=1),
           run_with_marshals(10.0, [{"values": 5, "placed": 1}] * 2,
                             each=0.0015)]
    assert read(MARSHAL_SERVE, evidence(two)) == pytest.approx(3.0)


@pytest.mark.parametrize("name,spans", [
    (REUSED_TRAIN, train_steps(reused=False)),
    (REUSED_SERVE, serve_ticks(reused=False)),
    (REUSED_TRAIN, [span("executor_run", 10.0, 10.02)]),
    (REUSED_SERVE, [span("executor_run", 10.0, 10.02, LOOP)]),
    (MARSHAL_SERVE, [span("decode_paged_step", 10.0, 10.02, LOOP),
                     span("executor_run", 10.001, 10.01, LOOP)]),
    (MARSHAL_SERVE, train_steps(reused=True)),
], ids=["train-parent", "serve-parent", "train-no-phases",
        "serve-no-phases", "step-without-phases", "no-step"])
def test_nothing_to_read_is_none_and_raises_nothing(name, spans):
    # the evidence holds a span that is no reader's, so that none falls
    # back on the tracer's own buffer
    assert read(name, evidence(spans + [span("other", 1, 2)])) is None


def test_the_parents_marshal_time_is_read_all_the_same():
    """``exec_marshal_ms.serve`` reads spans the parent opens too."""
    assert read(MARSHAL_SERVE,
                evidence(serve_ticks(reused=False))) == pytest.approx(2.0)


@pytest.mark.parametrize("name,moves,cells_,better,unit", [
    (REUSED_TRAIN, "train_tok_per_s", TRAIN_CELLS, "higher", "%"),
    (REUSED_SERVE, "serve_tok_per_s", SERVE_CELLS, "higher", "%"),
    (MARSHAL_SERVE, "tpot_p90_ms", SERVE_CELLS, "lower", "ms"),
])
def test_manifest_lists_them(name, moves, cells_, better, unit):
    (entry,) = [m for m in cells.manifest()["per_layer"]
                if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_span", "layer": "executor",
                     "moves": moves, "workloads": list(cells_)}
    for cell in cells_:
        assert cells.Cell(cell).module("layer_metrics", name).read
