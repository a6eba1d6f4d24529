"""Three things ``correct`` has to be shown to do, at a size a test run can
hold (CPU, toy widths, Pallas interpreter):

- the control fails: the reference computed one precision below the
  configuration's, put in the program's place, reads several times what
  the program reads (on the chip, at the cells' own sizes: PERF.md);
- a run whose timed path is broken underneath comes out not correct:
  a train step that returns its state unchanged, a served token altered
  where it is produced. These drive the traffic kind's ``run`` itself,
  past the harness's look for a chip;
- the committed limits files judge the chip's own recorded readings: every
  sound run correct, every control not.
"""

import argparse

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import cells, checks
from benchmark.references import gpt as gpt_ref
from benchmark.traffic_kinds import serve_closed, train_steps


def _context(workload, seed, seconds=1.0):
    cell = cells.Cell(workload)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0,
                              rehearse=True)
    ctx = bench_run.Context(cell, args, jax.devices())
    ctx.note = lambda *a, **k: None
    return cell, ctx


def _with_limits(monkeypatch, limits):
    monkeypatch.setattr(cells.Cell, "check_limits",
                        property(lambda self: limits))


def test_train_control_fp8_reads_far_above_the_program():
    cell, ctx = _context("gpt2s-train-s1024", seed=5)
    step = cell.family.build_train(ctx.config, ctx.traffic, ctx.place, True)
    got, batches, _t = train_steps.first_steps(
        step, cell.family, cell.reference, ctx.config, ctx.traffic, 5)
    step.close()
    ref = train_steps.reference_readings(cell.reference, ctx.config, 5,
                                         batches)
    low = train_steps.reference_readings(cell.reference, ctx.config, 5,
                                         batches, "fp8")
    program = checks.summary_values(checks.train(got, ref, {}))
    control = checks.summary_values(checks.train(
        {"losses": low[0], "gnorm": low[1], "dnorm": low[2]}, ref, {}))
    # at toy widths the worst leaf is a bias whose gradient is noise on
    # both sides; the median leaf shows the precision
    name = "grad_norm_gap_median_leaf"
    assert control[name] > 3 * program[name], (program, control)


def test_serve_control_bf16_puts_other_tokens_first():
    cfg = dict(vocab_size=4096, n_embd=128, n_layer=2, n_head=4,
               n_inner=512, n_positions=64)
    params = gpt_ref.init_params(3, cfg)
    rng = np.random.default_rng(3)

    class Req(object):
        """A prompt of 40 and the reference's own greedy 24 tokens."""

        def __init__(self):
            row = [int(t) for t in rng.integers(0, 4096, 40)]
            for _ in range(24):
                lg = np.asarray(gpt_ref.logits(
                    params, np.asarray([row + [0] * (64 - len(row))]),
                    cfg["n_head"], cfg["n_layer"]))
                row.append(int(lg[0, len(row) - 1].argmax()))
            self.prompt, self.tokens = row[:40], row[40:]

    sample = [Req() for _ in range(8)]
    sound = serve_closed.served_gaps(gpt_ref, cfg, params, sample)
    low = serve_closed.served_gaps(gpt_ref, cfg, params, sample, "bf16")
    assert len(sound) == len(low) == 8 * 24
    assert max(sound) < 1e-5      # greedy by the reference itself
    assert sum(low) / len(low) > 10 * max(sum(sound) / len(sound), 1e-7)


def test_train_step_that_keeps_its_state_is_not_correct(monkeypatch):
    from benchmark.families import common

    cell, ctx = _context("gpt2s-train-s1024", seed=9)
    _with_limits(monkeypatch, {})
    sound = train_steps.run(ctx)
    values = checks.summary_values(sound["checks"])
    limits = {k: 3 * values[k] for k in (
        "loss_gap_step1", "grad_norm_gap_worst_leaf",
        "update_norm_gap_median_leaf", "update_norm_gap_worst_leaf")}
    _with_limits(monkeypatch, limits)
    assert checks.correct(train_steps.run(_context(
        "gpt2s-train-s1024", seed=9)[1])["checks"])

    honest = common.TrainStep.run

    def stuck(self, feed):
        keep = {v: self.scope.get(v) + 0 for v in self.leaf_to_var.values()}
        loss = honest(self, feed)
        for var, val in keep.items():
            self.scope.set(var, val)
        return loss

    monkeypatch.setattr(common.TrainStep, "run", stuck)
    broken = train_steps.run(_context("gpt2s-train-s1024", seed=9)[1])
    assert not checks.correct(broken["checks"])
    rows = checks.summary(broken["checks"])
    assert rows["update_norm_gap_median_leaf"][0] > 0.9   # nothing moved


def test_served_token_altered_where_it_is_produced(monkeypatch):
    from paddle_tpu.serving import decode

    _with_limits(monkeypatch, {"served_logit_gap_mean": 1e-4})
    sound = serve_closed.run(_context("gpt2s-serve-chat", 4, 2.0)[1])
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert checks.correct(sound["checks"])

    honest = decode.DecodeEngine._emit
    count = [0]

    def altered(self, slot_idx, slot, tok):
        count[0] += 1
        if count[0] % 3 == 0:
            tok = (int(tok) + 1) % self._cfg.vocab_size
        return honest(self, slot_idx, slot, tok)

    monkeypatch.setattr(decode.DecodeEngine, "_emit", altered)
    broken = serve_closed.run(_context("gpt2s-serve-chat", 4, 2.0)[1])
    assert not checks.correct(broken["checks"])


def _chip_readings():
    """(cell, who, seed, {number: reading}) of every calibration line
    kept from the chip (``benchmark/calibrate.py``, PERF.md section 2)."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "chip_readings.jsonl")
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    return [(d["workload"], d["who"], d["seed"], d["numbers"])
            for d in lines]


@pytest.mark.parametrize(
    "workload,who,seed,numbers", _chip_readings(),
    ids=lambda v: str(v) if not isinstance(v, dict) else "")
def test_committed_limits_pass_the_program_and_fail_the_control(
        workload, who, seed, numbers):
    """The chip's own readings through ``checks.correct`` with the cell's
    limits file: every sound run is correct, every control is not."""
    limits = cells.Cell(workload).check_limits
    judged = checks.compare(numbers, limits, {})
    assert checks.correct(judged) == (who == "program"), judged["rows"]
