"""The committed limits of ``gpt2l-train-fsdp4`` against the chip's own
calibration readings (``benchmark/calibrate.py``, PR 25, call c3), as
``test_control_and_broken_path`` holds the older cells' limits to
``data/chip_readings.jsonl``: every sound run correct, every fp8 control
not. The readings of a cell added after the first benchmark live in a
file of their own, ``data/chip_readings.<cell>.jsonl``."""

import json
import os

import pytest

from benchmark.harness import cells, checks

CELL = "gpt2l-train-fsdp4"


def _chip_readings():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "chip_readings.%s.jsonl" % CELL)
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    return [(d["workload"], d["who"], d["seed"], d["numbers"])
            for d in lines]


@pytest.mark.parametrize(
    "workload,who,seed,numbers", _chip_readings(),
    ids=lambda v: str(v) if not isinstance(v, dict) else "")
def test_committed_limits_pass_the_program_and_fail_the_control(
        workload, who, seed, numbers):
    assert workload == CELL
    limits = cells.Cell(workload).check_limits
    assert limits, "the cell's limits file is empty"
    judged = checks.compare(numbers, limits, {})
    assert checks.correct(judged) == (who == "program"), judged["rows"]


def test_both_sides_of_each_seed_were_read():
    sides = {}
    for _, who, seed, _ in _chip_readings():
        sides.setdefault(seed, set()).add(who)
    assert sides and all(s == {"program", "control"} for s in sides.values())
