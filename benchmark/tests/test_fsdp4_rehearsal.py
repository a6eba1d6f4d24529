"""The four-chip cell that PR 24 could not prove on the chip (PERF.md,
Open questions, first row), kept alive as a rehearsal: its configuration,
traffic mix and collective readers live in ``data/fsdp4/`` and are laid
into a copy of the benchmark as a later PR would add them; the cell then
runs on four virtual CPU devices through ``CompiledProgram.with_mesh``
with the reference laid over the same four devices."""

import argparse
import json
import os
import shutil

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import cells, checks
from benchmark.traffic_kinds import train_steps

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "fsdp4")


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_fsdp4_cell_rehearses(tmp_path):
    bench = tmp_path / "benchmark"
    shutil.copytree(cells.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(DATA, "gpt2-large.json"), bench / "configs")
    shutil.copy(os.path.join(DATA, "lm-s1024-b16.json"), bench / "traffic")
    for name in ("collective_ms_per_step.fsdp", "collective_exposed_pct.fsdp"):
        shutil.copy(os.path.join(DATA, name + ".py"), bench / "layer_metrics")
    (bench / "limits" / "gpt2l-train-fsdp4.json").write_text("{}")
    manifest = cells.manifest()
    manifest["configs"].append({
        "name": "gpt2-large", "source": "x", "reduced": [], "why": "x",
        "file": "benchmark/configs/gpt2-large.json"})
    manifest["workloads"].append({
        "name": "gpt2l-train-fsdp4", "config": "gpt2-large",
        "traffic": "lm-s1024-b16", "chips": 4, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = cells.Cell("gpt2l-train-fsdp4", root=str(tmp_path),
                      bench_dir=str(bench))
    assert cell.chips == 4 and cell.config["mesh"]["fsdp"]
    assert callable(cell.module("layer_metrics",
                                "collective_exposed_pct.fsdp").read)
    args = argparse.Namespace(seed=6, seconds=1.0, trace=0, rehearse=True)
    ctx = bench_run.Context(cell, args, jax.devices())
    ctx.note = lambda *a, **k: None
    facts = train_steps.run(ctx)
    assert facts["attempted"] > 0 and facts["failed"] == 0
    got = checks.summary_values(facts["checks"])
    # toy widths on the CPU: the mesh step follows the reference closely
    assert got["loss_gap_step1"] < 1e-3
    assert got["grad_norm_gap_worst_leaf"] < 0.05
    assert got["update_norm_gap_median_leaf"] < 0.01
