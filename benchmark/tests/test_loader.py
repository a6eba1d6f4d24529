"""A cell, a traffic mix and a layer metric added as NEW files are found
without an edit to any file that is there."""

import json
import os
import shutil

from benchmark.harness import cells


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path
    bench = root / "benchmark"
    shutil.copytree(cells.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    manifest = cells.manifest()
    # a later PR's additions: one traffic file, one reader, two entries
    (bench / "traffic" / "lm-s512-b16.json").write_text(json.dumps({
        "kind": "train_steps", "seq_len": 512, "batch": 16,
        "fields": {"ids": {"draw": "uniform_int", "high": "vocab_size",
                           "shape": ["batch", "seq_len"]}}}))
    (bench / "layer_metrics" / "steps_counted.new-cell.py").write_text(
        "def read(ev):\n    return float(ev.facts['steps'])\n")
    (bench / "limits" / "gpt2s-train-s512.json").write_text("{}")
    manifest["workloads"].append({
        "name": "gpt2s-train-s512", "config": "gpt2-small",
        "traffic": "lm-s512-b16", "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "steps_counted.new-cell", "unit": "steps",
        "better": "higher", "source": "program_counter",
        "layer": "programs", "moves": "train_tok_per_s",
        "workloads": ["gpt2s-train-s512"]})
    for m in manifest["end_to_end"]:
        if m["name"] == "train_tok_per_s":
            m["workloads"].append("gpt2s-train-s512")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = cells.Cell("gpt2s-train-s512", root=str(root),
                      bench_dir=str(bench))
    assert cell.traffic["seq_len"] == 512
    assert cell.kind.__name__.endswith("train_steps")
    assert cell.config["n_embd"] == 768
    assert [m["name"] for m in cell.per_layer] == ["steps_counted.new-cell"]
    assert {m["name"] for m in cell.end_to_end} == {"train_tok_per_s",
                                                    "setup_s"}
    reader = cell.module("layer_metrics", "steps_counted.new-cell")

    class Ev(object):
        facts = {"steps": 7}

    assert reader.read(Ev()) == 7.0
    # the cells that were there are untouched by the additions
    old = cells.Cell("gpt2s-serve-chat", root=str(root), bench_dir=str(bench))
    assert "steps_counted.new-cell" not in [m["name"] for m in old.per_layer]


def test_every_named_file_exists():
    manifest = cells.manifest()
    for w in manifest["workloads"]:
        cell = cells.Cell(w["name"])
        assert cell.kind.run and cell.family and cell.reference
        assert isinstance(cell.check_limits, dict)
        for m in cell.per_layer:
            assert callable(cell.module("layer_metrics", m["name"]).read)
    for c in manifest["configs"]:
        assert os.path.isfile(os.path.join(cells.ROOT, c["file"]))
