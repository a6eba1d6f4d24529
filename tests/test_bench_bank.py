"""BENCH_BANK.json results-bank: successful chip measurements persist with
provenance (bank-the-best per slot, guarded prefixes), and the bank is a
record only — bench.py emits nothing from it. A bench run that finds no
TPU exits non-zero and prints no result line.

The bank module lives in bench.py (repo root); these tests exercise it
against a temp bank file via BENCH_BANK_PATH.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def bench_mod(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_BANK_PATH", str(tmp_path / "bank.json"))
    sys.path.insert(0, ROOT)
    import bench

    bench = importlib.reload(bench)  # pick up the env-driven BANK_PATH
    yield bench
    monkeypatch.delenv("BENCH_BANK_PATH", raising=False)
    importlib.reload(bench)  # restore the real repo-root BANK_PATH


def test_bank_write_and_best(bench_mod):
    b = bench_mod
    assert b.load_bank() == {}
    assert b.bank_write(
        "resnet50",
        {"metric": b.METRIC, "value": 1000.0, "unit": b.UNIT, "batch": 256,
         "device": "tpu", "remat": False},
    )
    e = b.load_bank()["resnet50"]
    # provenance fields stamped on write
    assert e["git_sha"] and e["measured_at"].endswith("Z")
    # bank-the-best: slower re-measurement does not overwrite
    assert not b.bank_write(
        "resnet50",
        {"metric": b.METRIC, "value": 900.0, "unit": b.UNIT, "batch": 64,
         "device": "tpu", "remat": False},
    )
    assert b.load_bank()["resnet50"]["value"] == 1000.0
    # faster one does
    assert b.bank_write(
        "resnet50_remat",
        {"metric": b.METRIC, "value": 1100.0, "unit": b.UNIT, "batch": 256,
         "device": "tpu", "remat": True},
    )
    slot, best = b.bank_best("resnet50")
    assert slot == "resnet50_remat" and best["value"] == 1100.0


def test_bank_best_never_promotes_serving_entry(bench_mod):
    """The BENCH_SERVING=1 rung banks requests/sec through the
    dynamic-batching runtime — a different convention from the headline
    tokens/sec metric. A generic prefix match must never promote it
    (same guard as the hostfeed rung); an explicit 'serving' prefix
    retrieves it."""
    b = bench_mod
    b.bank_write(
        "gpt_serving",
        {"metric": "gpt2_serving_throughput", "value": 99999.0,
         "unit": "requests/sec/chip", "batch": 8, "seq_len": 128,
         "device": "tpu", "serving": True, "offline_rps": 120000.0,
         "p99_ms": 12.0, "batch_fill": 0.97, "bucket_hit_rate": 1.0},
    )
    b.bank_write(
        "gpt_seq1024",
        {"metric": "gpt2_small_lm_throughput", "value": 100.0,
         "unit": "tokens/sec/chip", "batch": 16, "seq_len": 1024,
         "device": "tpu"},
    )
    slot, e = b.bank_best("gpt")
    assert slot == "gpt_seq1024" and not e.get("serving")
    slot, e = b.bank_best("gpt_serving")
    assert e["serving"] is True and e["value"] == 99999.0
    # serving facts survive the bank round-trip for provenance
    assert e["p99_ms"] == 12.0 and e["bucket_hit_rate"] == 1.0


def test_bank_best_never_promotes_prefix_entry(bench_mod):
    """The BENCH_DECODE prefix rung banks tokens/sec/user at ~90%
    prefix share — an amortized rate the cold-prompt 'gpt_decode'
    headline must never inherit (mirror of the serving/hostfeed/decode
    guards). Only a prefix containing 'prefix' retrieves it, and its
    TTFT/share facts survive the bank round-trip."""
    b = bench_mod
    b.bank_write(
        "gpt_decode_prefix",
        {"metric": "gpt2_decode_prefix_throughput", "value": 88888.0,
         "unit": "tokens/sec/user", "streams": 8, "max_len": 256,
         "device": "tpu", "decode": True, "prefix_cache": True,
         "ttft_ms": 3.2, "prefix_share": 0.9, "prefix_hit_rate": 0.97},
    )
    b.bank_write(
        "gpt_decode",
        {"metric": "gpt2_decode_throughput", "value": 120.0,
         "unit": "tokens/sec/user", "streams": 8, "max_len": 256,
         "device": "tpu", "decode": True},
    )
    # the generic decode prefix must pick the COLD rung despite the
    # prefix rung's (much) larger value
    slot, e = b.bank_best("gpt_decode")
    assert slot == "gpt_decode" and not e.get("prefix_cache")
    # and the training-headline prefix sees neither decode rung
    slot, e = b.bank_best("gpt")
    assert slot is None or not e.get("decode")
    slot, e = b.bank_best("gpt_decode_prefix")
    assert e["prefix_cache"] is True and e["value"] == 88888.0
    assert e["ttft_ms"] == 3.2 and e["prefix_share"] == 0.9


def test_bank_best_never_promotes_paged_or_spec_entry(bench_mod):
    """The ISSUE 16 rungs bank amortized rates the cold 'gpt_decode'
    headline must never inherit: gpt_decode_paged serves seq-4k streams
    off a small anchored pool, and gpt_decode_spec multiplies
    tokens/sec by drafting — both are guarded behind their own prefix
    words, mirroring the serving/prefix guards."""
    b = bench_mod
    b.bank_write(
        "gpt_decode_paged",
        {"metric": "gpt2_decode_paged_throughput", "value": 77777.0,
         "unit": "tokens/sec/user", "streams": 8, "max_len": 4096,
         "device": "tpu", "decode": True, "paged": True,
         "paged_block": 16, "pool_blocks": 129, "oom_sheds": 0},
    )
    b.bank_write(
        "gpt_decode_spec",
        {"metric": "gpt2_decode_spec_throughput", "value": 66666.0,
         "unit": "tokens/sec/user", "streams": 8, "max_len": 256,
         "device": "tpu", "decode": True, "spec": True,
         "spec_tokens": 4, "spec_speedup": 2.4, "spec_acceptance": 0.8,
         "draft_accuracy": 0.9},
    )
    b.bank_write(
        "gpt_decode",
        {"metric": "gpt2_decode_throughput", "value": 120.0,
         "unit": "tokens/sec/user", "streams": 8, "max_len": 256,
         "device": "tpu", "decode": True},
    )
    # the cold decode headline sees neither v2 rung
    slot, e = b.bank_best("gpt_decode")
    assert slot == "gpt_decode"
    assert not e.get("paged") and not e.get("spec")
    # each v2 rung is retrievable only by its own prefix word, with its
    # facts intact through the bank round-trip
    slot, e = b.bank_best("gpt_decode_paged")
    assert e["paged"] is True and e["pool_blocks"] == 129
    slot, e = b.bank_best("gpt_decode_spec")
    assert e["spec"] is True and e["spec_speedup"] == 2.4
    assert e["spec_acceptance"] == 0.8 and e["draft_accuracy"] == 0.9


def test_bank_best_never_promotes_tp_entry(bench_mod):
    """The SPMD tensor-parallel rung banks tokens/sec/user measured
    across a {"model": TP} mesh — a rate that spends TP devices per
    user and must never replace the single-device 'gpt_decode'
    headline. Only a prefix containing 'tp' retrieves it, and the mesh
    width survives the bank round-trip."""
    b = bench_mod
    b.bank_write(
        "gpt_decode_tp",
        {"metric": "gpt2_decode_tp_throughput", "value": 55555.0,
         "unit": "tokens/sec/user", "streams": 8, "max_len": 256,
         "device": "tpu", "decode": True, "tp": True, "tp_degree": 2},
    )
    b.bank_write(
        "gpt_decode",
        {"metric": "gpt2_decode_throughput", "value": 120.0,
         "unit": "tokens/sec/user", "streams": 8, "max_len": 256,
         "device": "tpu", "decode": True},
    )
    # the cold single-device headline never inherits the TP rate
    slot, e = b.bank_best("gpt_decode")
    assert slot == "gpt_decode" and not e.get("tp")
    # nor does the training-headline prefix see either decode rung
    slot, e = b.bank_best("gpt")
    assert slot is None or not e.get("decode")
    # the tp rung is retrievable by its own prefix with its facts intact
    slot, e = b.bank_best("gpt_decode_tp")
    assert e["tp"] is True and e["tp_degree"] == 2
    assert e["value"] == 55555.0


def test_bench_without_a_chip_exits_nonzero_and_prints_no_result(tmp_path):
    """No TPU -> the first child fails with kind no_tpu and the parent
    ends the run: non-zero exit, nothing on stdout (no replayed bank
    line, no CPU figure), even with a bank full of chip numbers."""
    bank_path = tmp_path / "bank.json"
    bank_path.write_text(json.dumps({
        "resnet50": {"metric": "resnet50_train_throughput", "value": 1384.0,
                     "unit": "images/sec/chip", "batch": 256,
                     "device": "tpu", "git_sha": "abc1234",
                     "measured_at": "2026-07-30T00:00:00Z"},
    }))
    env = dict(os.environ, BENCH_BANK_PATH=str(bank_path),
               JAX_PLATFORMS="cpu", BENCH_TIMEOUT="240")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, env=env, timeout=200, cwd=ROOT,
    )
    assert out.returncode != 0, out.stdout + out.stderr
    assert out.stdout.strip() == "", out.stdout
    assert "no TPU" in out.stderr
    assert json.loads(bank_path.read_text())["resnet50"]["value"] == 1384.0


def test_bank_write_preserves_census_when_new_entry_lacks_it(bench_mod):
    """A faster re-measurement whose live census was unavailable must
    not erase the slot's banked flops/bytes baseline (PERF.md's
    bytes-budget table sources it from the bank)."""
    b = bench_mod
    assert b.bank_write(
        "resnet50",
        {"metric": b.METRIC, "value": 1000.0, "unit": b.UNIT, "batch": 256,
         "device": "tpu", "flops": 6.1e12, "bytes_accessed": 7.9e10,
         "out_bytes": 1.0e8, "census_source": "live_census"},
    )
    # faster, census-less run: throughput updates, census fields carry
    assert b.bank_write(
        "resnet50",
        {"metric": b.METRIC, "value": 1200.0, "unit": b.UNIT, "batch": 256,
         "device": "tpu"},
    )
    e = b.load_bank()["resnet50"]
    assert e["value"] == 1200.0
    assert e["flops"] == 6.1e12
    assert e["bytes_accessed"] == 7.9e10
    assert e["census_source"] == "live_census"
    # a run WITH a fresh census replaces them
    assert b.bank_write(
        "resnet50",
        {"metric": b.METRIC, "value": 1300.0, "unit": b.UNIT, "batch": 256,
         "device": "tpu", "flops": 6.2e12, "bytes_accessed": 7.8e10,
         "out_bytes": 1.1e8, "census_source": "live_census"},
    )
    assert b.load_bank()["resnet50"]["flops"] == 6.2e12
    # carry is all-or-nothing: a PARTIAL fresh census (backend without
    # the out-bytes key) must not get the old run's out_bytes spliced in
    assert b.bank_write(
        "resnet50",
        {"metric": b.METRIC, "value": 1400.0, "unit": b.UNIT, "batch": 256,
         "device": "tpu", "flops": 6.3e12, "bytes_accessed": 7.7e10,
         "census_source": "live_census"},
    )
    e = b.load_bank()["resnet50"]
    assert e["flops"] == 6.3e12
    assert "out_bytes" not in e
