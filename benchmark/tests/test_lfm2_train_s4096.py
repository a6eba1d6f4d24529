"""The cell ``lfm2-train-s4096`` at a size a test run can hold (CPU, toy
widths, Pallas interpreter):

- every file the cell names is found; the manifest lists its four new
  readers for it alone and appends it to the generic train readers, not
  to the ones that would misread it; the configuration file holds every
  number of the catalog's row, the published widths, its three cuts and
  the deployment in words;
- its rehearsal runs the traffic kind's own ``run`` (one ``Executor.run``
  a step under a ``train_step`` span that carries the counts) and comes
  out correct against the plain reference; the fp8 control reads far
  above the program;
- the ops-and-bytes functions against hand-worked numbers, and the four
  new readers on a recorded toy trace: the two shares of a peak under
  100, nothing (no raise) where the program opens no ``train_step`` span;
- the committed limits judge the chip's own recorded readings
  (``data/chip_readings.lfm2-train-s4096.jsonl``): every sound run
  correct, every fp8 control not.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import cells, checks, xplane
from benchmark.kernels import moe_train
from benchmark.references import lfm2 as ref
from benchmark.tests.test_control_and_broken_path import (_context,
                                                          _with_limits)
from benchmark.traffic_kinds import train_steps

CELL = "lfm2-train-s4096"
NEW_READERS = ("moe_train_ms_per_step", "moe_train_roofline",
               "moe_train_load_max_pct", "train_mfu_pct")
GENERIC = ("step_device_ms.train", "host_gap_ms.train",
           "device_idle_pct.train", "idle_unattributed_pct.train",
           "exec_marshal_ms.train", "exec_dispatch_ms.train",
           "exec_fetch_wait_ms.train", "exec_values_placed_pct.train")
NAMED_FLASH = ("flash_fwd_ms_per_step", "flash_bwd_dq_ms_per_step",
               "flash_bwd_dkv_ms_per_step")
# float32 and bfloat16 at toy widths: what test_lfm2 reads, with room
TOY_LIMITS = {"loss_gap_step1": 5e-3, "loss_gap_step2": 5e-3,
              "loss_gap_step3": 5e-3, "grad_norm_gap_median_leaf": 5e-3}


def test_every_file_the_cell_names_is_found():
    cell = cells.Cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "lfm2"
    assert cell.traffic_name == "lm-s4096-b2" and cell.kind is train_steps
    assert cell.family.__name__.endswith("lfm2") and cell.reference is ref
    assert set(cell.check_limits) >= {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap_worst_leaf", "update_norm_gap_worst_leaf",
        "update_norm_gap_median_leaf"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS + GENERIC + NAMED_FLASH) == names
    for m in cell.per_layer:
        assert callable(cell.module("layer_metrics", m["name"]).read)
    assert {m["name"] for m in cell.end_to_end} == {"train_tok_per_s",
                                                    "setup_s"}
    assert (cell.traffic["seq_len"], cell.traffic["batch"],
            cell.traffic["tokens_per_step"]) == (4096, 2, 8192)


@pytest.mark.parametrize("name", NEW_READERS)
def test_manifest_lists_the_new_readers_for_this_cell_alone(name):
    (entry,) = [m for m in cells.manifest()["per_layer"]
                if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_tok_per_s"
    assert sorted(entry) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]


def test_readers_that_would_misread_the_cell_do_not_list_it():
    """``flash_train_roofline`` counts every layer as an attention layer
    of ``hidden_size``; ``flash_train_ms_per_step`` takes every
    ``tpu_custom_call`` of a step, and here the grouped products are such
    calls too; ``exec_values_reused_pct.train`` is pinned to its three
    cells by a test of the benchmark's own."""
    listed = {m["name"]: m["workloads"]
              for m in cells.manifest()["per_layer"]}
    for name in ("flash_train_roofline", "flash_train_ms_per_step",
                 "exec_values_reused_pct.train"):
        assert CELL not in listed[name], name
    for name in GENERIC + NAMED_FLASH:
        assert listed[name][-1] == CELL, name


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if r["name"] == "LFM2-8B-A1B"][0]


def test_the_file_holds_every_number_of_the_catalogs_config():
    row, cfg = _catalog_row(), cells.Cell(CELL).config
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key


def test_the_file_holds_the_published_widths_and_names_its_cuts():
    cfg = cells.Cell(CELL).config
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["num_experts_per_tok"],
            cfg["conv_L_cache"], cfg["rope_theta"], cfg["norm_eps"]) == (
                2048, 7168, 1792, 32, 8, 4, 3, 1000000, 1e-5)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert [cfg[k] for k in cfg["reduced"]] == [5, 8, 16384]
    assert cfg["published"] == {"num_hidden_layers": 24, "num_experts": 32,
                                "vocab_size": 65536}
    assert "4 chips share each layer" in cfg["deployment"]
    for key in ("tie_embedding", "rotary", "expert_bias", "dtype",
                "weights"):
        assert key in cfg["assumed"]
    assert cfg["control_precision"] == {"train": "fp8"}
    z = ref.sizes(cfg)
    assert z["kinds"] == ("conv", "full_attention", "conv", "conv", "conv")
    assert (z["dense"], z["experts"], z["held"], z["topk"], z["d"]) == (
        1, 32, 8, 4, 64)
    shapes = ref.shapes(cfg)
    assert shapes["l1/moe/wg"][0] == (2048, 32)
    assert shapes["l1/moe/w1"][0] == (8, 2048, 1792)
    trained = sum(int(_prod(s)) for k, (s, _k) in shapes.items()
                  if not ref.is_buffer(k))
    # ISSUE 44's table: 507.8 M (and the norms' gains)
    assert trained == 507820160


def _prod(shape):
    out = 1
    for n in shape:
        out *= n
    return out


def test_rehearsal_trains_and_matches_the_reference(monkeypatch):
    _with_limits(monkeypatch, TOY_LIMITS)
    got = train_steps.run(_context(CELL, 11, 1.0)[1])
    assert got["attempted"] > 0 and got["failed"] == 0
    assert checks.correct(got["checks"]), got["checks"]["rows"]
    from paddle_tpu.observability import trace

    steps = [s for s in trace.get_spans() if s["name"] == "train_step"]
    assert len(steps) >= got["attempted"] + train_steps.CHECK_STEPS
    # toy: 2 of 8 experts held, 64 tokens, top 2, four expert layers
    assert all(0 <= s["args"]["assignments"] <= 4 * 64 * 2 for s in steps)
    assert all(s["args"]["experts_hit"] <= 8 for s in steps)


def test_the_command_rehearses():
    """``run.py --workload lfm2-train-s4096 --rehearse`` end to end, in a
    process of its own as the driver starts it."""
    out = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", CELL, "--rehearse", "--seconds", "1", "--seed",
         str(2 ** 31 + 7)], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "completed" and line["failed"] == 0
    assert line["metric_names"] == ["setup_s", "train_tok_per_s"]


def test_train_control_fp8_reads_far_above_the_program():
    cell, ctx = _context(CELL, seed=5)
    step = cell.family.build_train(ctx.config, ctx.traffic, ctx.place, True)
    got, batches, _t = train_steps.first_steps(
        step, cell.family, cell.reference, ctx.config, ctx.traffic, 5)
    step.close()
    sound = train_steps.reference_readings(ref, ctx.config, 5, batches)
    low = train_steps.reference_readings(ref, ctx.config, 5, batches, "fp8")
    program = checks.summary_values(checks.train(got, sound, {}))
    control = checks.summary_values(checks.train(
        {"losses": low[0], "gnorm": low[1], "dnorm": low[2]}, sound, {}))
    name = "grad_norm_gap_median_leaf"
    assert control[name] > 3 * program[name], (program, control)
    # the router's bias is in neither side's norms
    assert not [k for k in sound[1] if ref.is_buffer(k)]
    assert set(sound[1]) == set(got["gnorm"])


def test_reference_counts_what_the_held_experts_receive():
    cell, ctx = _context(CELL, seed=5)
    params = ref.init_params(5, ctx.config)
    batch = train_steps.batch_for(ctx.traffic, ctx.config, 5, 0)
    counts = ref.first_counts(ctx.config, params, batch)
    tokens = ctx.traffic["batch"] * ctx.traffic["seq_len"]
    assert counts.shape == (4, 2)
    assert 0 < int(counts.sum()) < 4 * tokens * 2


# -- what the step needs, and the readers --------------------------------------

def test_moe_train_needs_hand_worked():
    cell = cells.Cell(CELL)
    cfg = cell.family.toy(cell.config)
    # toy: hidden 32, expert width 16, 2 of 8 experts held in each of four
    # expert layers, top 2; 10 assignments
    flops, moved = moe_train.needs(cfg, 10)
    assert flops == 3 * 3 * 2 * 10 * 32 * 16
    stacks = 4 * 2 * 3 * 32 * 16
    assert moved == 2 * (3 * stacks + stacks + 3 * 2 * 10 * 32)
    assert moe_train.even_share(cfg, {"batch": 2, "seq_len": 6}) == 3.0


def test_model_flops_is_issue_44s_reckoning():
    cell = cells.Cell(CELL)
    flops = moe_train.model_flops(cell.config, cell.traffic)
    # ~200 M matmul weights a token x 6 x 8192 tokens + causal attention:
    # ISSUE 44 reckons ~10.2 TFLOP a step
    assert 9.9e12 < flops < 10.6e12
    assert moe_train.expert_layers(cell.config) == 4
    assert moe_train.even_share(cell.config, cell.traffic) == 1024.0


def _event(name, start, dur):
    return xplane.Event(name, start, dur)


MOE = ('%ragged-dot-none.35 = f32[32768,1792]{1,0:T(8,128)} custom-call('
       'bf16[32768,2048]{1,0} %fusion.17, bf16[8,2048,1792]{2,1,0} '
       '%convert.3), custom_call_target="tpu_custom_call", '
       'frontend_attributes={mosaic_fusion_entry_point="true"}')
META = ('%ragged-dot-metadata.7 = (s32[9], s32[71], s32[71], s32[1]) '
        'custom-call(), custom_call_target="tpu_custom_call"')
FLASH = ('%flash_bwd_dkv.3 = bf16[2,32,4096,64] custom-call(), '
         'custom_call_target="tpu_custom_call", metadata={op_name='
         '"jit(fn)/flash_bwd_dkv/pallas_call"}')
OTHER = '%fusion.7 = bf16[8192,2048] fusion(), metadata={op_name="jit(fn)"}'


def _evidence(ops, modules, spans=()):
    plane = xplane.DevicePlane("/device:TPU:0", ops, modules)
    cell = cells.Cell(CELL)
    ev = types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, spans=list(spans),
        counters={}, requests=[], window=(0.0, 100.0), chips=1,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        facts={"tracer": types.SimpleNamespace(window=(0.0, 100.0))},
        ctx=types.SimpleNamespace(cell=cell),
        planes=lambda: [plane] if ops else [])
    ev.steps = lambda plane=None: modules
    ev.step_ops = lambda pattern=None, plane=None: (
        xplane.matching(ops, pattern) if pattern else ops)

    def seconds(pattern):
        found = ev.step_ops(pattern)
        return sum(e.dur for e in found) / len(modules) if found else None

    ev.kernel_seconds_per_step = seconds
    return cell, ev


def _toy_trace():
    """Two train steps of 120 ms: 36 grouped products of 1 ms and 4 of
    their metadata calls, a flash kernel, and fusions for the rest."""
    ops, modules = [], []
    for at in (1.0, 2.0):
        modules.append(_event("jit_fn(step)", at, 0.12))
        ops += [_event(MOE, at + 0.002 * i, 0.001) for i in range(36)]
        ops += [_event(META, at + 0.08 + 0.001 * i, 0.0001)
                for i in range(4)]
        ops.append(_event(FLASH, at + 0.09, 0.005))
        ops.append(_event(OTHER, at + 0.1, 0.02))
    return ops, modules


def _step_span(at, assignments, fullest):
    return {"name": "train_step", "start": at, "end": at + 0.13, "tid": 1,
            "args": {"assignments": assignments, "experts_hit": 32,
                     "expert_load_max": fullest}}


def test_new_readers_on_a_toy_trace():
    ops, modules = _toy_trace()
    cell, ev = _evidence(ops, modules, spans=[
        _step_span(1.0, 32768, 1090), _step_span(2.0, 32700, 1110)])
    read = lambda name: cell.module("layer_metrics", name).read(ev)  # noqa: E731
    assert read("moe_train_ms_per_step") == pytest.approx(36.4)
    flops, moved = moe_train.needs(cell.config, 32734)
    assert read("moe_train_roofline") == pytest.approx(
        100 * (flops / 197e12) / 0.0364)
    assert 0 < read("moe_train_roofline") <= 100
    assert read("moe_train_load_max_pct") == pytest.approx(
        100 * 1100 / 1024.0)
    busy = cell.module("layer_metrics", "step_device_ms.train").read(ev)
    assert read("train_mfu_pct") == pytest.approx(
        100 * moe_train.model_flops(cell.config, cell.traffic)
        / (1e-3 * busy * 197e12))
    assert 0 < read("train_mfu_pct") <= 100


def test_new_readers_find_nothing_on_a_program_without_the_span():
    ops, modules = _toy_trace()
    cell, ev = _evidence(ops, modules)        # an older commit: no span
    read = lambda name: cell.module("layer_metrics", name).read(ev)  # noqa: E731
    assert read("moe_train_roofline") is None
    assert read("moe_train_load_max_pct") is None
    cell, ev = _evidence([], [])              # and no trace
    for name in NEW_READERS:
        assert read(name) is None


# -- the committed limits against the chip's own readings ---------------------

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
    both = [s for s in sides.values() if s == {"program", "control"}]
    # a run of the benchmark itself adds a program row of its own seed
    assert len(both) >= 2 and all("program" in s for s in sides.values())
