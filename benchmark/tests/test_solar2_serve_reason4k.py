"""The cell ``solar2-serve-reason4k`` at a size a test run can hold (CPU,
toy widths, Pallas interpreter), as ``test_kanana2_serve_chat4k`` holds
its cell:

- its rehearsal runs the traffic kind's own ``run`` through the whole
  stack (windows handing their state to steps) and compares every served
  token; every file the cell names is found;
- the control fails: the reference with every matmul in fp8 puts other
  tokens first, far above what the program reads;
- a run whose timed path is broken underneath (the recurrent state never
  decays: ``A_log`` forced very negative in the served weights) comes out
  not correct;
- the new readers read a recorded toy trace (the event names the chip's
  trace of PR 31 gave), and return nothing (do not raise) where the
  program has no such span, counter or kernel;
- the committed limits judge the chip's own recorded readings
  (``data/chip_readings.solar2-serve-reason4k.jsonl``): every sound run
  correct, every fp8 control not.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmark.harness import cells, checks, xplane
from benchmark.kernels import hybrid_decode
from benchmark.references import solar_open2 as ref
from benchmark.tests.test_control_and_broken_path import (_context,
                                                          _with_limits)
from benchmark.traffic_kinds import serve_closed

CELL = "solar2-serve-reason4k"
NEW_READERS = ("kda_decode_ms_per_step", "kda_decode_roofline",
               "gqa_decode_ms_per_step", "gqa_decode_roofline",
               "moe_held_ms_per_step", "moe_held_roofline",
               "kda_step_device_ms")
TOY_LIMITS = {"served_logit_gap_mean": 1e-3, "served_logit_gap_widest": 0.05}


def test_every_file_the_cell_names_is_found():
    cell = cells.Cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "solar_open2"
    assert cell.kind is serve_closed
    assert cell.family.__name__.endswith("solar_open2")
    assert cell.reference is ref
    assert set(cell.check_limits) == set(TOY_LIMITS)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= names and "moe_experts_hit_pct" in names
    for m in cell.per_layer:
        assert callable(cell.module("layer_metrics", m["name"]).read)
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tok_per_s", "tpot_p90_ms", "setup_s"}


def test_the_file_holds_the_published_widths_and_names_its_cuts():
    cfg = cells.Cell(CELL).config
    kda = cfg["linear_attn_config"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"]) == (4096, 64, 128, 8, 1280, 8)
    assert (kda["num_heads"], kda["head_dim"],
            kda["short_conv_kernel_size"]) == (64, 128, 4)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert [cfg[k] for k in cfg["reduced"]] == [8, 20, 24576]
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 320,
                                "vocab_size": 196608}
    assert "16 chips share each layer" in cfg["deployment"]
    assert ref.sizes(cfg)["experts"] == 320 and ref.sizes(cfg)["held"] == 20
    assert ref.sizes(cfg)["gqa"] == (0, 4)


def test_rehearsal_serves_and_compares_every_token(monkeypatch):
    _with_limits(monkeypatch, TOY_LIMITS)
    got = serve_closed.run(_context(CELL, 11, 2.0)[1])
    assert got["attempted"] > 0 and got["failed"] == 0
    assert got["checks"]["detail"]["tokens_compared"] > 50
    assert checks.correct(got["checks"]), got["checks"]["rows"]
    assert got["counters"]["moe_assignments"] > 0
    assert got["counters"]["kda_state_resets"] > 0
    assert got["counters"]["kda_state_bytes"] > 0
    steps = [s for s in got["spans"] if s["name"] == "decode_paged_step"]
    assert steps and all("state_slots_live" in s["args"] for s in steps)


def test_a_state_that_never_decays_is_not_correct(monkeypatch):
    """Every delta-rule layer's ``A_log`` at -20 in the served scope: the
    decay is 1 - 2e-9, the state forgets nothing."""
    from benchmark.families import solar_open2 as family

    _with_limits(monkeypatch, TOY_LIMITS)
    honest = family.ServeStack.set_params

    def broken(self, params):
        honest(self, params)
        for var in set(self._vars.values()):
            if var.endswith("_kda_a_log"):
                self.scope.set(var, np.full_like(
                    np.asarray(self.scope.get(var)), -20.0))

    monkeypatch.setattr(family.ServeStack, "set_params", broken)
    got = serve_closed.run(_context(CELL, 11, 2.0)[1])
    assert got["attempted"] > 0 and not checks.correct(got["checks"])


def test_control_fp8_puts_other_tokens_first():
    cell, ctx = _context(CELL, 3)
    cfg = ctx.config
    params = ref.init_params(3, cfg)
    rng = np.random.default_rng(3)

    class Req(object):
        """A prompt of 20 and the reference's own greedy 12 tokens."""

        def __init__(self):
            row = [int(t) for t in rng.integers(0, cfg["vocab_size"], 20)]
            for _ in range(12):
                lg = np.asarray(ref.logits(cfg, params, np.asarray([row])))
                row.append(int(lg[0, -1].argmax()))
            self.prompt, self.tokens = row[:20], row[20:]

    sample = [Req() for _ in range(4)]
    sound = serve_closed.served_gaps(ref, cfg, params, sample)
    low = serve_closed.served_gaps(ref, cfg, params, sample, "fp8")
    assert len(sound) == len(low) == 4 * 12
    assert max(sound) < 1e-5      # greedy by the reference itself
    assert sum(low) / len(low) > 10 * max(sum(sound) / len(sound), 1e-7)


def test_weights_asked_twice_while_alive_are_one_set():
    cell, ctx = _context(CELL, 3)
    a = ref.init_params(17, ctx.config)
    b = ref.init_params(17, ctx.config)
    assert all(a[k] is b[k] for k in a)
    assert ref.init_params(18, ctx.config)["head"] is not a["head"]


def test_seeded_gates_decay_as_the_configuration_file_says():
    cell, ctx = _context(CELL, 3)
    p = ref.init_params(5, ctx.config)
    a_log = np.asarray(p["l1/kda/a_log"])
    assert (np.exp(a_log) >= 1).all() and (np.exp(a_log) <= 16).all()
    dt = np.log1p(np.exp(np.asarray(p["l1/kda/dt_bias"], np.float64)))
    assert dt.min() >= 0.99e-3 and dt.max() <= 1.01e-1


# -- the new readers ------------------------------------------------------------

def _event(name, start, dur):
    return xplane.Event(name, start, dur)


KDA = ('%kda_decode.5 = (f32[64,64,128]{2,1,0:T(8,128)}, f32[65,64,128,128]'
       '{3,2,1,0:T(8,128)}) custom-call(), custom_call_target='
       '"tpu_custom_call", metadata={op_name="jit(fn)/kda_step/kda_decode/'
       'pallas_call"}')
GQA = ('%flash_decode_paged_gqa.1 = bf16[64,128,128]{2,1,0} custom-call(), '
       'custom_call_target="tpu_custom_call", metadata={op_name='
       '"jit(fn)/flash_decode_paged_gqa/pallas_call"}')
MHA = ('%flash_decode_paged.1 = f32[64,1,768] custom-call(), '
       'custom_call_target="tpu_custom_call"')
MOE = ('%ragged-dot-none.2 = f32[512,1280]{1,0:T(8,128)S(1)} custom-call('
       'bf16[512,4096]{1,0} %fusion.17, bf16[20,4096,1280]{2,1,0} '
       '%const_map__so2_4_moe_experts_w1__.1), custom_call_target='
       '"tpu_custom_call", frontend_attributes={mosaic_fusion_entry_point='
       '"true",ragged_dot_tiling="128,512,256"}')
OTHER = '%fusion.7 = bf16[64,4096] fusion(), metadata={op_name="jit(fn)/mul"}'


def _evidence(ops, modules, spans=(), counters=None, requests=()):
    plane = xplane.DevicePlane("/device:TPU:0", ops, modules)
    cell = cells.Cell(CELL)
    ev = types.SimpleNamespace(
        config=cell.config, spans=list(spans), counters=counters or {},
        requests=list(requests), window=(0.0, 100.0),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        facts={"tracer": types.SimpleNamespace(window=(0.0, 100.0))},
        planes=lambda: [plane] if ops else [])
    return cell, ev


def _toy_trace():
    """Two T = 1 steps (each: both kernels, an expert product, something
    else) around a longer prefill window that runs neither kernel."""
    ops, modules = [_event(OTHER, 0.0, 0.001)], []
    for at in (1.0, 3.0):
        modules.append(_event("jit_fn(step)", at, 0.1))
        ops += [_event(KDA, at + 0.01, 0.006), _event(GQA, at + 0.02, 0.003),
                _event(MOE, at + 0.03, 0.008), _event(OTHER, at + 0.05, 0.02)]
    modules.append(_event("jit_fn(window)", 2.0, 0.5))
    ops += [_event(MOE, 2.1, 0.3), _event(OTHER, 5.0, 0.001)]
    return ops, modules


def test_device_readers_take_the_steps_not_the_windows():
    ops, modules = _toy_trace()
    step = {"name": "decode_paged_step", "start": 1.0, "end": 1.2, "tid": 1,
            "args": {"experts_hit": 120, "assignments": 250,
                     "state_slots_live": 60}}
    record = types.SimpleNamespace(prompt=[0] * 3000, times=[10.0, 60.0],
                                   sent=5.0, ended=None)
    cell, ev = _evidence(ops, modules, spans=[step], requests=[record])
    read = lambda name: cell.module("layer_metrics", name).read(ev)  # noqa: E731
    assert read("kda_decode_ms_per_step") == pytest.approx(6.0)
    assert read("gqa_decode_ms_per_step") == pytest.approx(3.0)
    assert read("moe_held_ms_per_step") == pytest.approx(8.0)
    assert read("kda_step_device_ms") == pytest.approx(100.0)
    flops, moved = hybrid_decode.kda_needs(cell.config, 60)
    assert moved == 6 * 60 * 64 * 128 * 128 * 4 * 2
    assert flops == 6 * 60 * 64 * 128 * 128 * 7
    assert read("kda_decode_roofline") == pytest.approx(
        100 * (moved / 819e9) / 0.006)
    flops, moved = hybrid_decode.gqa_needs(cell.config, [3001])
    assert moved == 2 * 3001 * 2 * 1024 * 2
    assert flops == 2 * 3001 * 64 * 4 * 128
    assert read("gqa_decode_roofline") == pytest.approx(
        100 * (moved / 819e9) / 0.003)
    flops, moved = hybrid_decode.moe_held_needs(cell.config, 120, 250)
    assert moved == 2 * 120 * 3 * 4096 * 1280
    assert read("moe_held_roofline") == pytest.approx(
        100 * (moved / 819e9) / 0.008)
    for name in ("kda_decode_roofline", "gqa_decode_roofline",
                 "moe_held_roofline"):
        assert 0 < read(name) <= 100


def test_the_older_cells_kernels_are_not_this_cells():
    """A GPT step (the ``flash_decode_paged`` kernel) is no step of this
    cell, and its kernel is not the grouped one."""
    ops = [_event(MHA, 0.01, 0.004), _event(OTHER, 0.02, 0.001)]
    cell, ev = _evidence(ops, [_event("jit_fn(step)", 0.0, 0.1)])
    for name in NEW_READERS:
        assert cell.module("layer_metrics", name).read(ev) is None


def test_experts_hit_share_is_of_the_experts_held():
    cell, ev = _evidence([], [], counters={
        "decode_steps": 10, "moe_experts_hit": 10 * 8 * 16})
    read = cell.module("layer_metrics", "moe_experts_hit_pct").read
    assert read(ev) == pytest.approx(100 * 16 / 20)


def test_readers_find_nothing_in_a_program_without_the_model():
    """A commit without the spans, the counters and the kernels: every
    new reader returns None and none raises."""
    ops = [_event(OTHER, 0.0, 0.01)]
    cell, ev = _evidence(ops, [_event("jit_fn", 0.0, 0.02)], counters={
        "decode_steps": 10})
    for name in NEW_READERS:
        assert cell.module("layer_metrics", name).read(ev) is None
    cell, ev = _evidence([], [])
    ev.facts = {}
    for name in NEW_READERS:
        assert cell.module("layer_metrics", name).read(ev) is None


# -- the committed limits against the chip's readings ----------------------------

def _chip_readings():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "chip_readings.%s.jsonl" % CELL)
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    return [(d["who"], d["seed"], d["numbers"]) for d in lines]


@pytest.mark.parametrize("who,seed,numbers", _chip_readings(),
                         ids=lambda v: str(v) if not isinstance(v, dict)
                         else "")
def test_committed_limits_pass_the_program_and_fail_the_control(
        who, seed, numbers):
    limits = cells.Cell(CELL).check_limits
    assert set(limits) == {"served_logit_gap_mean",
                           "served_logit_gap_widest"}
    judged = checks.compare(numbers, limits, {})
    assert checks.correct(judged) == (who == "program"), judged["rows"]
