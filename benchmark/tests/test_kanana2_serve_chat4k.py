"""The cell ``kanana2-serve-chat4k`` at a size a test run can hold (CPU,
toy widths, Pallas interpreter), as ``test_control_and_broken_path`` and
``test_fsdp4_rehearsal`` hold the older cells:

- its rehearsal runs the traffic kind's own ``run`` through the whole
  stack and compares every served token;
- the control fails: the reference with every matmul in fp8 puts other
  tokens first, far above what the program reads;
- a run whose timed path is broken underneath (one expert's contribution
  zeroed in the served weights, which is what a zeroed gate does) comes
  out not correct;
- the new readers read a recorded toy trace, and return nothing (do not
  raise) where the program has no such span, counter or kernel;
- the committed limits judge the chip's own recorded readings
  (``data/chip_readings.kanana2-serve-chat4k.jsonl``): every sound run
  correct, every fp8 control not.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmark.harness import cells, checks, xplane
from benchmark.kernels import latent_decode
from benchmark.references import deepseek as ref
from benchmark.tests.test_control_and_broken_path import (_context,
                                                          _with_limits)
from benchmark.traffic_kinds import serve_closed

CELL = "kanana2-serve-chat4k"


def test_rehearsal_serves_and_compares_every_token(monkeypatch):
    _with_limits(monkeypatch, {"served_logit_gap_mean": 1e-3,
                               "served_logit_gap_widest": 0.05})
    got = serve_closed.run(_context(CELL, 11, 2.0)[1])
    assert got["attempted"] > 0 and got["failed"] == 0
    assert got["checks"]["detail"]["tokens_compared"] > 50
    assert checks.correct(got["checks"]), got["checks"]["rows"]
    assert got["counters"]["moe_assignments"] > 0


def test_zeroed_expert_gate_is_not_correct(monkeypatch):
    """Every expert layer's most used expert contributes nothing (its
    down projection zeroed in the served scope, as a gate of 0 would)."""
    from benchmark.families import deepseek as family

    _with_limits(monkeypatch, {"served_logit_gap_mean": 1e-3,
                               "served_logit_gap_widest": 0.05})
    honest = family.ServeStack.set_params

    def broken(self, params):
        honest(self, params)
        for var in set(self._vars.values()):
            if var.endswith("_experts_w2"):
                w2 = self.scope.get(var)
                self.scope.set(var, w2.at[:4].set(0))

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
    c = ref.init_params(18, ctx.config)
    assert c["head"] is not a["head"]
    assert not np.array_equal(np.asarray(c["norm"], "float32"),
                              np.asarray(a["norm"], "float32"))


# -- the new readers ------------------------------------------------------------

def _event(name, start, dur):
    return xplane.Event(name, start, dur)


MLA = ('%mla_decode_paged.3 = f32[64,32,512] custom-call(), '
       'custom_call_target="tpu_custom_call", metadata={op_name='
       '"jit(fn)/mla_absorb/mla_decode_paged/pallas_call"}')
MOE = ('%ragged-dot-none.2 = f32[384,768]{1,0:T(8,128)S(1)} custom-call('
       'bf16[384,2048]{1,0} %fusion.17, bf16[128,2048,768]{2,1,0} '
       '%const_map__ds_4_moe_experts_w1__.1), custom_call_target='
       '"tpu_custom_call", frontend_attributes={mosaic_fusion_entry_point='
       '"true",ragged_dot_tiling="128,512,256"}')
OTHER = '%fusion.7 = bf16[64,2048] fusion(), metadata={op_name="jit(fn)/mul"}'


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
    """Two T = 1 steps (each: kernel, expert op, something else) around a
    longer prefill window that runs neither kernel."""
    ops, modules = [_event(OTHER, 0.0, 0.001)], []
    for at in (1.0, 3.0):
        modules.append(_event("jit_fn(step)", at, 0.1))
        ops += [_event(MLA, at + 0.01, 0.004), _event(MOE, at + 0.02, 0.010),
                _event(OTHER, at + 0.04, 0.020)]
    modules.append(_event("jit_fn(window)", 2.0, 0.5))
    ops += [_event(MOE, 2.1, 0.3), _event(OTHER, 5.0, 0.001)]
    return ops, modules


def test_device_readers_take_the_steps_not_the_windows():
    ops, modules = _toy_trace()
    step = {"name": "decode_paged_step", "start": 1.0, "end": 1.2, "tid": 1,
            "args": {"experts_hit": 600, "assignments": 1920}}
    record = types.SimpleNamespace(prompt=[0] * 1000, times=[10.0, 60.0],
                                   sent=5.0, ended=None)
    cell, ev = _evidence(ops, modules, spans=[step], requests=[record])
    read = lambda name: cell.module("layer_metrics", name).read(ev)  # noqa: E731
    assert read("mla_decode_ms_per_step") == pytest.approx(4.0)
    assert read("moe_ms_per_step") == pytest.approx(10.0)
    assert read("decode_step_device_ms") == pytest.approx(100.0)
    flops, moved = latent_decode.moe_needs(cell.config, 600, 1920)
    assert moved == 2 * 600 * 3 * 2048 * 768
    assert read("moe_decode_roofline") == pytest.approx(
        100 * (moved / 819e9) / 0.010)
    flops, moved = latent_decode.mla_needs(cell.config, [1001])
    assert moved == 7 * 1001 * 576 * 2
    assert flops == 7 * 1001 * 2 * 32 * (576 + 512)
    assert read("mla_decode_roofline") == pytest.approx(
        100 * (moved / 819e9) / 0.004)


def test_span_and_counter_readers():
    fetch = {"name": "executor_fetch", "start": 1.1, "end": 1.18, "tid": 1,
             "args": {}}
    step = {"name": "decode_paged_step", "start": 1.0, "end": 1.2, "tid": 1,
            "args": {}}
    stray = dict(fetch, start=2.0, end=2.5)     # a window's fetch
    cell, ev = _evidence([], [], spans=[step, fetch, stray], counters={
        "decode_steps": 10, "moe_experts_hit": 10 * 6 * 122})
    read = lambda name: cell.module("layer_metrics", name).read(ev)  # noqa: E731
    assert read("logits_fetch_ms_p50") == pytest.approx(80.0)
    assert read("moe_experts_hit_pct") == pytest.approx(100 * 122 / 128)


def test_readers_find_nothing_in_a_program_without_the_model():
    """A commit without the spans, the counters and the kernel: every new
    reader returns None and none raises."""
    ops = [_event(OTHER, 0.0, 0.01)]
    cell, ev = _evidence(ops, [_event("jit_fn", 0.0, 0.02)], counters={
        "decode_steps": 10})
    for name in ("moe_ms_per_step", "moe_decode_roofline",
                 "mla_decode_ms_per_step", "mla_decode_roofline",
                 "decode_step_device_ms", "moe_experts_hit_pct",
                 "logits_fetch_ms_p50"):
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


def test_enough_seeds_were_read():
    readings = _chip_readings()
    assert len({s for w, s, _ in readings if w == "program"}) >= 5
    assert len({s for w, s, _ in readings if w == "control"}) >= 3
