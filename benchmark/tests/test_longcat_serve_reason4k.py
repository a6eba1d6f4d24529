"""The cell ``longcat-serve-reason4k`` at a size a test run can hold (CPU,
toy widths, Pallas interpreter), as ``test_solar2_serve_reason4k`` holds
its cell:

- every file the cell names is found, and the configuration file holds
  the published widths, its three cuts and the deployment in words;
- its rehearsal runs the traffic kind's own ``run`` through the whole
  stack (windows landing in four latent pools, then steps) and compares
  every served token;
- the control fails: the reference with every matmul in fp8 puts other
  tokens first, far above what the program reads;
- a run whose timed path is broken underneath (the second attention of
  every double layer reads a pool without latents: its ``kv_norm`` zeroed
  in the served weights) comes out not correct;
- the new readers, and the latent model's readers of the step program's
  times, read a recorded toy trace (the event names the chip's
  traces of PR 27 and PR 31 gave the same kernels), and return nothing
  (do not raise) where the program has no such span, counter or kernel;
- the committed limits judge the chip's own recorded readings
  (``data/chip_readings.longcat-serve-reason4k.jsonl``): every sound run
  correct, every fp8 control not.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmark.harness import cells, checks, xplane
from benchmark.kernels import shortcut_decode
from benchmark.references import longcat_flash as ref
from benchmark.tests.test_control_and_broken_path import (_context,
                                                          _with_limits)
from benchmark.traffic_kinds import serve_closed

CELL = "longcat-serve-reason4k"
# the step program and its two named kernels are the latent model's: the
# readers of their times are the ones the benchmark had
SHARED_READERS = ("decode_step_device_ms", "mla_decode_ms_per_step",
                  "moe_ms_per_step")
# what needs this model's counts or keys
NEW_READERS = ("scmoe_held_roofline", "mla2_decode_roofline",
               "scmoe_experts_hit_pct", "moe_zero_share_pct")
DEVICE_READERS = SHARED_READERS + NEW_READERS[:2]
# the toy serves bfloat16 weights whose softmax scores over 12 outputs lie
# close together: a near-tie that rounding flips moves one token's logit by
# up to ~0.1 (seed 11: 0.081), the mean by nothing
TOY_LIMITS = {"served_logit_gap_mean": 1e-3, "served_logit_gap_widest": 0.25}


def test_every_file_the_cell_names_is_found():
    cell = cells.Cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "longcat_flash"
    assert cell.traffic_name == "reason-closed-64-p4k"
    assert cell.kind is serve_closed
    assert cell.family.__name__.endswith("longcat_flash")
    assert cell.reference is ref
    assert set(cell.check_limits) == set(TOY_LIMITS)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS + SHARED_READERS) <= names
    # readers that would halve this model's attentions, or read keys its
    # source does not have, are not asked of it
    assert not names & {"mla_decode_roofline", "moe_decode_roofline",
                        "moe_experts_hit_pct", "moe_held_roofline"}
    for m in cell.per_layer:
        assert callable(cell.module("layer_metrics", m["name"]).read)
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tok_per_s", "tpot_p90_ms", "setup_s"}


def test_its_metrics_list_the_cell_and_the_solar_cell_shares_its_mix():
    """The lists are not pinned to what they hold today: a later cell that
    a reader works for is appended to them."""
    manifest = cells.manifest()
    listed = {m["name"]: m["workloads"] for m in manifest["per_layer"]
              if m["name"] in NEW_READERS + SHARED_READERS}
    assert set(listed) == set(NEW_READERS + SHARED_READERS)
    for name, workloads in listed.items():
        assert CELL in workloads, name
        if name in SHARED_READERS:
            assert "kanana2-serve-chat4k" in workloads, name
    same_mix = [w["name"] for w in manifest["workloads"]
                if w["traffic"] == "reason-closed-64-p4k"]
    assert same_mix == ["solar2-serve-reason4k", CELL]


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if r["name"] == "LongCat-Flash-Omni"][0]


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
    assert (cfg["hidden_size"], cfg["ffn_hidden_size"],
            cfg["expert_ffn_hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["moe_topk"],
            cfg["zero_expert_num"], cfg["routed_scaling_factor"],
            cfg["rope_theta"]) == (6144, 12288, 2048, 64, 1536, 512, 128, 64,
                                   128, 12, 256, 6, 10000000)
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert [cfg[k] for k in cfg["reduced"]] == [4, 16, 16384]
    assert cfg["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                "vocab_size": 131072}
    assert "32 chips share each layer" in cfg["deployment"]
    assert "1/32" in cfg["deployment"]
    for key in ("mla_scale_q_lora", "mla_scale_kv_lora", "router",
                "torch_dtype", "weights"):
        assert key in cfg["assumed"]
    assert any("encoders" in d for d in cfg["departures"])
    z = ref.sizes(cfg)
    assert (z["experts"], z["held"], z["zeros"], z["topk"]) == (512, 16, 256,
                                                                12)
    assert z["qscale"] == 2.0 and round(z["kvscale"], 3) == 3.464
    shapes = ref.shapes(cfg)
    assert shapes["l0/moe/wg"][0] == (6144, 768)
    assert sum(int(np.prod(s)) for s, _k in shapes.values()) == 5172749312


def test_rehearsal_serves_and_compares_every_token(monkeypatch):
    _with_limits(monkeypatch, TOY_LIMITS)
    got = serve_closed.run(_context(CELL, 11, 2.0)[1])
    assert got["attempted"] > 0 and got["failed"] == 0
    assert got["checks"]["detail"]["tokens_compared"] > 50
    assert checks.correct(got["checks"]), got["checks"]["rows"]
    assert got["counters"]["moe_zero_assignments"] > 0
    assert got["counters"]["moe_assignments"] > 0
    steps = [s for s in got["spans"] if s["name"] == "decode_paged_step"]
    assert steps and all("zero_assignments" in s["args"] for s in steps)
    # 4 slots, top 3, 2 double layers: held + elsewhere + identity
    assert all(s["args"]["assignments"] + s["args"]["zero_assignments"]
               <= 4 * 3 * 2 for s in steps)


def test_a_second_attention_over_an_empty_latent_is_not_correct(
        monkeypatch):
    """``kv_norm`` of attention 1 of every double layer zeroed in the
    served scope: its pool (entry ``2l + 1``) keeps rope keys and no
    latent, so every head's value is zero."""
    from benchmark.families import longcat_flash as family

    _with_limits(monkeypatch, TOY_LIMITS)
    honest = family.ServeStack.set_params

    def broken(self, params):
        honest(self, params)
        for var in set(self._vars.values()):
            if var.endswith("_att1_kv_norm"):
                self.scope.set(var, np.zeros_like(
                    np.asarray(self.scope.get(var))))

    monkeypatch.setattr(family.ServeStack, "set_params", broken)
    got = serve_closed.run(_context(CELL, 11, 2.0)[1])
    assert got["attempted"] > 0 and not checks.correct(got["checks"])
    # by the mean, which a router's near-tie flipped by bfloat16 rounding
    # (the widest gap of a sound toy run) does not move
    mean = checks.summary_values(got["checks"])["served_logit_gap_mean"]
    assert mean > 10 * TOY_LIMITS["served_logit_gap_mean"]


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


def test_served_gaps_takes_each_row_at_its_own_length():
    """The caller pads every row to the longest with id 0: a row is cut
    at its last id that is not 0 and padded again, its gaps do not depend
    on what it was padded to, and the positions past its end and a row of
    padding alone read 0. A row that really ends in id 0 keeps that
    position's gap (the padding is the same zero)."""
    cell, ctx = _context(CELL, 3)
    cfg = ctx.config
    params = ref.init_params(5, cfg)
    ids = np.random.default_rng(5).integers(1, cfg["vocab_size"], (4, 8))
    ids[1, 5:] = 0                       # a row of 5, as the caller pads it
    ids[2, 7] = 0                        # a row of 8 whose last id is 0
    ids[3] = 0                           # the caller's empty row
    whole = ref.served_gaps(cfg, params, ids)
    assert whole.shape == (4, 8)
    assert not whole[3].any() and (whole[:3, :4] > 0).all()
    short = ref.served_gaps(cfg, params, ids[1:2, :5])
    np.testing.assert_allclose(short[0, :4], whole[1, :4], atol=1e-5)
    wide = np.zeros((1, 2 * ref.WIDTH_STEP), np.int64)
    wide[0, :8] = ids[2]
    np.testing.assert_allclose(ref.served_gaps(cfg, params, wide)[0, :7],
                               whole[2, :7], atol=1e-5)


def test_weights_asked_twice_while_alive_are_one_set():
    cell, ctx = _context(CELL, 3)
    a = ref.init_params(17, ctx.config)
    b = ref.init_params(17, ctx.config)
    assert all(a[k] is b[k] for k in a)
    assert ref.init_params(18, ctx.config)["head"] is not a["head"]


def test_seeded_router_bias_perturbs_the_choice_and_does_not_make_it():
    """The router alone at its published width, 64 tokens of unit rms: a
    bias of the scores' size leaves the choice to the token (the 12 picks
    of 64 tokens spread over hundreds of the 768 outputs, ~10 of the 16
    held experts hit, a third of the picks identity experts); N(0, 0.02)
    as it is would pick nearly one dozen outputs for every token."""
    import jax

    cfg = cells.Cell(CELL).config
    z = ref.sizes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    p = {"wg": ref._draw(keys[0], (6144, 768), "w"),
         "bias": ref._draw(keys[1], (768,), "b")}
    assert float(np.abs(np.asarray(p["bias"])).max()) < 4 * 0.02 / 768
    x = jax.random.normal(keys[2], (64, 6144))
    experts = np.asarray(ref.route(x, p, z)[0])
    spread = len(np.unique(experts))
    assert spread > 300
    assert 6 <= len(np.unique(experts[experts < 16])) <= 16
    assert 0.2 < (experts >= 512).mean() < 0.45
    swamped = dict(p, bias=p["bias"] * 768)
    same = np.asarray(ref.route(x, swamped, z)[0])
    assert len(np.unique(same)) < 0.4 * spread
    # most tokens share most of their dozen
    common = np.bincount(same.reshape(-1), minlength=768)
    assert np.sort(common)[-8:].min() > 48


# -- the new readers ------------------------------------------------------------

def _event(name, start, dur):
    return xplane.Event(name, start, dur)


MLA = ('%mla_decode_paged.3 = f32[64,64,512] custom-call(), '
       'custom_call_target="tpu_custom_call", metadata={op_name='
       '"jit(fn)/mla_absorb/mla_decode_paged/pallas_call"}')
MOE = ('%ragged-dot-none.2 = f32[768,2048]{1,0:T(8,128)S(1)} custom-call('
       'bf16[768,6144]{1,0} %fusion.17, bf16[16,6144,2048]{2,1,0} '
       '%const_map__lc_2_moe_experts_w1__.1), custom_call_target='
       '"tpu_custom_call", frontend_attributes={mosaic_fusion_entry_point='
       '"true",ragged_dot_tiling="128,512,256"}')
KDA = ('%kda_decode.5 = f32[64,64,128] custom-call(), custom_call_target='
       '"tpu_custom_call"')
OTHER = '%fusion.7 = bf16[64,6144] fusion(), metadata={op_name="jit(fn)/mul"}'


def _evidence(ops, modules, spans=(), counters=None):
    plane = xplane.DevicePlane("/device:TPU:0", ops, modules)
    cell = cells.Cell(CELL)
    ev = types.SimpleNamespace(
        config=cell.config, spans=list(spans), counters=counters or {},
        requests=[], window=(0.0, 100.0),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        facts={"tracer": types.SimpleNamespace(window=(0.0, 100.0))},
        planes=lambda: [plane] if ops else [])
    return cell, ev


def _toy_trace():
    """Two T = 1 steps (each: eight kernel calls, twelve grouped products,
    something else) around a longer prefill window that runs no kernel."""
    ops, modules = [_event(OTHER, 0.0, 0.001)], []
    for at in (1.0, 3.0):
        modules.append(_event("jit_fn(step)", at, 0.1))
        ops += [_event(MLA, at + 0.001 * i, 0.0005) for i in range(8)]
        ops += [_event(MOE, at + 0.02 + 0.001 * i, 0.0005)
                for i in range(12)]
        ops.append(_event(OTHER, at + 0.05, 0.02))
    modules.append(_event("jit_fn(window)", 2.0, 0.5))
    ops += [_event(MOE, 2.1, 0.3), _event(OTHER, 5.0, 0.001)]
    return ops, modules


def test_device_readers_take_the_steps_not_the_windows():
    ops, modules = _toy_trace()
    step = {"name": "decode_paged_step", "start": 1.0, "end": 1.2, "tid": 1,
            "args": {"experts_hit": 40, "assignments": 64,
                     "zero_assignments": 1000, "latent_rows_live": 320000}}
    cell, ev = _evidence(ops, modules, spans=[step])
    read = lambda name: cell.module("layer_metrics", name).read(ev)  # noqa: E731
    assert read("decode_step_device_ms") == pytest.approx(100.0)
    assert read("mla_decode_ms_per_step") == pytest.approx(4.0)
    assert read("moe_ms_per_step") == pytest.approx(6.0)
    # 160k tokens' rows in two pools a double layer, four double layers
    flops, moved = shortcut_decode.mla2_needs(cell.config, 320000)
    assert moved == 8 * 160000 * 576 * 2
    assert flops == 8 * 160000 * 2 * 64 * (576 + 512)
    assert read("mla2_decode_roofline") == pytest.approx(
        100 * (moved / 819e9) / 0.004)
    flops, moved = shortcut_decode.held_needs(cell.config, 40, 64)
    assert moved == 2 * 40 * 3 * 6144 * 2048
    assert flops == 64 * 2 * 3 * 6144 * 2048
    assert read("scmoe_held_roofline") == pytest.approx(
        100 * (moved / 819e9) / 0.006)
    for name in ("mla2_decode_roofline", "scmoe_held_roofline"):
        assert 0 < read(name) <= 100


def test_counter_readers_are_shares_of_what_a_step_could_do():
    cell, ev = _evidence([], [], counters={
        "decode_steps": 10, "moe_experts_hit": 10 * 4 * 10,
        "moe_zero_assignments": 10 * 64 * 12 * 4 // 3})
    read = lambda name: cell.module("layer_metrics", name).read(ev)  # noqa: E731
    assert read("scmoe_experts_hit_pct") == pytest.approx(100 * 10 / 16)
    assert read("moe_zero_share_pct") == pytest.approx(100 / 3)


def test_another_models_step_is_no_step_of_this_cell():
    """A step of the state model (the ``kda_decode`` kernel) runs no
    latent kernel: the device readers find nothing."""
    ops = [_event(KDA, 0.01, 0.004), _event(MOE, 0.02, 0.004),
           _event(OTHER, 0.03, 0.001)]
    cell, ev = _evidence(ops, [_event("jit_fn(step)", 0.0, 0.1)])
    for name in DEVICE_READERS:
        assert cell.module("layer_metrics", name).read(ev) is None


def test_readers_find_nothing_in_a_program_without_the_model():
    """A commit without the spans, the counters and the kernels: every
    new reader returns None and none raises."""
    ops = [_event(OTHER, 0.0, 0.01)]
    cell, ev = _evidence(ops, [_event("jit_fn", 0.0, 0.02)], counters={
        "decode_steps": 10})
    for name in SHARED_READERS + NEW_READERS:
        assert cell.module("layer_metrics", name).read(ev) is None
    cell, ev = _evidence([], [])
    ev.facts = {}
    for name in SHARED_READERS + NEW_READERS:
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
