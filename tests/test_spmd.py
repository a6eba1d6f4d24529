"""SPMD mainline: PartitionSpec policy, reshard round-trips, GSPMD
parity, telemetry, and the probe acceptance bar.

The tentpole contract (paddle_tpu/parallel/spmd.py): an UNTRANSFORMED
program + NamedSharding-committed inputs/state, with the XLA SPMD
partitioner deriving the collectives. These tests run in-process on the
8 virtual CPU devices conftest arms. tools/spmd_probe.py holds the
closed loop (TP=2 decode token-exactness vs the oracle, byte-equal f64
train digests, the DP=4-checkpoint -> TP=2-serve conversion); here live
the policy table's unit bars and the fast in-process parity runs.
"""

import warnings

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import compiler
from paddle_tpu.parallel import spmd


def _axes(model=1, data=1):
    return {"model": model, "data": data}


# ---------------------------------------------------------------------------
# spec_for: the documented param-name -> PartitionSpec policy table
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,shape,want",
    [
        # Megatron column rule: qkv + fc0 split the output dim
        ("gpt_3_att_q.w_0", (64, 64), (None, "model")),
        ("gpt_0_att_v.b_0", (64,), ("model",)),
        ("gpt_1_ffn_fc0.w_0", (64, 128), (None, "model")),
        ("gpt_1_ffn_fc0.b_0", (128,), ("model",)),
        # row rule: out-proj + fc1 split the input dim, bias replicated
        ("gpt_2_att_out.w_0", (64, 64), ("model",)),
        ("gpt_2_att_out.b_0", (64,), ()),
        ("gpt_5_ffn_fc1.w_0", (128, 64), ("model",)),
        ("gpt_5_ffn_fc1.b_0", (64,), ()),
        # vocab-column head
        ("lm_head.w_0", (64, 212), (None, "model")),
        # embeddings and layernorms replicate (documented)
        ("tok_embedding", (211, 64), ()),
        ("pos_embedding", (32, 64), ()),
        ("gpt_0_ln0.w_0", (64,), ()),
        ("emb_ln.b_0", (64,), ()),
        # paged KV pools [blocks, 1, block, hidden]: a token's heads lie
        # side by side on the last dim, which the model axis splits;
        # addressing replicated
        ("gpt_paged_k_0_n4x32", (4, 1, 32, 64), (None, None, None, "model")),
        ("gpt_paged_v_3_n16x4", (16, 1, 4, 64), (None, None, None, "model")),
        # a row the axis does not divide stays whole
        ("gpt_paged_k_1_n8x4", (8, 1, 4, 33), ()),
    ],
)
def test_tp_policy_table(name, shape, want):
    assert spmd.spec_for(name, shape, _axes(model=2)) == want


def test_tp_rules_inert_without_model_axis():
    # a pure-DP mesh never touches param layout
    assert spmd.spec_for("gpt_0_att_q.w_0", (64, 64), _axes()) == ()
    assert spmd.spec_for("gpt_0_att_q.w_0", (64, 64), _axes(data=4)) == ()


def test_non_divisible_dim_falls_back_replicated():
    # GPTConfig.tiny's vocab of 211 does not divide TP=2: the head
    # replicates instead of erroring (correctness never depends on
    # divisibility)
    assert spmd.spec_for("lm_head.w_0", (64, 211), _axes(model=2)) == ()
    assert spmd.spec_for("lm_head.b_0", (211,), _axes(model=2)) == ()


def test_override_beats_name_policy():
    got = spmd.spec_for(
        "gpt_0_att_q.w_0", (64, 64), _axes(model=2), override=("model",)
    )
    assert got == ("model",)


def test_unknown_param_replicates_with_one_time_warning():
    name = "totally_novel_block.w_0"
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = spmd.spec_for(name, (64, 64), _axes(model=2))
        again = spmd.spec_for(name, (64, 64), _axes(model=2))
    assert got == () and again == ()
    hits = [x for x in w if name in str(x.message)]
    assert len(hits) == 1  # warned exactly once across repeat calls
    # non-parameter unknowns (optimizer slots, caches with odd names)
    # replicate silently
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        spmd.spec_for("novel_state_xyz", (64,), _axes(model=2),
                      is_parameter=False)
    assert not w


def test_fsdp_shards_dim0_of_float_state():
    # params AND same-shaped optimizer accumulators shard dim 0 over
    # data when divisible...
    got = spmd.spec_for("fc_0.w_0_velocity_0", (16, 32), _axes(data=2),
                        fsdp=True, is_parameter=False)
    assert got == ("data",)
    # ...an odd leading dim stays replicated...
    got = spmd.spec_for("odd.w_0_velocity_0", (15, 32), _axes(data=2),
                        fsdp=True, is_parameter=False)
    assert got == ()
    # ...and integer state never FSDP-shards
    got = spmd.spec_for("step_counter", (16,), _axes(data=2), fsdp=True,
                        is_parameter=False, is_floating=False)
    assert got == ()


# ---------------------------------------------------------------------------
# lower() + reshard round-trips over the real virtual-device mesh
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gpt_scope():
    from paddle_tpu.models import gpt

    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    with fluid.unique_name.guard():
        infer, startup, _feeds, _logits = gpt.build_gpt_infer(cfg, 16)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    baseline = {
        v.name: np.array(np.asarray(scope.get(v.name)))
        for v in infer.list_vars()
        if getattr(v, "is_parameter", False)
    }
    return infer, scope, baseline


def test_lower_assigns_policy_specs(gpt_scope):
    infer, _scope, baseline = gpt_scope
    plan = spmd.lower(infer, spmd.tp_mesh(2))
    qkv = [n for n in baseline if n.endswith("_att_q.w_0")]
    assert qkv and all(plan.spec_of(n) for n in qkv)
    assert plan.summary()["sharded_params"] == len(plan.sharded_params())
    assert plan.summary()["mesh"] == (("model", 2),)
    # layernorms replicated: absent from the sharded set
    assert not any("_ln" in n for n in plan.sharded_params())


def test_reshard_round_trip_dp_to_tp_to_single(gpt_scope):
    """DP-replicated -> TP=2 -> single-device, bit-exact at every hop
    (the in-memory image of load_train_checkpoint's N->M conversion;
    the probe covers the on-disk DP=4-checkpoint -> TP=2 leg)."""
    import jax

    infer, scope, baseline = gpt_scope
    names = sorted(baseline)

    # hop 1: a DP=4 data mesh (params replicated, the train placement)
    plan_dp = spmd.lower(infer, spmd.data_mesh(4))
    assert spmd.place_scope(scope, plan_dp, names) == len(names)

    # hop 2: the TP=2 serving mesh — qkv/ffn actually split over devices
    plan_tp = spmd.lower(infer, spmd.tp_mesh(2))
    assert spmd.place_scope(scope, plan_tp, names) == len(names)
    qkv = next(n for n in names if n.endswith("_att_q.w_0"))
    val = scope.get(qkv)
    assert len(val.sharding.device_set) == 2
    shard = val.addressable_shards[0].data
    assert shard.shape[1] * 2 == baseline[qkv].shape[1]
    for n in names:
        assert (np.asarray(scope.get(n)) == baseline[n]).all(), n

    # hop 3: back to one device — still bit-exact
    for n in names:
        scope.set(n, jax.device_put(
            np.asarray(scope.get(n)), jax.devices()[0]))
        assert (np.asarray(scope.get(n)) == baseline[n]).all(), n


def test_active_plan_telemetry(gpt_scope):
    from paddle_tpu.observability import registry as obs_registry
    from paddle_tpu.observability import xla_stats

    infer, _scope, _baseline = gpt_scope
    plan = spmd.lower(infer, spmd.tp_mesh(2))
    assert spmd.active_plan() is plan
    gauges = obs_registry.gauge_values()
    assert gauges.get('spmd_mesh_shape{axis="model"}') == 2.0
    assert gauges.get("spmd_sharded_params") == float(
        len(plan.sharded_params()))
    rendered = obs_registry.render_prometheus()
    assert "spmd_mesh_shape" in rendered
    assert "spmd_sharded_params" in rendered
    stanza = xla_stats.compiles_endpoint().get("spmd")
    assert stanza and stanza["specs_fp"] == plan.fingerprint()


def test_spmd_summary_enters_compile_key(gpt_scope):
    """The sharding policy is part of the compile identity: same
    program, different mesh -> different key (the strict gate and
    compile telemetry see sharding changes as new programs)."""
    from paddle_tpu.observability import xla_stats

    infer, _scope, _baseline = gpt_scope
    k_plain = xla_stats.make_key(infer, ["ids"], ["out"])
    k_tp = xla_stats.make_key(
        infer, ["ids"], ["out"],
        spmd=spmd.lower(infer, spmd.tp_mesh(2)).summary())
    k_tp2 = xla_stats.make_key(
        infer, ["ids"], ["out"],
        spmd=spmd.lower(infer, spmd.tp_mesh(4)).summary())
    assert k_plain != k_tp
    assert k_tp != k_tp2


# ---------------------------------------------------------------------------
# in-process GSPMD parity: FSDP leg (the DP leg lives in
# test_multiprocess_dp.py; byte-equal digests live in the probe's f64
# child)
# ---------------------------------------------------------------------------


def _mlp(seed=90):
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=32, act="relu")
        logits = fluid.layers.fc(h, size=5)
        loss = fluid.layers.softmax_with_cross_entropy(logits, y)
        avg = fluid.layers.mean(loss)
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(avg)
    return main, startup, avg


def test_fsdp_matches_single_device_and_shards_velocity():
    def run(fsdp):
        scope = fluid.core.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        main, startup, avg = _mlp()
        with fluid.scope_guard(scope):
            exe.run(startup)
            prog = main
            if fsdp:
                prog = compiler.CompiledProgram(main).with_mesh(
                    loss_name=avg.name, mesh_axes={"data": 2}, fsdp=True
                )
            losses = []
            for step in range(3):
                rng = np.random.RandomState(77 + step)
                feed = {
                    "x": rng.rand(32, 16).astype("float32"),
                    "y": rng.randint(0, 5, (32, 1)).astype("int64"),
                }
                (lv,) = exe.run(prog, feed=feed, fetch_list=[avg.name])
                losses.append(float(np.asarray(lv).reshape(-1)[0]))
            vel = {
                v.name: scope.get(v.name)
                for v in main.list_vars()
                if v.persistable and "velocity" in v.name
            }
        return losses, vel

    base, _ = run(fsdp=False)
    got, vel = run(fsdp=True)
    np.testing.assert_allclose(got, base, rtol=1e-5, atol=1e-5)
    # the optimizer-sharding claim, in-process: a divisible velocity
    # accumulator holds HALF its rows per device
    sharded = [v for v in vel.values()
               if getattr(v, "addressable_shards", None)
               and v.addressable_shards[0].data.shape[0] * 2
               == v.shape[0]]
    assert sharded, "no velocity accumulator was dim-0 sharded"


# ---------------------------------------------------------------------------
# the closed loop (ISSUE acceptance): tools/spmd_probe.py --fast
# ---------------------------------------------------------------------------


def test_spmd_probe_fast_acceptance():
    """Tentpole bar: TP=2 decode token-exact vs the oracle across
    miss/hit/chunked/resume, DP=2/FSDP=2 f64 train digests byte-equal
    single-device, optimizer bytes ~1/N under FSDP, a DP=4 checkpoint
    served by a TP=2 replica bit-exact, and 0 steady-state recompiles
    under the armed strict gate. Subprocess via the shared conftest
    helper (the probe arms its own virtual devices)."""
    from conftest import run_probe_subprocess

    p, report = run_probe_subprocess("spmd_probe.py")
    assert p.returncode == 0, "probe failed:\n%s\n%s" % (
        p.stdout[-3000:], p.stderr[-2000:]
    )
    assert report["pass"] is True
    assert report["tp_parity"] == {
        "chunked_windows": True, "hit": True, "miss": True,
        "resume": True, "slot_churn": True,
    }
    assert report["train"]["dp_equal"] and report["train"]["fsdp_equal"]
    assert report["train"]["opt_bytes_ratio"] <= 0.6
    assert report["reshard"]["bit_exact"] and report["reshard"]["serve_parity"]
    assert report["strict"]["steady_recompiles"] == 0
