"""A step's arguments are resolved once (``_CompiledBlock._resolve``):
the scope cell, the placement and the last array handed over are kept
between runs of a block against a scope, and a run checks each by
identity. Every case here drives a WARM block (one that has its record)
beside a freshly built ``_CompiledBlock`` run on a copy of the same scope,
and wants the fetches and every persistable equal bit for bit, whatever
was done to the scope between the runs. Toy widths on the CPU.
"""

import gc
import sys
import threading
import weakref

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import compiler, core, profiler
from paddle_tpu.fluid import executor as executor_mod
from paddle_tpu.models import gpt
from paddle_tpu.observability import trace
from paddle_tpu.serving import decode
from test_phase_spans import _train_program as _adam_trained_program

PLACE = fluid.CPUPlace()
LR = "learning_rate_0"


def _train_program(donate=False):
    """The small Adam-trained classifier of ``test_phase_spans``."""
    main, startup, loss = _adam_trained_program()
    if donate:
        main._donate_mutable = True
    return main, startup, loss


def _feed(step, n=8):
    r = np.random.RandomState(100 + step)
    return {"x": r.rand(n, 8).astype("float32"),
            "y": r.randint(0, 4, (n, 1)).astype("int64")}


def _names(scope):
    names, s = set(), scope
    while s is not None:
        names |= set(s.local_var_names())
        s = s._parent
    return sorted(names)


def _copy_of(scope):
    """A flat scope holding a host copy of every value ``scope`` sees."""
    out = core.Scope()
    for n in _names(scope):
        v = scope.get(n)
        if isinstance(v, core.LoDTensor):
            out.set(n, core.LoDTensor(np.array(v.numpy()), v.lod()))
        elif v is not None:
            out.set(n, np.array(v))
    return out


def _same_bits(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape, what
    assert got.tobytes() == want.tobytes(), what


def _same_scopes(scope, ref):
    assert _names(scope) == _names(ref)
    for n in _names(ref):
        _same_bits(scope.get(n), ref.get(n), n)


def _marshals():
    return [s["args"] for s in trace.with_phases(trace.get_spans())
            if s["name"] == "executor_marshal"]


def _record_holds_what_the_scope_holds(block, scope):
    """Each remembered value IS its cell's value, and none is a buffer
    that a donation deleted."""
    rec = block._records[scope]
    for e in rec.entries.values():
        held = e.held()
        if held is executor_mod._NOT_HELD:
            continue
        assert held is e.cell.value, e.name
        assert isinstance(held, jax.Array) and not held.is_deleted()
    return rec


class _Warm(object):
    """A trained program on ``scope`` through one executor, and the
    oracle: ``step`` runs the warm block and a new block on a copy."""

    def __init__(self, donate=False, scope=None, startup_scope=None):
        self.main, startup, self.loss = _train_program(donate)
        self.exe = fluid.Executor(PLACE)
        self.scope = scope if scope is not None else core.Scope()
        self.exe.run(startup, scope=startup_scope or self.scope)
        self.notes = []

    def block(self):
        (block,) = [c for c in self.exe._cache.values()
                    if c.program is self.main]
        return block

    def step(self, i):
        feed = _feed(i)
        ref = _copy_of(self.scope)
        fresh = executor_mod._CompiledBlock(
            self.main, 0, list(feed), [self.loss.name], PLACE)
        (want,) = fresh.run(ref, feed, executor_mod._fixed_rng(), PLACE)
        trace.reset()
        (got,) = self.exe.run(self.main, feed=feed, fetch_list=[self.loss],
                              scope=self.scope)
        (note,) = _marshals()
        assert note["reused"] + note["placed"] <= note["values"]
        self.notes.append(note)
        _same_bits(got, want, "loss of step %d" % i)
        _same_scopes(self.scope, ref)
        _record_holds_what_the_scope_holds(self.block(), self.scope)
        return note


def _params(warm):
    return [v.name for v in warm.main.list_vars()
            if getattr(v, "is_parameter", False)]


# -- what may be done to a scope between two runs ----------------------------
def _nothing(warm, i):
    pass


def _set_a_parameter(warm, i):
    name = _params(warm)[0]
    new = np.asarray(warm.scope.get(name)) * 0.5 + i
    # a host array once, a device array the next time
    warm.scope.set(name, new if i % 2 else jax.numpy.asarray(new))


def _set_a_tensor_in_place(warm, i):
    warm.scope.find_var(LR).get_tensor().set(
        np.array([0.3 / (i + 1)], "float32"))


def _write_a_numpy_value_in_place(warm, i):
    v = warm.scope.get(LR)
    if not isinstance(v, np.ndarray):
        warm.scope.set(LR, np.array([0.05], "float32"))
    else:
        v[...] = 0.2 / (i + 1)


def _erase_and_create_again(warm, i):
    name = _params(warm)[-1]
    kept = np.asarray(warm.scope.get(name)) + 1.0
    warm.scope.erase([name])
    warm.scope.set(name, kept)


DISTURBANCES = [_nothing, _set_a_parameter, _set_a_tensor_in_place,
                _write_a_numpy_value_in_place, _erase_and_create_again]


@pytest.mark.parametrize("donate", [False, True], ids=["kept", "donated"])
@pytest.mark.parametrize("disturb", DISTURBANCES,
                         ids=[d.__name__.strip("_") for d in DISTURBANCES])
def test_a_warm_block_sees_what_a_new_one_sees(disturb, donate):
    """Five steps, the scope disturbed before the third, fourth and
    fifth: the warm block's loss and state are a new block's, bit for
    bit (``program._donate_mutable``: also with its state donated)."""
    warm = _Warm(donate)
    for i in range(5):
        if i >= 2:
            disturb(warm, i)
        warm.step(i)


def test_steady_steps_hand_everything_over_on_the_identity_check():
    """From the second step on nothing but the two feeds is looked up or
    placed, nothing is resolved again, and the writeback goes through
    the cells, not ``scope.set``."""
    warm = _Warm()
    first = warm.step(0)
    assert first["reused"] == 0 and first["placed"] == 2
    resolved = profiler.get_counter("executor_arg_records_resolved")
    reused = profiler.get_counter("executor_values_reused")
    sets = []
    real = warm.scope.set
    warm.scope.set = lambda n, v: (sets.append(n), real(n, v))[1]
    try:
        notes = [warm.step(i) for i in (1, 2, 3)]
    finally:
        del warm.scope.set
    # the oracle's new blocks resolve too, once each, and reuse nothing
    assert profiler.get_counter("executor_arg_records_resolved") \
        - resolved == 3
    for note in notes:
        assert note["placed"] == 2
        assert note["reused"] == note["values"] - 2 > 10
    assert profiler.get_counter("executor_values_reused") - reused \
        == sum(n["reused"] for n in notes)
    assert sets == []


def test_a_set_value_is_looked_up_once_and_remembered_if_it_is_an_array():
    warm = _Warm()
    for i in range(2):
        warm.step(i)
    steady = warm.notes[-1]["reused"]
    name = _params(warm)[0]
    warm.scope.set(name, jax.numpy.asarray(warm.scope.get(name)) * 2.0)
    assert warm.step(2)["reused"] == steady - 1
    assert warm.step(3)["reused"] == steady
    # a host value is placed on every run and never remembered
    warm.scope.set(LR, np.array([0.02], "float32"))
    for i in (4, 5, 6):
        note = warm.step(i)
        assert note["reused"] == steady - 1 and note["placed"] == 3
        entry = warm.block()._records[warm.scope].entries[LR]
        assert entry.held() is executor_mod._NOT_HELD
        assert isinstance(warm.scope.get(LR), np.ndarray)


def test_the_record_keeps_no_array_the_scope_has_let_go():
    """``reset_caches`` and a reload set host arrays over the device
    arrays of before: those are freed at once, not at the next run of
    every block that once took them (4.8 GB of pools in
    ``gpt2s-serve-chat``, which read twice its peak memory when the
    record held them strongly)."""
    warm = _Warm()
    for i in range(2):
        warm.step(i)
    name = _params(warm)[0]
    gone = weakref.ref(warm.scope.get(name))
    assert isinstance(gone(), jax.Array)
    warm.scope.set(name, np.array(warm.scope.get(name)))
    gc.collect()
    assert gone() is None
    warm.step(2)


def test_structure_changes_resolve_again_and_value_changes_do_not():
    scope = core.Scope()
    kid = scope.new_scope()
    assert scope.structure_stamp() == (0,)
    scope.set("a", 1)
    assert scope.structure_stamp() == (1,) and kid.structure_stamp() == (0, 1)
    scope.set("a", 2)
    scope.find_var("a").set_value(3)
    assert scope.structure_stamp() == (1,)
    assert kid.find_var("a") is scope.find_var("a")
    assert kid.find_local_var("a") is None
    kid.set("a", 4)                          # the kid shadows its parent
    assert kid.structure_stamp() == (1, 1) and scope.get("a") == 3
    assert kid.find_local_var("a") is kid.find_var("a")
    scope.erase(["a", "never_there"])
    assert kid.structure_stamp() == (1, 2)
    kid.erase(["a"])
    assert kid.structure_stamp() == (2, 2) and kid.find_var("a") is None


def test_a_kid_scope_shadows_its_parent_after_the_first_run():
    """Startup fills the parent; the steps run on a kid. The first
    writeback makes the kid's own cells (the parent's are never
    written), then a constant the kid still reads from its parent is
    shadowed: both resolve again and both are seen."""
    parent = core.Scope()
    warm = _Warm(scope=parent.new_scope(), startup_scope=parent)
    before = _copy_of(parent)
    resolved = profiler.get_counter("executor_arg_records_resolved")
    for i in range(3):
        warm.step(i)
    _same_scopes(parent, before)             # the parent was only read
    assert warm.scope.find_local_var(LR) is None
    # the second step found new cells under the names (one constant is
    # still its parent's), the third finds what the second left in them
    assert [n["reused"] for n in warm.notes] == [0, 1,
                                                 warm.notes[2]["values"] - 2]
    # two for the warm block (the first run, then the kid's new cells),
    # one for each of the oracle's three blocks
    assert profiler.get_counter("executor_arg_records_resolved") \
        - resolved == 5
    warm.scope.set(LR, np.array([0.5], "float32"))   # shadows the parent's
    warm.step(3)
    assert profiler.get_counter("executor_arg_records_resolved") \
        - resolved == 7
    warm.scope.erase([LR])                   # and the parent's is back
    for i in (4, 5):
        warm.step(i)
    _same_scopes(parent, before)


def test_two_scopes_on_one_block_from_two_threads():
    """A serving pool's workers share one compiled block over a scope
    each: every thread's steps are the steps a new block makes on a copy
    of that thread's scope."""
    main, startup, loss = _train_program()
    exe = fluid.Executor(PLACE)
    scopes = [core.Scope(), core.Scope(), core.Scope()]
    for s in scopes:
        exe.run(startup, scope=s)
    feeds = [[_feed(10 * t + i) for i in range(6)] for t in range(3)]
    for t, s in enumerate(scopes):           # three different states
        s.set(LR, np.array([0.01 * (t + 1)], "float32"))
    refs = [_copy_of(s) for s in scopes]
    block = executor_mod._CompiledBlock(
        main, 0, list(feeds[0][0]), [loss.name], PLACE)
    rng = executor_mod._fixed_rng()
    got = [[] for _ in scopes]
    failed = []

    def work(t):
        try:
            for feed in feeds[t]:
                (out,) = block.run(scopes[t], feed, rng, PLACE)
                got[t].append(np.asarray(out))
        except BaseException as e:           # noqa: B036 (reported below)
            failed.append(e)
            raise

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(len(scopes))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(before)
    assert not failed and not any(th.is_alive() for th in threads)
    for t, ref in enumerate(refs):
        fresh = executor_mod._CompiledBlock(
            main, 0, list(feeds[0][0]), [loss.name], PLACE)
        for feed, out in zip(feeds[t], got[t]):
            (want,) = fresh.run(ref, feed, rng, PLACE)
            _same_bits(out, want, "thread %d" % t)
        _same_scopes(scopes[t], ref)
        _record_holds_what_the_scope_holds(block, scopes[t])
    assert len(block._records) == 3
    del scopes[0], s                         # a dropped scope drops its record
    gc.collect()
    assert len(block._records) == 2


def test_a_host_segment_between_two_xla_segments():
    """A counter the first segment steps is read by the second from
    ``local_env`` (the scope still holds the value of before), past a
    host op: never from the record."""
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            ctr = fluid.layers.create_global_var(
                shape=[1], value=0.0, dtype="float32", persistable=True,
                name="ctr")
            fluid.layers.increment(ctr, value=1.0, in_place=True)
            h = fluid.layers.fc(input=x, size=4)
            shown = fluid.layers.Print(h, message="between")
            out = fluid.layers.elementwise_add(
                fluid.layers.fc(input=shown, size=2), ctr)
    exe = fluid.Executor(PLACE)
    scope = core.Scope()
    exe.run(startup, scope=scope)
    for i in range(4):
        feed = {"x": np.full((2, 4), 1.0 + i, "float32")}
        ref = _copy_of(scope)
        fresh = executor_mod._CompiledBlock(
            main, 0, list(feed), [out.name], PLACE)
        assert [k for k, _s, _p in fresh._plans] == ["xla", "host", "xla"]
        (want,) = fresh.run(ref, feed, executor_mod._fixed_rng(), PLACE)
        trace.reset()
        (got,) = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
        first, second = _marshals()
        for note in (first, second):
            assert note["reused"] + note["placed"] <= note["values"]
        if i:
            # all but the feed; then all but the stepped counter and
            # the host op's output, which ``local_env`` holds
            assert first["reused"] == first["values"] - 1
            assert second["reused"] == second["values"] - 2
        _same_bits(got, want, "step %d" % i)
        _same_scopes(scope, ref)
        assert float(np.asarray(scope.get("ctr"))[0]) == i + 1.0


# -- two programs alternating on one scope -----------------------------------
SLOTS, MAX_LEN, BLOCK = 3, 24, 4


def _session():
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0,
                             vocab_size=97)
    cfg.max_position_embeddings = MAX_LEN + 1
    with fluid.unique_name.guard():
        _infer, startup, _n, _logits = gpt.build_gpt_infer(cfg, MAX_LEN)
    scope = core.Scope()
    fluid.Executor(PLACE).run(startup, scope=scope)
    return decode.DecodeSession(
        cfg, place=PLACE, scope=scope, slots=SLOTS, max_len=MAX_LEN,
        prefill_buckets=[8], block_size=BLOCK, spec_tokens=1)


def test_a_step_and_a_window_alternate_on_one_scope():
    """The serve step and a prefill window share the pools: each finds
    the other's outputs in the cells, looks those up and reuses the
    weights. The oracle is a session on a copy of the scope whose
    executor forgets its blocks before every call."""
    warm = _session()
    oracle = _session()
    for n in _names(warm.scope):
        oracle.scope.set(n, np.array(warm.scope.get(n)))
    warm.reset_caches()
    oracle.reset_caches()
    pools = {n for layer in warm.pool_names() for n in layer}
    weights = {v.name for v in warm._paged_step[1][0].list_vars()
               if v.persistable and v.name not in pools
               and warm.scope.get(v.name) is not None}
    rng = np.random.default_rng(3)
    tables = [[1, 2, 3], [4, 5, 6], ()]
    calls = [("window", 0, 5), ("step", None, None), ("window", 1, 3),
             ("step", None, None), ("step", None, None),
             ("window", 0, 4), ("step", None, None)]
    positions = [0, 0, 0]
    seen = []
    for kind, slot, n in calls:
        if kind == "window":
            prompt = [int(t) for t in rng.integers(0, 97, n)]
            args = (tables[slot], prompt, positions[slot])
            positions[slot] += n
            method = "paged_window"
            kw = {"slot": slot}
        else:
            args = (rng.integers(0, 97, (SLOTS, 1)), list(positions),
                    tables, [True, True, False])
            positions = [p + 1 if t else p
                         for p, t in zip(positions, tables)]
            method = "paged_step"
            kw = {}
        oracle.exe._cache.clear()
        oracle.exe._plans.clear()
        want = getattr(oracle, method)(*args, **kw)
        trace.reset()
        got = getattr(warm, method)(*args, **kw)
        (note,) = _marshals()
        assert note["reused"] + note["placed"] <= note["values"]
        seen.append((kind, note))
        _same_bits(got, want, kind)
        for name in sorted(pools):
            _same_bits(warm.scope.get(name), oracle.scope.get(name), name)
    # once both programs have run, a step hands every weight over on the
    # identity check and looks up the pools the window wrote (and the
    # other way round); two steps in a row reuse the pools too
    after_window = [note for (kind, note), (before, _n) in
                    zip(seen[3:], seen[2:]) if kind == "step"
                    and before == "window"]
    in_a_row = [note for (kind, note), (before, _n) in
                zip(seen[3:], seen[2:]) if kind == "step"
                and before == "step"]
    assert after_window and in_a_row
    for note in after_window:
        assert len(weights) <= note["reused"] < len(weights) + len(pools)
    for note in in_a_row:
        assert note["reused"] >= len(weights) + len(pools)


def test_reset_caches_frees_the_pools_of_before():
    """The engine's warm-up ends in ``reset_caches``: host zeros set
    over the pools the programs left. No program's record keeps those
    alive (on the chip they are 4.8 GB of ``gpt2s-serve-chat``'s 16)."""
    sess = _session()
    sess.reset_caches()
    sess.paged_window([1, 2, 3], [5, 6, 7], 0, slot=0)
    sess.paged_step(np.ones((SLOTS, 1), "int64"), [3, 0, 0],
                    [[1, 2, 3], (), ()], [True, False, False])
    pools = [n for layer in sess.pool_names() for n in layer]
    shapes = {tuple(sess.scope.get(n).shape) for n in pools}
    assert all(isinstance(sess.scope.get(n), jax.Array) for n in pools)

    def live_pools():
        gc.collect()
        return [a for a in jax.live_arrays()
                if tuple(a.shape) in shapes and not a.is_deleted()]

    assert len(live_pools()) >= len(pools)
    sess.reset_caches()
    assert live_pools() == []


# -- under a mesh ------------------------------------------------------------
@pytest.fixture
def named_shardings_built(monkeypatch):
    """Counts the ``NamedSharding``s this repo's code constructs (it
    reaches the class through ``jax.sharding`` at the call)."""
    built = []
    real = jax.sharding.NamedSharding

    class Counting(real):
        def __init__(self, *a, **kw):
            built.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.sharding, "NamedSharding", Counting)
    return built


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
@pytest.mark.parametrize("entry", ["with_mesh", "with_data_parallel"])
def test_no_sharding_is_built_after_the_first_step(entry,
                                                   named_shardings_built):
    """GSPMD (``SpmdPlan.sharding_of`` / ``feed_sharding``) and the
    shard_map mesh path alike: a value's ``NamedSharding`` is built once
    a name, at the first run, and the later steps build none."""
    main, startup, loss = _train_program()
    exe = fluid.Executor(PLACE)
    scope = core.Scope()
    exe.run(startup, scope=scope)
    if entry == "with_mesh":
        target = compiler.CompiledProgram(main).with_mesh(
            loss_name=loss.name, mesh_axes={"data": 4}, fsdp=True)
    else:
        target = compiler.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=jax.devices()[:4])
    losses = []
    for i in range(4):
        if i == 1:
            assert named_shardings_built
            del named_shardings_built[:]
        trace.reset()
        (out,) = exe.run(target, feed=_feed(i), fetch_list=[loss],
                         scope=scope)
        losses.append(float(np.mean(out)))
        (note,) = _marshals()
        assert note["reused"] + note["placed"] <= note["values"]
        if i:
            # the shard_map path commits no placement to the scope (only
            # GSPMD does): its one read-only value, the learning rate,
            # stays where startup put it and is laid out on every step
            placed = 2 if entry == "with_mesh" else 3
            assert note["placed"] == placed
            assert note["reused"] == note["values"] - placed
    assert named_shardings_built == []
    assert losses[-1] < losses[0]
    (block,) = [c for c in exe._cache.values() if c.program is main]
    _record_holds_what_the_scope_holds(block, scope)
