"""The decode engine: paged KV block tables + speculative decoding.

Covers the ISSUE-16 tentpole surfaces: the paged cache ops as units
(permuted / shared / copy-on-write tables), the host-side block
allocator and zero-copy prefix index, and the engine end-to-end —
greedy + seeded-sampled token parity vs the full-forward oracle with
speculation forced through EVERY accept/reject split point, prefix
hit / chunked / resume admissions, prefix eviction, pool-OOM shedding,
and the zero-steady-recompile invariant under the armed strict gate.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import profiler
from paddle_tpu.models import gpt
from paddle_tpu.observability import registry as obs_registry
from paddle_tpu.serving import decode as sdecode
from paddle_tpu.serving.batcher import ServerOverloadedError

MAX_LEN = 20
SLOTS = 3
BLOCK = 4
SPEC_K = 4


# -- op units ---------------------------------------------------------------
# name -> (heads, block, table entries, live keys a slot, Lengths given,
# dead entries poisoned). The kernel takes ceil(128 / block) table entries
# a program, or the whole table where it is shorter.
PAGED_KERNEL_CASES = {
    # every table entry live: the semantics without Lengths
    "whole_table": (2, BLOCK, 5, [3, 11, 20], False, False),
    "lengths": (2, BLOCK, 5, [3, 11, 20], True, False),
    # one key, exactly one block, one block + 1, the whole table
    "length_edges": (2, BLOCK, 5, [1, BLOCK, BLOCK + 1, 5 * BLOCK], True,
                     False),
    # 11 entries in programs of 8: the last program is short, and the
    # lengths end in the first program, at its edge, and in the second
    "ragged_programs": (2, 16, 11, [7, 128, 129, 176], True, False),
    "ragged_whole_table": (2, 16, 11, [7, 130, 176], False, False),
    # a tp = 4 shard of 12 heads
    "three_heads": (3, BLOCK, 5, [3, 11, 20], True, False),
    # dead entries aimed at a block of NaN keys and inf values
    "poisoned_dead": (2, BLOCK, 5, [3, 11, 17], True, True),
    "poisoned_dead_ragged": (2, 16, 11, [7, 128, 129], True, True),
}


@pytest.mark.parametrize("case", sorted(PAGED_KERNEL_CASES))
@pytest.mark.parametrize("mask", ["per_slot", "per_head", "none"])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 3e-2)])
def test_paged_flash_kernel_matches_gather_reference(case, mask, dtype, tol):
    """flash_decode_paged_attention's Pallas kernel (interpret mode)
    against its gather-then-softmax reference, through permuted tables,
    with the key bias in each layout the kernel admits: one mask per slot
    (what the engine feeds), one per head, and none. With Lengths
    the reference masks the dead table entries itself and reads clean
    tables, so a kernel (or fallback) that let a dead block into the
    result — the poisoned cases aim them at NaN keys and inf values —
    would differ or not be finite."""
    import importlib

    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    H, block, MB, live, given, poisoned = PAGED_KERNEL_CASES[case]
    live = np.array(live)
    S, D = len(live), 16
    NB = S * MB + 1
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(S, H, 1, D), dtype)
    # a token's keys are one row of the pool, its heads side by side
    k_pool = rs.randn(NB + 1, block, H * D)
    v_pool = rs.randn(NB + 1, block, H * D)
    k_pool[NB], v_pool[NB] = np.nan, np.inf      # no clean table names it
    k_pool, v_pool = jnp.asarray(k_pool, dtype), jnp.asarray(v_pool, dtype)
    clean = rs.permutation(NB - 1)[:S * MB].reshape(S, MB) + 1
    cols = np.arange(MB * block)[None]
    kb = np.where(cols < live[:, None], 0.0, -1e4)
    if mask == "per_head":
        kb = np.repeat(kb, H, 0) + 0.1 * rs.randn(S * H, MB * block)
    kb = None if mask == "none" else jnp.asarray(kb, "float32")
    ref_kb, kwargs, tables = kb, {}, clean
    if given:
        dead = cols >= (-(-live // block) * block)[:, None]
        ref_kb = np.where(dead, -1e30, 0.0)
        if mask == "per_head":
            ref_kb = np.repeat(ref_kb, H, 0)
        ref_kb = jnp.asarray(ref_kb, "float32") + (0.0 if kb is None else kb)
        kwargs["lengths"] = jnp.asarray(live, "int32")
        if poisoned:
            tables = np.where(dead[:, ::block], NB, clean)
    want = fa.flash_decode_paged_attention(q, k_pool, v_pool,
                                           jnp.asarray(clean), key_bias=ref_kb)
    tables = jnp.asarray(tables)
    got = fa.flash_decode_paged_attention(q, k_pool, v_pool, tables,
                                          key_bias=kb, interpret=True,
                                          **kwargs)
    assert got.shape == (S, H, 1, D) and got.dtype == q.dtype
    assert np.isfinite(np.asarray(got, "float32")).all()
    np.testing.assert_allclose(np.asarray(got, "float32"),
                               np.asarray(want, "float32"), atol=tol)
    # the fallback reads a table row no further than the kernel does
    dense = fa.flash_decode_paged_attention(q, k_pool, v_pool, tables,
                                            key_bias=kb, **kwargs)
    np.testing.assert_allclose(np.asarray(dense, "float32"),
                               np.asarray(want, "float32"), atol=tol)


# name -> (heads, d_head, block, table entries): the toy rows of the
# tests' models (hidden 32 and 64: a padded row on the chip) and the
# serve cell's 12 x 64 = 768 lanes
PAGED_ROW_GEOMETRY = {
    "toy_hidden32": (2, 16, BLOCK, 5),
    "toy_hidden64": (4, 16, BLOCK, 40),
    "real_12x64": (12, 64, 16, 12),
}


@pytest.mark.parametrize("geometry", sorted(PAGED_ROW_GEOMETRY))
@pytest.mark.parametrize("mask", ["per_slot", "per_head"])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_paged_kernel_matches_reference_attention(geometry, mask, dtype, tol):
    """The kernel (interpret mode) and its fallback against
    ``reference_attention`` over rows gathered BY HAND from the
    ``[blocks, block, heads * d_head]`` pool, head h read from lanes
    h*d_head .. (h+1)*d_head: permuted tables, one physical block shared
    by two slots, dead table entries aimed at a block of NaN keys and inf
    values, a slot of one key, and an inactive slot parked on the sink
    block (table all 0, position 0)."""
    import importlib

    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    N, D, block, MB = PAGED_ROW_GEOMETRY[geometry]
    H, S = N * D, MB * block
    B = 5
    rs = np.random.RandomState(1)
    live = np.array([1, block + 1, S - 3, S, 1])    # the last slot: inactive
    NB = B * MB + 2
    poison = NB - 1
    k_pool = rs.randn(NB, block, H)
    v_pool = rs.randn(NB, block, H)
    k_pool[poison], v_pool[poison] = np.nan, np.inf
    tables = rs.permutation(np.arange(1, NB - 1))[:B * MB].reshape(B, MB)
    tables[1, 0] = tables[2, 0]                     # a shared first block
    tables[4] = 0                                   # the sink block
    entries = -(-live // block)
    for b in range(B - 1):
        tables[b, entries[b]:] = poison
    q = jnp.asarray(rs.randn(B, N, 1, D), dtype)
    k_pool, v_pool = jnp.asarray(k_pool, dtype), jnp.asarray(v_pool, dtype)
    cols = np.arange(S)[None]
    kb = np.where(cols < live[:, None], 0.0, -1e4)             # [B, S]
    if mask == "per_head":
        kb = (kb[:, None] + 0.5 * rs.randn(B, N, S)).reshape(B * N, S)
    kb = jnp.asarray(kb, "float32")

    # the oracle: rows by hand, dead entries read block 0 and are masked
    clean = np.where(np.arange(MB)[None] < entries[:, None], tables, 0)
    kf, vf = (np.asarray(p, "float32") for p in (k_pool, v_pool))
    rows_k = kf[clean].reshape(B, S, N, D).transpose(0, 2, 1, 3)
    rows_v = vf[clean].reshape(B, S, N, D).transpose(0, 2, 1, 3)
    dead = cols >= (entries * block)[:, None]
    bias = np.asarray(kb).reshape(B, -1, 1, S) + np.where(
        dead, -1e30, 0.0)[:, None, None, :]
    want = np.asarray(fa.reference_attention(
        jnp.asarray(q, "float32"), jnp.asarray(rows_k), jnp.asarray(rows_v),
        bias=jnp.asarray(bias, "float32")))

    for interpret in (True, None):
        got = fa.flash_decode_paged_attention(
            q, k_pool, v_pool, jnp.asarray(tables), key_bias=kb,
            lengths=jnp.asarray(live, "int32"), interpret=interpret)
        assert got.shape == (B, N, 1, D) and got.dtype == q.dtype
        got = np.asarray(got, "float32")
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, atol=tol)


def test_paged_kernel_refuses_a_pool_of_another_row():
    """Heads as a dim of the pool (the layout before PR 30) or a row of
    another width is an error, not a reinterpretation."""
    import importlib

    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    q = jnp.zeros((2, 2, 1, 16), "float32")
    tables = jnp.zeros((2, 3), "int32")
    for shape in [(7, 2, BLOCK, 16), (7, BLOCK, 16), (7, BLOCK, 64)]:
        pool = jnp.zeros(shape, "float32")
        with pytest.raises(ValueError):
            fa.flash_decode_paged_attention(q, pool, pool, tables)


def test_pool_row_round_trip_puts_each_head_back():
    """The model side of the row: projections [S, T, hidden] are written
    as they are (``_apply_kv_cache``), and what a window or a verify
    reads back through the table (``_gather_heads``) holds head h's
    values in head h, position by position."""
    from paddle_tpu.models import bert as _bert

    cfg = gpt.GPTConfig.tiny()
    heads, hidden = cfg.num_heads, cfg.hidden_size
    d_head = hidden // heads
    S, T, MB, NB = 2, 6, 3, 8
    rs = np.random.RandomState(4)
    k_new = rs.randn(S, T, hidden).astype("f4")
    v_new = rs.randn(S, T, hidden).astype("f4")
    tables = np.array([[5, 2, 6], [3, 1, 4]], "int64")
    pos = np.array([2, 0], "int64")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        kinds = gpt.cache_kinds(cfg)[:1]
        assert kinds[0][0].shape(NB, BLOCK) == [NB, 1, BLOCK, hidden]
        from paddle_tpu.models import cache_kinds
        ((pk, pv),) = cache_kinds.declare_pools(kinds, NB, BLOCK)
        kv = fluid.layers.data(name="k", shape=[T, hidden], dtype="float32")
        vv = fluid.layers.data(name="v", shape=[T, hidden], dtype="float32")
        tb = fluid.layers.data(name="tb", shape=[MB], dtype="int64")
        ps = fluid.layers.data(name="ps", shape=[], dtype="int64")
        k_upd, v_upd = _bert._apply_kv_cache(
            {"k": pk, "v": pv, "tables": tb, "pos": ps,
             "mode": "paged_step"}, kv, vv, cfg)
        rows = [_bert._gather_heads(p, tb, cfg) for p in (k_upd, v_upd)]
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    for p in (pk, pv):
        scope.set(p.name, np.zeros([NB, 1, BLOCK, hidden], "f4"))
    got_k, got_v = exe.run(
        main, feed={"k": k_new, "v": v_new, "tb": tables, "ps": pos},
        fetch_list=rows, scope=scope)
    assert got_k.shape == (S, heads, MB * BLOCK, d_head)
    for got, new in ((got_k, k_new), (got_v, v_new)):
        for s in range(S):
            for h in range(heads):
                np.testing.assert_array_equal(
                    got[s, h, pos[s]:pos[s] + T],
                    new[s, :, h * d_head:(h + 1) * d_head])
    # and the bytes lie in the pool as the projection left them
    pool = np.asarray(scope.get(pk.name))
    np.testing.assert_array_equal(pool[tables[1, 0], 0, 0], k_new[1, 0])


def test_greedy_generate_through_the_interpreted_paged_kernel():
    """Token-exact against the full-forward oracle with the T = 1 step
    through the Pallas kernel (interpreter) over the [1, hidden] rows."""
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0,
                             use_flash_attention=True)
    cfg.flash_interpret = True
    dense = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    with fluid.unique_name.guard():
        infer, startup, _n, logits = gpt.build_gpt_infer(dense, 10)
    infer.random_seed = startup.random_seed = 9
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
        want = gpt._reference_generate(exe, infer, logits, dense, [3, 7, 5],
                                       10, scope=scope)
        got = gpt.greedy_generate(exe, infer, logits, cfg, [3, 7, 5], 10,
                                  scope=scope)
    assert got == want and len(got) == 10


def test_tp_engine_runs_the_paged_kernel_on_its_shard_of_every_row():
    """Under a {"model": 2} mesh the pools split on their LAST dim (a
    shard holds its heads' lanes of every row, ``parallel/spmd.py``) and
    the kernel runs per shard on them (``nn_ops._per_shard``): the same
    tokens as one device through the dense branch."""
    outs = {}
    for tp, flash in ((1, False), (2, True)):
        cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0,
                                 use_flash_attention=flash)
        cfg.max_position_embeddings = 16
        cfg.flash_interpret = True
        with fluid.unique_name.guard():
            infer, startup, _n, _logits = gpt.build_gpt_infer(cfg, 16)
        infer.random_seed = startup.random_seed = 11
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.core.Scope()
        with fluid.executor.scope_guard(scope):
            exe.run(startup)
        engine = sdecode.DecodeEngine(
            cfg, scope=scope, slots=2, max_len=16, prefill_buckets=[16],
            param_program=infer, block_size=BLOCK, tp=tp).start()
        try:
            outs[tp] = [engine.generate(p, max_new_tokens=6).tokens(
                timeout=240) for p in ([3, 7, 5], [9, 1, 2, 4, 8])]
            if tp == 2:
                pool = scope.get(engine.session.pool_names()[0][0])
                assert tuple(pool.sharding.spec) == (None, None, None,
                                                     "model")
                assert pool.addressable_shards[0].data.shape == (
                    engine.session.pool_blocks, 1, BLOCK,
                    cfg.hidden_size // 2)
        finally:
            engine.stop()
    assert outs[2] == outs[1]


def test_kv_cache_paged_write_gather_ops():
    """The paged scatter/gather pair through arbitrary runtime tables:
    a permuted write lands each token at tables[s, pos//B] offset
    pos%B, and a gather materializes each slot's logical row through
    its table — including one pool block SHARED by two tables."""
    NB, H, B, D, S, MB = 7, 2, BLOCK, 3, 2, 3
    T = 6  # window longer than one block, not block-aligned at the end
    rs = np.random.RandomState(3)
    pool0 = rs.randn(NB, H, B, D).astype("f4")
    new = rs.randn(S, H, T, D).astype("f4")
    tables = np.array([[5, 2, 6], [3, 1, 4]], "int64")
    pos = np.array([[2], [0]], "int64")  # slot 0 starts mid-block

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cache = main.global_block().create_var(
            name="pp", shape=[NB, H, B, D], dtype="float32",
            persistable=True)
        nv = fluid.layers.data(name="nv", shape=[H, T, D],
                               dtype="float32")
        tb = fluid.layers.data(name="tb", shape=[MB], dtype="int64")
        ps = fluid.layers.data(name="ps", shape=[1], dtype="int64")
        out = fluid.layers.kv_cache_write_paged(cache, nv, tb, ps)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    scope.set("pp", pool0.copy())
    (got,) = exe.run(main, feed={"nv": new, "tb": tables, "ps": pos},
                     fetch_list=[out], scope=scope)
    want = pool0.copy()
    for s in range(S):
        for j in range(T):
            a = int(pos[s, 0]) + j
            want[tables[s, a // B], :, a % B, :] = new[s, :, j, :]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(scope.get("pp")), want)

    # gather through tables that SHARE pool block 2 between both slots
    gtab = np.array([[5, 2, 6], [2, 1, 4]], "int64")
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2):
        cache2 = main2.global_block().create_var(
            name="pp", shape=[NB, H, B, D], dtype="float32",
            persistable=True)
        tb2 = fluid.layers.data(name="tb", shape=[MB], dtype="int64")
        row = fluid.layers.kv_cache_gather_paged(cache2, tb2)
    (grow,) = exe.run(main2, feed={"tb": gtab}, fetch_list=[row],
                      scope=scope)
    assert grow.shape == (S, H, MB * B, D)
    for s in range(S):
        wrow = np.concatenate([want[gtab[s, b]] for b in range(MB)],
                              axis=1)
        np.testing.assert_array_equal(grow[s], wrow)


def test_kv_cache_block_copy_op_cow():
    """The COW primitive: Cache[dst] = Cache[src] per fed pair, with a
    src==dst pair degenerating to a no-op (callers pad with those to
    reuse one compiled pair count)."""
    NB, H, B, D = 5, 2, BLOCK, 3
    rs = np.random.RandomState(7)
    pool0 = rs.randn(NB, H, B, D).astype("f4")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cache = main.global_block().create_var(
            name="bc", shape=[NB, H, B, D], dtype="float32",
            persistable=True)
        src = fluid.layers.data(name="src", shape=[2], dtype="int64")
        dst = fluid.layers.data(name="dst", shape=[2], dtype="int64")
        out = fluid.layers.kv_cache_block_copy(cache, src, dst)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    scope.set("bc", pool0.copy())
    (got,) = exe.run(
        main, feed={"src": np.array([[3, 1]], "int64"),
                    "dst": np.array([[4, 1]], "int64")},
        fetch_list=[out], scope=scope)
    want = pool0.copy()
    want[4] = pool0[3]  # the COW duplicate; [1]->[1] is the no-op pad
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(scope.get("bc")), want)


# -- host-ledger units ------------------------------------------------------
def test_block_allocator_freelist_refcount_oom():
    al = sdecode.BlockAllocator(6)  # sink + 5
    assert al.free_blocks == 5 and al.shared_blocks == 0
    a = al.alloc(2)
    assert sorted(a) == [1, 2]  # low ids first
    assert al.alloc(4) is None  # all-or-nothing: 3 free < 4
    assert al.free_blocks == 3  # the failed alloc took nothing
    al.incref([a[0]])
    assert al.refs(a[0]) == 2 and al.shared_blocks == 1
    assert al.decref(a) == 1  # a[0] survives under the extra ref
    assert al.refs(a[0]) == 1 and al.free_blocks == 4
    assert al.decref([a[0]]) == 1
    assert al.free_blocks == 5
    with pytest.raises(ValueError):
        al.decref([a[0]])  # double free
    with pytest.raises(ValueError):
        al.incref([sdecode.BlockAllocator.SINK])  # sink is untouchable
    with pytest.raises(ValueError):
        al.decref([0])
    assert al.alloc(0) == []
    assert al.stats() == {"blocks": 6, "free": 5, "shared": 0}


def test_paged_prefix_index_lookup_publish_evict():
    """Zero-copy store semantics: publish pins the slot's own blocks by
    refcount, lookup increfs every matched block for the caller, and
    eviction under allocator pressure (need_free) only takes entries
    whose block the store ALONE references."""
    al = sdecode.BlockAllocator(10)
    ix = sdecode.PagedPrefixIndex(BLOCK, 3, al)
    p1 = list(range(10))  # blocks [0:4], [4:8]; tail never cached
    assert ix.lookup(p1) == ([], 0)
    owned = al.alloc(3)  # an admitted slot's table
    new = ix.publish(p1, owned)
    assert [e.block_idx for e in new] == owned[:2]
    assert al.refs(owned[0]) == 2  # slot ref + store pin
    # the slot retires: store pins keep both published blocks alive
    al.decref(owned)
    assert al.refs(owned[0]) == 1 and al.refs(owned[2]) == 0
    ent, toks = ix.lookup(p1[:9])  # 9 tokens -> both blocks usable
    assert toks == 8 and [e.block_idx for e in ent] == owned[:2]
    assert al.refs(owned[0]) == 2  # lookup increfed for the caller
    # full-block prompt caps at len-1: the last token is recomputed
    ent2, toks2 = ix.lookup(p1[:8])
    assert toks2 == 4 and len(ent2) == 1
    al.decref([e.block_idx for e in ent2])
    # need_free eviction skips blocks a live slot still shares
    free0 = al.free_blocks
    assert ix.evict_one(need_free=True) is False  # both blocks shared
    al.decref([e.block_idx for e in ent])  # "slot" drops its refs
    assert ix.evict_one(need_free=True) is True
    assert al.free_blocks == free0 + 1  # entry's decref freed its block
    # pin budget: publishing past max_blocks evicts LRU entries
    b2 = al.alloc(3)
    ix.publish(list(range(100, 112)), b2)
    assert len(ix) <= ix.max_blocks
    assert ix.evictions >= 2


def test_paged_prefix_index_refcount_blocks_eviction():
    """An eviction forced while an admission still holds a looked-up
    block must not free it under the slot: under allocator pressure
    (need_free) blocks a caller references are skipped, and a store
    whose every block is so held gives nothing back; the pin-budget
    eviction drops the ENTRY but the block lives until the holder's
    decref."""
    al = sdecode.BlockAllocator(8)
    ix = sdecode.PagedPrefixIndex(BLOCK, 2, al)
    pa = list(range(8)) + [0]
    owned = al.alloc(3)
    ix.publish(pa, owned)  # 2 blocks -> store at its budget
    al.decref(owned)       # the publishing slot retires
    held, toks = ix.lookup(pa)  # an in-flight admission's references
    assert toks == 8 and all(al.refs(e.block_idx) == 2 for e in held)
    # everything held: allocator pressure cannot take a block back
    assert ix.evict_one(need_free=True) is False
    assert ix.evictions == 0
    # publishing a new prefix at the budget drops the LRU entry, but the
    # held block is not freed (and so cannot be handed out and rewritten)
    free0 = al.free_blocks
    fresh = al.alloc(2)
    new = ix.publish(list(range(50, 54)) + [0], fresh)
    assert len(new) == 1 and ix.evictions == 1
    assert al.refs(held[0].block_idx) == 1  # the admission's own ref
    assert al.free_blocks == free0 - 2
    assert ix._entries.get(held[1].key) is held[1]  # newer entry kept
    # release ONE: exactly that block returns to the free list
    al.decref([held[0].block_idx])
    assert al.free_blocks == free0 - 1
    assert al.refs(held[1].block_idx) == 2
    al.decref([held[1].block_idx])


def test_paged_prefix_index_collision_verified_not_trusted(monkeypatch):
    """A hash collision (equal chain key, different tokens) must stop
    the chain at lookup AND at publish — the token tuples are compared,
    never the key alone."""
    monkeypatch.setattr(sdecode, "_block_hash", lambda prev, toks: 42)
    al = sdecode.BlockAllocator(8)
    ix = sdecode.PagedPrefixIndex(2, 4, al)
    pa = [1, 2, 9]
    pb = [3, 4, 9]  # different tokens, same (engineered) key
    assert len(ix.publish(pa, al.alloc(2))) == 1
    ent, toks = ix.lookup(pb)
    assert toks == 0 and ent == []  # collision -> miss fallthrough
    assert ix.publish(pb, al.alloc(2)) == []  # cannot chain past it
    ent, toks = ix.lookup(pa)
    assert toks == 2                # the real owner still hits
    al.decref([e.block_idx for e in ent])


def test_paged_prefix_index_verifies_chain_parent_not_just_tokens(
        monkeypatch):
    """Review regression: a key collision with EQUAL tokens but a
    different parent (prefixes A||X vs B||X under a tokens-only hash)
    must not splice A's X-block K/V into B's chain — the stored
    (prev, tokens) link is verified, never the tokens alone."""
    monkeypatch.setattr(sdecode, "_block_hash",
                        lambda prev, toks: ("t", toks))  # ignores prev
    al = sdecode.BlockAllocator(12)
    ix = sdecode.PagedPrefixIndex(2, 4, al)
    a, b, x = [1, 2], [3, 4], [7, 8]
    assert len(ix.publish(a + x + [0], al.alloc(3))) == 2  # chain A -> X
    # lookup B||X: block B misses; even a direct walk that reached the
    # X entry must reject it (its parent is A's key, not B's)
    ent, toks = ix.lookup(b + x + [0])
    assert toks == 0 and ent == []
    # publish B||X: B registers, but X's colliding entry (parent A)
    # stops the chain — B's X-block is NOT registered under A's entry
    slot_b = al.alloc(3)
    new = ix.publish(b + x + [0], slot_b)
    assert [e.block_idx for e in new] == slot_b[:1]
    # the genuine A||X chain still hits end to end
    ent, toks = ix.lookup(a + x + [0])
    assert toks == 4
    al.decref([e.block_idx for e in ent])


def test_spec_drafters():
    """Built-in drafters: trailing-n-gram continuation (longest n wins,
    most recent earlier match) and last-token repetition; both pad to
    k and never crash on short histories."""
    h = [5, 1, 2, 3, 9, 1, 2, 3]
    assert sdecode._ngram_draft(h, 3) == [9, 1, 2]  # trigram [1,2,3]
    assert sdecode._repeat_draft(h, 2) == [3, 3]
    assert len(sdecode._ngram_draft([7], 4)) == 4
    assert sdecode._ngram_draft([], 2) == [0, 0]
    with pytest.raises(ValueError):
        sdecode.DecodeEngine(gpt.GPTConfig.tiny(), spec_draft="nope",
                             block_size=BLOCK)


# -- engine end-to-end ------------------------------------------------------
@pytest.fixture(scope="module")
def pg():
    """One model + oracle and a speculative engine on it (k=4, prefix
    index 4 blocks, chunked prefill 8). The engine's drafter is
    swappable per-test via the dict."""
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = MAX_LEN + SPEC_K  # spec headroom
    with fluid.unique_name.guard():
        infer, startup, _names, logits = gpt.build_gpt_infer(cfg, MAX_LEN)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    draft = {"fn": sdecode._ngram_draft}
    engine = sdecode.DecodeEngine(
        cfg, scope=scope, slots=SLOTS, max_len=MAX_LEN,
        param_program=infer, block_size=BLOCK, spec_tokens=SPEC_K,
        prefill_chunk=8,
        prefix_cache_mb=4 * gpt.paged_block_bytes(cfg, BLOCK) / 2.0 ** 20,
        drafter=lambda h, k: draft["fn"](h, k),
    ).start()

    def oracle(prompt):
        return gpt._reference_generate(
            exe, infer, logits, cfg, prompt, MAX_LEN, scope=scope
        )

    yield {"cfg": cfg, "infer": infer, "exe": exe, "scope": scope,
           "logits": logits, "engine": engine, "oracle": oracle,
           "draft": draft}
    engine.stop()


def _simulate_spec(prompt, full, max_new, width, drafter):
    """Host mirror of one slot's paged spec schedule: prefill emits
    token 0, then each tick verifies [pending, drafts] and accepts the
    longest matching prefix. Returns (tokens, drafted, accepted) — the
    exact per-stream accounting the engine must report."""
    out = [full[len(prompt)]]
    drafted = accepted = 0
    while len(out) < max_new:
        win = [out[-1]] + drafter(prompt + out, width - 1)
        emitted = 0
        for j in range(width):
            tok = full[len(prompt) + len(out)]
            emitted += 1
            out.append(tok)
            if len(out) >= max_new:
                break
            if j < width - 1 and tok != win[j + 1]:
                break
        drafted += width - 1
        accepted += max(emitted - 1, 0)
    return out, drafted, accepted


def test_paged_spec_parity_every_split_point(pg):
    """Forced drafters hit every accept/reject split: a perfect drafter
    (full acceptance), corruption at each draft index c (acceptance
    stops exactly at c), and an alien drafter (zero acceptance). Token
    streams stay EXACT vs the full-forward oracle at every split, and
    the per-stream drafted/accepted tallies match the host schedule."""
    engine, oracle = pg["engine"], pg["oracle"]
    rs = np.random.RandomState(11)
    p = list(rs.randint(0, pg["cfg"].vocab_size, 5))
    full = oracle(p)
    max_new = 12

    def forced(corrupt):
        def fn(hist, k):
            d = list(full[len(hist):len(hist) + k])
            d += [0] * (k - len(d))
            if corrupt is not None and corrupt < len(d):
                d[corrupt] = (d[corrupt] + 1) % pg["cfg"].vocab_size
            return d
        return fn

    want = full[len(p):len(p) + max_new]
    for corrupt in (None, 0, 1, 2):
        pg["draft"]["fn"] = forced(corrupt)
        sim_toks, sim_d, sim_a = _simulate_spec(
            p, full, max_new, SPEC_K, forced(corrupt))
        assert sim_toks == want  # the mirror is itself exact
        s = engine.generate(p, max_new_tokens=max_new)
        assert s.tokens(timeout=120) == want, "corrupt=%r" % corrupt
        assert (s.spec_drafted, s.spec_accepted) == (sim_d, sim_a), \
            "corrupt=%r" % corrupt
        if corrupt is None:
            assert s.spec_accepted > 0
        if corrupt == 0:
            assert s.spec_accepted == 0
    pg["draft"]["fn"] = sdecode._ngram_draft
    st = engine.stats()
    assert st["spec_drafted"] > 0
    assert 0.0 <= st["spec_acceptance"] <= 1.0


def test_set_spec_width_runtime_toggle(pg):
    """set_spec_width flips an engine between its two compiled
    verify widths without a restart: width 1 runs token-exact with
    ZERO drafting (the drafter is never consulted), width k restores
    speculation, and uncompiled widths refuse."""
    engine, oracle = pg["engine"], pg["oracle"]
    rs = np.random.RandomState(7)
    p = list(rs.randint(0, pg["cfg"].vocab_size, 6))
    want = oracle(p)[6:][:8]

    def bomb(hist, k):  # width 1 must never draft
        raise AssertionError("drafter called at width 1")

    pg["draft"]["fn"] = bomb
    engine.set_spec_width(1)
    try:
        s = engine.generate(p, max_new_tokens=8)
        assert s.tokens(timeout=120) == want
        assert (s.spec_drafted, s.spec_accepted) == (0, 0)
    finally:
        engine.set_spec_width(SPEC_K)
        pg["draft"]["fn"] = sdecode._ngram_draft
    s2 = engine.generate(p, max_new_tokens=8)
    assert s2.tokens(timeout=120) == want
    assert s2.spec_drafted > 0  # speculation is back on
    for bad in (0, 2, SPEC_K + 1):
        with pytest.raises(ValueError):
            engine.set_spec_width(bad)


def test_paged_greedy_parity_and_prefix_hit(pg):
    """Greedy parity across prompt lengths through the spec engine
    (acceptance rate must never perturb tokens), then a re-submitted
    long prompt rides the ZERO-COPY prefix index: cached whole blocks,
    token-exact, no device copy programs in the session."""
    engine, oracle = pg["engine"], pg["oracle"]
    rs = np.random.RandomState(0)
    for n in (1, 3, 9, MAX_LEN - 6):
        p = list(rs.randint(0, pg["cfg"].vocab_size, n))
        want = oracle(p)[n:]
        got = engine.generate(p).tokens(timeout=120)
        assert got == want, "prompt len %d" % n
    p = list(rs.randint(0, pg["cfg"].vocab_size, 14))
    want = oracle(p)[14:][:4]
    s1 = engine.generate(p, max_new_tokens=4)
    assert s1.tokens(timeout=120) == want
    assert s1.cached_prefix_tokens == 0
    s2 = engine.generate(p, max_new_tokens=4)
    assert s2.tokens(timeout=120) == want
    assert s2.cached_prefix_tokens == 12  # 3 whole blocks of the 13 cap
    st = engine.stats()
    assert st["prefix_hits"] >= 1
    assert st["paged"]["block_size"] == BLOCK
    assert st["prefix_store"]["cached_blocks"] >= 1


def test_paged_chunked_resume_and_eviction(pg):
    """Chunked prefill (windows at block-aligned offsets), resume
    re-prefill, and prefix-store eviction under the 4-block pin budget
    all stay token-exact."""
    engine, oracle = pg["engine"], pg["oracle"]
    rs = np.random.RandomState(5)
    p = list(rs.randint(0, pg["cfg"].vocab_size, 13))  # 2 windows @ 8
    full = oracle(p)
    s = engine.generate(p, max_new_tokens=5)
    assert s.tokens(timeout=120) == full[13:18]
    assert s.admit_windows == 2
    # resume: the engine re-prefills prompt + suffix and continues
    sr = engine.generate(p, max_new_tokens=5,
                         resume_tokens=full[13:15])
    assert sr.tokens(timeout=120) == full[15:18]
    # churn distinct prompts through the 4-block store -> evictions;
    # the original prompt stays exact whatever survived
    ev0 = engine.pindex.evictions
    for seed in (31, 32, 33):
        q = list(np.random.RandomState(seed).randint(
            0, pg["cfg"].vocab_size, 14))
        engine.generate(q, max_new_tokens=2).tokens(timeout=120)
    assert engine.pindex.evictions > ev0
    s3 = engine.generate(p, max_new_tokens=5)
    assert s3.tokens(timeout=120) == full[13:18]


def test_paged_sampled_parity_vs_seeded_oracle(pg):
    """Seeded sampling through the spec verify path must reproduce the
    engine-free oracle's stream bit-for-bit: each consumed verify row is
    the sequential logits row, and one uniform per emitted token keeps
    the PR-13 resume contract (fast_forward_rng) intact."""
    from conftest import engine_free_oracle

    engine = pg["engine"]
    pg["draft"]["fn"] = sdecode._ngram_draft
    p = [2, 9, 4, 9, 4]
    knobs = dict(temperature=0.8, top_k=32, seed=123)
    want = engine_free_oracle(pg, p, 10, MAX_LEN, knobs)
    got = engine.generate(p, max_new_tokens=10, **knobs).tokens(timeout=120)
    assert got == want
    # and the sampled stream replays deterministically on the spec path
    assert engine.generate(p, max_new_tokens=10,
                           **knobs).tokens(timeout=120) == want


def test_paged_zero_steady_recompiles_and_gauges(pg):
    """Churn through the warmed engine (its strict gate armed at
    start): block-table admissions, spec verify ticks, prefix hits and
    retirements cause ZERO steady-state compiles (tables/positions are
    runtime data), and the pool gauges are live."""
    engine = pg["engine"]
    pg["draft"]["fn"] = sdecode._ngram_draft
    c0 = profiler.get_counters()
    rs = np.random.RandomState(8)
    streams = [
        engine.generate(
            list(rs.randint(0, pg["cfg"].vocab_size, 1 + i % 7)),
            max_new_tokens=2 + i % 5,
        )
        for i in range(2 * SLOTS)
    ]
    for s in streams:
        s.tokens(timeout=120)
    c1 = profiler.get_counters()
    assert c1.get("serving_steady_recompiles", 0) == c0.get(
        "serving_steady_recompiles", 0
    )
    assert c1.get("xla_compiles", 0) == c0.get("xla_compiles", 0)
    gauges = obs_registry.gauge_values()
    assert "decode_blocks_free" in gauges
    assert "decode_blocks_shared" in gauges
    assert "decode_spec_acceptance" in gauges
    st = engine.stats()
    assert st["paged"]["free"] + (len(engine._active)
                                  + len(engine._prefilling)) >= 0
    assert st["paged"]["blocks"] == engine.session.pool_blocks


def test_engine_copies_a_shared_block_before_it_writes_it():
    """Copy-on-write through the engine: a block the slot is about to
    write that someone else also references (here a reference taken by
    hand, standing for an index entry or a second slot) is first copied
    into a fresh block and the table entry swapped, so the other holder's
    bytes never change and the stream stays token-exact."""
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = MAX_LEN
    with fluid.unique_name.guard():
        infer, startup, _names, logits = gpt.build_gpt_infer(cfg, MAX_LEN)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    engine = sdecode.DecodeEngine(
        cfg, scope=scope, slots=1, max_len=MAX_LEN, param_program=infer,
        block_size=BLOCK,
    ).start(loop=False)
    try:
        p = [3, 1, 4, 1, 5, 9]  # 6 tokens: the second block is half full
        want = gpt._reference_generate(
            exe, infer, logits, cfg, p, MAX_LEN, scope=scope)[len(p):]
        s = engine.submit(p, max_new_tokens=5)
        engine._tick()  # admission, and the first step into block 1
        shared = engine._slot_blocks[0][1]
        engine.allocator.incref([shared])
        names = [n for layer in engine.session.pool_names() for n in layer]
        before = [np.asarray(scope.get(n))[shared].copy() for n in names]
        engine._tick()
        assert engine._slot_blocks[0][1] != shared
        assert engine.allocator.refs(shared) == 1  # ours alone now
        for n, b in zip(names, before):
            np.testing.assert_array_equal(np.asarray(scope.get(n))[shared], b)
        while not s.done:
            engine._tick()
        assert s.tokens(timeout=1) == want[:5]
        engine.allocator.decref([shared])
        assert engine.allocator.free_blocks == engine.session.pool_blocks - 1
    finally:
        engine.stop()


def test_paged_pool_oom_sheds_not_wedges():
    """A pool sized for ONE full-length stream: the first admission
    completes exactly; a concurrent second admission sheds with
    ServerOverloadedError (retryable) instead of wedging the loop, and
    the shed slot's blocks return to the free list."""
    cfg = gpt.GPTConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0)
    cfg.max_position_embeddings = MAX_LEN
    with fluid.unique_name.guard():
        infer, startup, _names, logits = gpt.build_gpt_infer(cfg, MAX_LEN)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    engine = sdecode.DecodeEngine(
        cfg, scope=scope, slots=2, max_len=MAX_LEN,
        param_program=infer, block_size=BLOCK,
        pool_blocks=1 + MAX_LEN // BLOCK,  # sink + one stream's worth
    ).start()
    try:
        p = [3, 1, 4, 1, 5, 9, 2, 6, 5]  # 9 tokens -> 3 blocks at admit
        want = gpt._reference_generate(
            exe, infer, logits, cfg, p, MAX_LEN, scope=scope
        )[len(p):]
        s1 = engine.submit(p, max_new_tokens=MAX_LEN - len(p))
        s2 = engine.submit(list(reversed(p)),
                           max_new_tokens=MAX_LEN - len(p))
        with pytest.raises(ServerOverloadedError):
            s2.tokens(timeout=120)
        assert s1.tokens(timeout=120) == want
        st = engine.stats()
        assert st["oom_sheds"] >= 1
        assert st["paged"]["free"] == engine.session.pool_blocks - 1
    finally:
        engine.stop()
