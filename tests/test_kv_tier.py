"""Fleet KV tier (ISSUE 17): host block store semantics, wire codec
chain verification, evict→spill→re-admit token-exactness with refcount
pinning across the async D2H, router cache-affinity scoring, and the
role-split fleet plumbing (peers file, role fill order).

The closed-loop acceptance (3-replica affinity TTFT bar, spill-churn
crossover, strict gate) lives in ``tools/fleet_probe.py --fast`` via
``tests/test_fleet.py::test_fleet_probe_fast_acceptance``; these are
the fast in-process seams.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.observability import registry as obs_registry
from paddle_tpu.serving import kv_tier
from paddle_tpu.serving.kv_tier import (
    HostBlockStore, SpillWorker, block_hash, chain_keys,
    decode_entries, encode_entries,
)

BLOCK = 8
SPEC = {"seed": 5, "vocab_size": 50, "hidden_size": 16, "num_layers": 1,
        "num_heads": 2, "intermediate_size": 32, "max_len": 32,
        "slots": 4, "prefill_buckets": [8, 32]}
# one block of a GPT pool as it lies: [1, block, hidden], a token's heads
# side by side (``DecodeEngine.block_row_shape()``)
ROW = [1, BLOCK, SPEC["hidden_size"]]


def _payload(seed, layers=1):
    rs = np.random.RandomState(seed)
    return [(rs.randn(*ROW).astype(np.float32),
             rs.randn(*ROW).astype(np.float32)) for _ in range(layers)]


def _chain(n, seed=0):
    """n linked (key, prev, tokens, payload) blocks."""
    rs = np.random.RandomState(seed)
    out, prev = [], 0
    for i in range(n):
        toks = tuple(int(t) for t in rs.randint(0, 50, BLOCK))
        key = block_hash(prev, toks)
        out.append((key, prev, toks, _payload(100 + i)))
        prev = key
    return out


def _count(name):
    return obs_registry.counter(name).value()


# ---------------------------------------------------------------------------
# chain digests
# ---------------------------------------------------------------------------
def test_block_hash_and_chain_keys():
    toks = (1, 2, 3, 4, 5, 6, 7, 8)
    k1 = block_hash(0, toks)
    assert k1 == block_hash(0, list(toks))  # container-insensitive
    assert k1 != block_hash(0, toks[:-1] + (9,))
    assert block_hash(k1, toks) != k1  # chained, not positional

    prompt = list(range(30))
    keys = chain_keys(prompt, BLOCK)
    assert len(keys) == 3  # 30 tokens -> 3 FULL blocks
    assert keys[0] == block_hash(0, tuple(prompt[:8]))
    assert keys[1] == block_hash(keys[0], tuple(prompt[8:16]))
    assert chain_keys(prompt[:7], BLOCK) == []


# ---------------------------------------------------------------------------
# HostBlockStore (satellite: unit coverage)
# ---------------------------------------------------------------------------
def test_host_store_round_trip_bit_exact():
    store = HostBlockStore(1 << 20)
    (key, prev, toks, payload), = _chain(1)
    assert store.put(key, prev, toks, payload)
    got = store.get(key, prev, toks)
    assert got is not None
    for (k0, v0), (k1, v1) in zip(payload, got.payload):
        assert np.array_equal(k0, k1) and np.array_equal(v0, v1)
    # chain-verified: the same key under a different claimed link misses
    assert store.get(key, "bogus-prev", toks) is None
    assert store.get(key, prev, toks[:-1] + (99,)) is None
    assert store.get("missing", prev, toks) is None


def test_host_store_lru_cap_and_eviction_counter():
    blocks = _chain(4)
    nbytes = sum(k.nbytes + v.nbytes for k, v in blocks[0][3])
    store = HostBlockStore(3 * nbytes)  # room for exactly 3
    ev0 = _count("kv_tier_host_evictions")
    for key, prev, toks, payload in blocks[:3]:
        assert store.put(key, prev, toks, payload)
    assert len(store) == 3 and store.bytes_used == 3 * nbytes
    # touch the oldest so the SECOND-oldest becomes the LRU victim
    store.get(blocks[0][0], blocks[0][1], blocks[0][2])
    key, prev, toks, payload = blocks[3]
    assert store.put(key, prev, toks, payload)
    assert len(store) == 3
    assert store.get(blocks[1][0], blocks[1][1], blocks[1][2]) is None
    assert store.get(blocks[0][0], blocks[0][1], blocks[0][2]) is not None
    assert _count("kv_tier_host_evictions") - ev0 == 1


def test_host_store_counters_match_traffic():
    blocks = _chain(3, seed=7)
    nbytes = sum(k.nbytes + v.nbytes for k, v in blocks[0][3])
    store = HostBlockStore(1 << 20)
    s0, d0 = _count("kv_tier_spills"), _count("kv_tier_bytes_d2h")
    r0, h0 = _count("kv_tier_readmits"), _count("kv_tier_bytes_h2d")
    for key, prev, toks, payload in blocks[:2]:
        assert store.put(key, prev, toks, payload)
    # idempotent re-put counts nothing
    assert store.put(blocks[0][0], blocks[0][1], blocks[0][2],
                     blocks[0][3])
    # a PULLED block (tally=False) lands without spill accounting
    assert store.put(blocks[2][0], blocks[2][1], blocks[2][2],
                     blocks[2][3], tally=False)
    assert _count("kv_tier_spills") - s0 == 2
    assert _count("kv_tier_bytes_d2h") - d0 == 2 * nbytes
    e = store.get(blocks[0][0], blocks[0][1], blocks[0][2])
    store.note_readmit(e)
    store.note_readmit(e)
    assert _count("kv_tier_readmits") - r0 == 2
    assert _count("kv_tier_bytes_h2d") - h0 == 2 * nbytes
    st = store.stats()
    assert st["host_blocks"] == 3
    assert st["host_bytes"] == 3 * nbytes


def test_host_store_refuses_oversized_block():
    blocks = _chain(1)
    key, prev, toks, payload = blocks[0]
    nbytes = sum(k.nbytes + v.nbytes for k, v in payload)
    store = HostBlockStore(nbytes - 1)
    assert not store.put(key, prev, toks, payload)
    assert len(store) == 0 and store.bytes_used == 0


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------
def test_wire_codec_round_trip_bit_exact():
    chain = _chain(3, seed=11)
    blob = encode_entries(chain)
    json.dumps(blob)  # must be JSON-serializable as-is
    back = decode_entries(blob, ROW)
    assert len(back) == 3
    for (key, prev, toks, payload), (k2, p2, t2, pl2) in zip(chain, back):
        assert k2 == key and p2 == prev and t2 == toks
        for (k0, v0), (k1, v1) in zip(payload, pl2):
            assert np.array_equal(k0, k1) and np.array_equal(v0, v1)


def test_wire_codec_rejects_broken_chain():
    chain = _chain(3, seed=13)
    blob = encode_entries(chain)
    # corrupt the MIDDLE entry's tokens: its digest no longer matches,
    # so decode must keep only the verified prefix (1 block), never the
    # poisoned tail
    blob[1]["tokens"] = [0] * BLOCK
    back = decode_entries(blob, ROW)
    assert len(back) == 1 and back[0][0] == chain[0][0]
    # an empty blob decodes to nothing rather than raising
    assert decode_entries([], ROW) == []


# ---------------------------------------------------------------------------
# spill worker
# ---------------------------------------------------------------------------
def test_spill_worker_batches_and_survives_errors():
    done = []
    evt = threading.Event()
    calls = []

    def batch(jobs):
        calls.append(list(jobs))
        if len(calls) == 1:
            raise RuntimeError("first batch dies")
        done.extend(jobs)
        evt.set()

    w = SpillWorker(batch)
    try:
        w.submit("a")
        # wait out batch 1 (the failing one), then queue two more
        deadline = time.monotonic() + 5
        while not calls and time.monotonic() < deadline:
            time.sleep(0.01)
        w.submit("b")
        w.submit("c")
        assert evt.wait(5)
        assert done == ["b", "c"]  # batched together, error contained
        assert w.drain(2.0)
        assert w.pending == 0
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# eviction pins the block across the async D2H
# ---------------------------------------------------------------------------
def test_index_evict_pins_block_until_spill_completes():
    from paddle_tpu.serving.decode import BlockAllocator, PagedPrefixIndex

    alloc = BlockAllocator(8)
    pinned = []

    def on_evict(victim):
        # the engine hook: take the spill pin BEFORE the index decref
        alloc.incref([victim.block_idx])
        pinned.append(victim.block_idx)

    idx = PagedPrefixIndex(BLOCK, max_blocks=1, allocator=alloc,
                           on_evict=on_evict)
    prompt = list(range(BLOCK))
    (blk,) = alloc.alloc(1)
    idx.publish(prompt, [blk])           # index holds its own ref
    alloc.decref([blk])                  # drop the "slot" ref
    assert alloc.refs(blk) == 1          # index is the only holder
    assert idx.evict_one()
    # evicted from the index, but the spill pin keeps it alive: the
    # allocator must NOT re-issue the block while the worker reads it
    assert alloc.refs(blk) == 1
    got = alloc.alloc(6)                 # everything but SINK + the pin
    assert got is not None and blk not in got
    assert alloc.alloc(1) is None        # pool exhausted except the pin
    alloc.decref(got)
    # the loop thread's drain: dropping the pin actually frees it
    assert pinned == [blk]
    alloc.decref([blk])
    got = alloc.alloc(7)                 # SINK stays pinned
    assert got is not None and blk in got


# ---------------------------------------------------------------------------
# engine: evict -> spill -> re-admit, token-exact, counters match
# ---------------------------------------------------------------------------
def _engine(**flag_over):
    from paddle_tpu.serving.replica import build_gpt_decode_engine

    flags = {"FLAGS_decode_prefix_cache_mb": 4.0,
             "FLAGS_decode_block_size": BLOCK,
             "FLAGS_kv_tier_host_mb": 0.0}
    flags.update(flag_over)
    fluid.set_flags(flags)
    return build_gpt_decode_engine(SPEC).start()


def test_engine_spill_readmit_token_exact_and_counters():
    from paddle_tpu.models import gpt as _gpt

    eng = _engine(FLAGS_kv_tier_host_mb=4.0)
    oracle = _engine()  # same seeded spec, no tier
    try:
        assert eng.host_store is not None
        eng.pindex.max_blocks = 1  # squeeze: every chain spills
        block_bytes = _gpt.paged_block_bytes(eng.session.cfg, BLOCK)
        s0, d0 = _count("kv_tier_spills"), _count("kv_tier_bytes_d2h")
        r0, h0 = _count("kv_tier_readmits"), _count("kv_tier_bytes_h2d")
        rs = np.random.RandomState(3)
        shared = [int(t) for t in rs.randint(0, 50, 2 * BLOCK + 1)]
        for i in range(4):
            prompt = shared + [i]
            a = eng.generate(prompt, max_new_tokens=3).tokens(timeout=60)
            b = oracle.generate(prompt,
                                max_new_tokens=3).tokens(timeout=60)
            assert a == b, (i, a, b)
        st = eng.stats()["kv_tier"]
        assert st["spills"] >= 1 and st["readmits"] >= 1
        eng._spill_worker.drain(2.0)
        spills = _count("kv_tier_spills") - s0
        readmits = _count("kv_tier_readmits") - r0
        assert spills >= 1 and readmits >= 1
        # byte counters are exact multiples of the block payload size
        assert _count("kv_tier_bytes_d2h") - d0 == spills * block_bytes
        assert _count("kv_tier_bytes_h2d") - h0 == readmits * block_bytes
        assert st["readmit_tokens"] == st["readmits"] * BLOCK
    finally:
        eng.stop()
        oracle.stop()


def test_engine_export_offer_cross_engine_token_exact():
    """The disaggregated-prefill seam, in-process: a warm engine
    exports its chain, the wire codec round-trips it, a COLD engine
    offers it into its host tier and serves the prompt token-exactly
    through the standard re-admission path."""
    warm = _engine(FLAGS_kv_tier_host_mb=4.0)
    cold = _engine(FLAGS_kv_tier_host_mb=4.0)
    try:
        rs = np.random.RandomState(9)
        prefix = [int(t) for t in rs.randint(0, 50, 2 * BLOCK)]
        prompt = prefix + [1, 2]
        expect = warm.generate(prompt, max_new_tokens=3).tokens(timeout=60)
        entries = warm.request_export(prefix, timeout=5.0)
        assert len(entries) == 2
        # what a replica advertises beside its blocks is what it sends
        assert warm.block_row_shape() == cold.block_row_shape() == ROW
        assert {a.shape for _k, _p, _t, payload in entries
                for kv in payload for a in kv} == {tuple(ROW)}
        blob = encode_entries(entries)
        back = decode_entries(blob, ROW)
        assert cold.offer_blocks(back) == 2
        assert cold.estimate_cached_tokens(prompt) == 2 * BLOCK
        got = cold.generate(prompt, max_new_tokens=3).tokens(timeout=60)
        assert got == expect
        assert cold.stats()["kv_tier"]["readmits"] >= 2
    finally:
        warm.stop()
        cold.stop()


# ---------------------------------------------------------------------------
# router affinity scoring
# ---------------------------------------------------------------------------
def test_router_affinity_scores_stale_and_misses():
    from paddle_tpu.serving.router import Router

    r = Router(port=0)
    r.add_backend(1, "127.0.0.1", 1111, ready=True)
    r.add_backend(2, "127.0.0.1", 2222, ready=True)
    prompt = list(range(5 * BLOCK))
    keys = chain_keys(prompt, BLOCK)
    now = time.monotonic()
    with r._lock:
        b1, b2 = r._backends["1"], r._backends["2"]
        b1.prefix_heads = frozenset([keys[1]])
        b1.advert_block = BLOCK
        b1.advert_t = now
        b2.prefix_heads = frozenset([keys[3]])
        b2.advert_block = BLOCK
        b2.advert_t = now
    # deepest advertised chain head wins: b2 knows 4 blocks, b1 only 2
    pick = r._pick(prompt_ids=prompt)
    assert pick.id == "2" and pick.affinity_score == 4 * BLOCK
    h0 = _count("router_affinity_hits")
    # a stale advert scores zero: the pick falls back to least-inflight
    stale0 = _count("router_affinity_stale")
    with r._lock:
        b2.advert_t = now - 1e4
    pick = r._pick(prompt_ids=prompt)
    assert pick.id == "1"
    assert _count("router_affinity_stale") > stale0
    # no advert anywhere -> miss counter, least-inflight fallback
    m0 = _count("router_affinity_misses")
    with r._lock:
        b1.prefix_heads = frozenset()
        b2.prefix_heads = frozenset()
        b1.inflight = 3
    pick = r._pick(prompt_ids=prompt)
    assert pick.id == "2"
    assert _count("router_affinity_misses") > m0
    assert _count("router_affinity_hits") > h0  # from the first pick
    # /backends debuggability rows (satellite: operator surface)
    d = b1.as_dict()
    for key in ("role", "prefix_heads", "prefix_head_sample",
                "advert_block", "advert_age_s", "affinity_score"):
        assert key in d


# ---------------------------------------------------------------------------
# gateway role + fleet role/peers plumbing
# ---------------------------------------------------------------------------
def test_gateway_rejects_unknown_role():
    from paddle_tpu.serving.gateway import Gateway

    with pytest.raises(ValueError):
        Gateway(object(), port=0, role="prefll")


def test_fleet_role_fill_order_and_peers_file(tmp_path):
    from paddle_tpu.serving.fleet import FleetController, _Replica

    model = tmp_path / "model"
    model.mkdir()
    ctrl = FleetController(
        model_dir=str(model), workdir=str(tmp_path / "work"),
        replicas=3, roles={"prefill": 1, "decode": 2}, autoscale=False,
    )
    with pytest.raises(ValueError):
        FleetController(model_dir=str(model),
                        workdir=str(tmp_path / "w2"),
                        roles={"prefil": 1})

    class _Proc:
        pid = 1234

        def poll(self):
            return None

    def fake(rid, role, state="ready", port=None):
        r = _Replica(rid, 1, str(model), _Proc(), "", "", "", role=role)
        r.state = state
        if port:
            r.endpoint = {"gateway_port": port}
        return r

    with ctrl._lock:
        # empty pool: the prefill slot fills first
        assert ctrl._role_for_next() == "prefill"
        ctrl._replicas[0] = fake(0, "prefill", port=7001)
        assert ctrl._role_for_next() == "decode"
        ctrl._replicas[1] = fake(1, "decode")
        ctrl._replicas[2] = fake(2, "decode")
        # declared counts met: extras stay decode under a role spec
        assert ctrl._role_for_next() == "decode"
        # the prefill replica dying reopens its slot first
        ctrl._replicas[0].state = "exited"
        assert ctrl._role_for_next() == "prefill"
        ctrl._replicas[0].state = "ready"
        assert fake(0, "prefill").info()["role"] == "prefill"
        ctrl._update_peers_locked()
    doc = json.loads(open(ctrl._peers_file).read())
    assert doc["peers"] == [{"id": 0, "host": ctrl.host, "port": 7001}]
    assert kv_tier.read_peers(ctrl._peers_file) == doc["peers"]
    # a roleless controller never steers spawns
    plain = FleetController(model_dir=str(model),
                            workdir=str(tmp_path / "w3"), replicas=2,
                            autoscale=False)
    with plain._lock:
        assert plain._role_for_next() == "mixed"
    assert kv_tier.read_peers(str(tmp_path / "nope.json")) == []


# ---------------------------------------------------------------------------
# fleet_report roll-up (satellite: prefix-cache effectiveness)
# ---------------------------------------------------------------------------
def test_prefix_cache_rollup():
    from paddle_tpu.observability.aggregate import _prefix_cache_rollup

    summaries = {
        "0": {"counters": {
            "decode_prefix_hits": 8, "decode_prefix_misses": 2,
            "decode_prefix_cached_tokens": 160,
            "decode_prompt_tokens": 400,
            "kv_tier_spills": 3, "kv_tier_readmits": 2,
            "kv_tier_bytes_d2h": 3000, "kv_tier_bytes_h2d": 2000,
        }},
        "1": {"counters": {
            "decode_prefix_hits": 2, "decode_prefix_misses": 8,
            "decode_prefix_cached_tokens": 40,
            "decode_prompt_tokens": 100,
        }},
    }
    roll = _prefix_cache_rollup(summaries)
    assert roll["per_replica"]["0"]["hit_rate"] == 0.8
    assert roll["per_replica"]["1"]["hit_rate"] == 0.2
    assert roll["fleet"]["hits"] == 10 and roll["fleet"]["misses"] == 10
    assert roll["fleet"]["hit_rate"] == 0.5
    assert roll["fleet"]["cached_token_fraction"] == 0.4  # 200/500
    assert roll["fleet"]["bytes_d2h"] == 3000
    assert roll["fleet"]["bytes_h2d"] == 2000
    empty = _prefix_cache_rollup({})
    assert empty["fleet"]["hit_rate"] is None
