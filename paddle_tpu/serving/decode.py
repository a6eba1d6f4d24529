"""Autoregressive decode runtime: a paged KV cache + continuous batching.

The serving stack's generation path. `InferenceServer` batches whole
forwards; a GPT completion served that way recomputes the full
[1, max_len] forward for every emitted token — O(T^2) model forwards at
batch 1. This module replaces that with the production decode shape.

  cache    ONE pool per layer of ``block_size``-token blocks
           ([blocks, r0, block, r1] persistable scope vars, a token's
           row [r0, r1] what the model's ``cache_kinds`` says: GPT's
           keys of all heads side by side, [1, hidden]; device-resident
           between steps), addressed through per-slot
           BLOCK TABLES that ride every program as fed data. A slot holds
           ceil(len/block) blocks, not a max_len row; ``BlockAllocator``
           hands them out by refcount.
  sink     block 0 is never handed out: idle and prefilling slots feed an
           all-sink table, so the fused step's unconditional
           scatter-writes can never touch a live block.
  window   (one compiled program per PROMPT bucket) a prompt window runs
           one causal forward at a FED offset and writes its K/V through
           the slot's table. A whole prompt is a window at offset 0;
           ``FLAGS_decode_prefill_chunk`` caps the tokens a tick may
           prefill, so a long prompt admits as windows BETWEEN steps.
  step     (ONE compiled program a width) every tick runs a single fused
           step over ALL slots: each active slot contributes its pending
           token (plus a k-1 draft under ``decode_spec_tokens`` = k, ONE
           batched verify) against its table, masked by its own length.
           The program ends in the greedy pick (``step_tail``): a tick
           fetches [slots, width] ids, the logits stay on the device for
           the streams that sample.
  prefix   ``PagedPrefixIndex`` maps hash-chained prompt-token blocks to
           the pool blocks a finished prefill already wrote: ZERO-copy.
           A hit puts the block into the admitted slot's table and
           increfs it; eviction is a decref, LRU under
           ``FLAGS_decode_prefix_cache_mb``. Cached K/V are the same
           projections the full forward computes, so hit and miss stay
           token-exact vs the oracle.

Tables, offsets and lengths are runtime data: admission, retirement,
sharing and chunking change no compiled shape, so a churned request mix
holds the strict-compile gate at zero steady-state recompiles. Decode is
bandwidth-bound (every token re-reads the weights plus the cache), which
is why batching all slots into one step is the throughput lever.

Layering: ``DecodeSession`` is the synchronous core (programs, pool init,
``paged_window`` / ``paged_step`` / ``block_copy``) — ``gpt.greedy_generate``
drives a 1-slot session inline; ``DecodeEngine`` owns the
continuous-batching loop (admission queue, allocator, prefix index,
chunked-prefill scheduler, streaming) and is what
``InferenceServer.generate()`` fronts.
"""

from __future__ import annotations

import copy
import queue
import re
import threading
import time
from collections import deque

import numpy as np

import paddle_tpu.fluid as fluid

from ..fluid import flags as _flags
from ..fluid import profiler as _profiler
from ..models import cache_kinds as _cache_kinds
from ..models import gpt as _gpt
from ..observability import exporter as _obs_exporter
from ..observability import registry as _obs_registry
from ..observability import trace as _trace
from ..observability import xla_stats as _xla_stats
from . import kv_tier as _kv_tier
from .batcher import ServerOverloadedError, ServingError

__all__ = [
    "DecodeSession",
    "DecodeEngine",
    "GenerationStream",
    "fast_forward_rng",
    "prefill_ladder",
    "sample_token",
    "session_for_generate",
    "step_tail",
]


def _flag(name, override):
    return override if override is not None else _flags.get_flag(name)


def _block_size(override):
    """``block_size`` (else ``FLAGS_decode_block_size``): tokens a KV
    block."""
    n = int(_flag("decode_block_size", override))
    if n < 1:
        raise ValueError(
            "block_size (FLAGS_decode_block_size) is the tokens a KV block "
            "holds and must be >= 1, got %d" % n)
    return n


def _require(model, mode):
    """A served model's module lists the modes that are not built for its
    cache (``UNSUPPORTED``: mode -> what it is)."""
    what = getattr(model, "UNSUPPORTED", {}).get(mode)
    if what is not None:
        raise NotImplementedError(
            "%s does not build %s" % (model.__name__, what))


def prefill_ladder(max_len, buckets=None):
    """Ascending prompt-length buckets, each a compiled prefill shape.
    ``buckets``: explicit list/CSV (``FLAGS_decode_prefill_buckets``), or
    None for the default powers-of-two ladder capped by (and always
    including) ``max_len`` — mirroring the batch ladder in buckets.py."""
    if isinstance(buckets, str):
        buckets = [int(b) for b in buckets.split(",") if b.strip()]
    if buckets:
        out = sorted(set(int(b) for b in buckets))
        if out[0] < 1:
            raise ValueError("prefill buckets must be positive: %r"
                             % (buckets,))
        kept = [b for b in out if b <= max_len]
        if len(kept) != len(out):
            import warnings

            # dropped, not fatal: FLAGS_decode_prefill_buckets may be
            # shared across engines with different max_len — but an
            # operator whose whole ladder exceeded max_len should hear
            # that every prompt will now pad to the full-length program
            warnings.warn(
                "prefill buckets %r exceed max_len %d and were dropped"
                "%s" % (
                    [b for b in out if b > max_len], max_len,
                    "; every prompt now pads to the full-length program"
                    if not kept else "",
                ), stacklevel=2)
        out = kept
        if not out or out[-1] != max_len:
            out.append(int(max_len))
        return out
    out = []
    b = 8
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(int(max_len))
    return out


# ---------------------------------------------------------------------------
# the pool's host side: block allocator + zero-copy prefix index
# ---------------------------------------------------------------------------


# The chain digest is shared fleet-wide now — the router's affinity
# scorer and the host-spill store must compute the exact keys this
# module publishes, so the one definition lives in kv_tier. Still a
# module-level hook here so tests can inject colliding functions; the
# index never trusts the key alone — every match re-compares the stored
# (prev, tokens) link and falls through to the full-prefill path on
# mismatch.
_block_hash = _kv_tier.block_hash


class _PrefixEntry(object):
    __slots__ = ("key", "prev", "tokens", "block_idx", "refs")

    def __init__(self, key, prev, tokens, block_idx):
        self.key = key
        self.prev = prev
        self.tokens = tokens
        self.block_idx = block_idx
        self.refs = 0


class BlockAllocator(object):
    """Host free-list + refcount ledger over the paged pool's physical
    blocks. Block 0 is the reserved SINK (idle / prefilling slots park
    their tables on it so the fused step's unconditional scatter-writes
    never touch a live block) and is never handed out. Sharing is a
    refcount: a prefix-store entry and any number of admitted slots may
    reference one block; whoever drops the last reference returns it to
    the free list — eviction and retirement are both just ``decref``.

    Single-mutator discipline: only the engine's loop thread
    allocates/increfs/decrefs."""

    SINK = 0

    def __init__(self, blocks):
        if blocks < 2:
            raise ValueError(
                "paged pool needs >= 2 blocks (sink + 1), got %d" % blocks
            )
        self.blocks = int(blocks)
        self._free = list(range(self.blocks - 1, 0, -1))  # pop() -> low ids
        self._refs = [0] * self.blocks
        self._refs[self.SINK] = 1  # permanently pinned

    def alloc(self, n):
        """Take ``n`` fresh blocks (refcount 1 each) or None if the free
        list can't cover all of them — all-or-nothing so a half-admitted
        slot never holds partial tables."""
        if n < 0:
            raise ValueError("alloc(%d)" % n)
        if n == 0:
            return []
        if len(self._free) < n:
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        return out

    def incref(self, block_ids):
        for b in block_ids:
            if not 0 < b < self.blocks or self._refs[b] <= 0:
                raise ValueError("incref on dead/sink block %d" % b)
            self._refs[b] += 1

    def decref(self, block_ids):
        """Drop one reference per id; blocks hitting zero return to the
        free list. Returns the number actually freed."""
        freed = 0
        for b in block_ids:
            if not 0 < b < self.blocks or self._refs[b] <= 0:
                raise ValueError("decref on dead/sink block %d" % b)
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self._free.append(b)
                freed += 1
        return freed

    def refs(self, block_id):
        return self._refs[block_id]

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def shared_blocks(self):
        return sum(1 for r in self._refs[1:] if r > 1)

    def stats(self):
        return {
            "blocks": self.blocks,
            "free": self.free_blocks,
            "shared": self.shared_blocks,
        }


class PagedPrefixIndex(object):
    """Hash-chain prefix index over the pool, ZERO-copy: entries point
    straight at pool blocks (the slot's own finished-prefill blocks at
    publish time), held alive by one allocator reference each.
    A hit extends the admitted slot's table with the entry's block and
    increfs it; no device copy moves in either direction. Eviction is a
    refcount decrement — a block still referenced by live slots survives
    until the last slot retires.

    Single-mutator discipline: the engine's loop thread is the only
    caller of ``lookup``/``publish``/``evict_one``.

    ``max_blocks`` caps how many pool blocks the store itself may pin
    (``FLAGS_decode_prefix_cache_mb``).

    ``on_evict`` is the host-spill seam (kv_tier): called with the
    victim entry BEFORE the index drops its reference, while the block's
    bytes are still live — the engine's hook pins the block and hands it
    to the spill worker. Must not mutate the index."""

    def __init__(self, block, max_blocks, allocator, on_evict=None):
        if block < 1 or max_blocks < 1:
            raise ValueError(
                "need block >= 1 and max_blocks >= 1, got %d / %d"
                % (block, max_blocks)
            )
        self.block = int(block)
        self.max_blocks = int(max_blocks)
        self.allocator = allocator
        self.on_evict = on_evict
        from collections import OrderedDict

        self._entries = OrderedDict()  # key -> _PrefixEntry, LRU order
        self.evictions = 0

    def __len__(self):
        return len(self._entries)

    def lookup(self, prompt):
        """Longest cached block-chain prefix of ``prompt``, capped at
        ``len(prompt) - 1`` tokens so admission ALWAYS recomputes at
        least the last prompt token (its logits are the first emitted
        token — a full-prompt hit would leave nothing to emit from).
        Every matched entry's block is INCREF'D for the caller — the
        references become the admitted slot's table entries; on a failed
        admission the caller must decref them back. A hash collision
        (equal key, different stored tokens) stops the chain: the suffix
        from there runs the normal prefill path."""
        usable = (len(prompt) - 1) // self.block
        out = []
        prev = 0
        for b in range(usable):
            toks = tuple(prompt[b * self.block:(b + 1) * self.block])
            key = _block_hash(prev, toks)
            e = self._entries.get(key)
            # verify the WHOLE chain link, not just this block's tokens:
            # a key collision with equal tokens but a different parent
            # (A||X vs B||X) would otherwise splice another prompt's
            # prefix K/V into this request
            if e is None or e.tokens != toks or e.prev != prev:
                break
            out.append(e)
            prev = key
        for e in out:
            self.allocator.incref([e.block_idx])
            self._entries.move_to_end(e.key)
        return out, len(out) * self.block

    def publish(self, prompt, slot_blocks):
        """Register every full block of ``prompt`` not indexed yet,
        pointing each entry at the admitted slot's OWN pool block
        (``slot_blocks[b]`` for prompt block b) — zero-copy publish.
        Each new entry increfs its block (the store's reference).
        Stops chaining at a collision, a missing slot block, or the
        store's pin budget. Returns the new entries."""
        new = []
        prev = 0
        for b in range(len(prompt) // self.block):
            toks = tuple(prompt[b * self.block:(b + 1) * self.block])
            key = _block_hash(prev, toks)
            e = self._entries.get(key)
            if e is not None:
                if e.tokens != toks or e.prev != prev:
                    break  # collision squatting on the key
                self._entries.move_to_end(key)
                prev = key
                continue
            if b >= len(slot_blocks):
                break
            if len(self._entries) >= self.max_blocks:
                if not self.evict_one():
                    break  # budget full of blocks slots still share
            e = _PrefixEntry(key, prev, toks, slot_blocks[b])
            self.allocator.incref([e.block_idx])
            self._entries[key] = e
            new.append(e)
            prev = key
        return new

    def forget(self, entry):
        if self._entries.get(entry.key) is entry:
            del self._entries[entry.key]
            self.allocator.decref([entry.block_idx])

    def evict_one(self, need_free=False):
        """Drop the least-recently-used entry — preferring one whose
        block the store alone references (decref actually FREES it).
        With ``need_free`` the sweep only takes such entries (the
        allocator-pressure path: evicting a slot-shared block releases
        no memory). Returns True if an entry was dropped."""
        victim = None
        for e in self._entries.values():  # oldest first
            if self.allocator.refs(e.block_idx) == 1:
                victim = e
                break
        if victim is None:
            if need_free:
                return False
            victim = next(iter(self._entries.values()), None)
            if victim is None:
                return False
        if self.on_evict is not None:
            try:
                self.on_evict(victim)
            except Exception:  # noqa: BLE001 - spill is best-effort
                pass
        del self._entries[victim.key]
        self.allocator.decref([victim.block_idx])
        self.evictions += 1
        _profiler.bump_counter("decode_prefix_evictions")
        return True

    def admit(self, key, prev, tokens, block_idx):
        """Register a block REBUILT from outside the device pool (a
        host-store re-admission or a pulled peer payload) under its
        chain key. The caller owns ``block_idx`` with exactly one
        reference and hands it to the index — unlike ``publish`` there
        is no slot also holding it, so no extra incref. Returns the new
        entry, or None when the key is already (or cannot be) indexed —
        then the caller keeps its reference."""
        toks = tuple(int(t) for t in tokens)
        if self._entries.get(key) is not None:
            return None
        if len(self._entries) >= self.max_blocks:
            if not self.evict_one():
                return None
        e = _PrefixEntry(key, prev, toks, block_idx)
        self._entries[key] = e
        return e

    def head_keys(self, k):
        """Newest-``k`` chain keys — the replica's cache-affinity
        advertisement. Read lock-free off the gateway thread: the dict
        view is copied first and a racing mutation at worst yields a
        slightly stale list, which the router's staleness bound already
        tolerates."""
        try:
            keys = list(self._entries.keys())
        except RuntimeError:  # resized mid-copy — advertise nothing
            return []
        return keys[-int(k):][::-1] if k > 0 else []

    def stats(self):
        return {
            "block": self.block,
            "max_blocks": self.max_blocks,
            "cached_blocks": len(self._entries),
            "evictions": self.evictions,
        }


def step_tail(main, startup, logits, width):
    """The tail the session gives every family's step program of
    ``width``: the greedy pick is made where the logits are
    (``argmax`` over the vocabulary of the [slots * width, vocab] float32
    ``logits``; equal logits go to the lowest id), and the logits stay on
    the device as a persistable scope var, the way the pools do. So a
    step brings slots * width ids to the host, and a sampled stream's
    rows are read from the scope (``DecodeSession.step_logits``).
    -> (the ids' name, the kept logits' name)."""
    with fluid.program_guard(main, startup):
        pick = fluid.layers.argmax(logits, axis=-1)
        kept = main.global_block().create_var(
            name="decode_step_logits.w%d" % width, shape=logits.shape,
            dtype=logits.dtype, persistable=True)
        fluid.layers.assign(logits, kept)
    return pick.name, kept.name


class DecodeSession(object):
    """Synchronous KV-cache decode core over one Executor + scope.

    Builds the bucketed window programs, the fused step program of each
    width and the block copy (all under fresh ``unique_name`` guards, so
    their parameter names are the canonical ``<layer>.w_0`` spellings),
    seeds the pools with zeros directly in the scope (no startup run —
    the scope's model params are someone else's and must not be
    re-initialized), and exposes ``paged_window`` / ``paged_step`` /
    ``block_copy``. Thread-compatible, not thread-safe: one driver at a
    time (the engine's loop thread, or the caller of
    ``greedy_generate``)."""

    def __init__(self, cfg, place=None, scope=None, slots=None,
                 max_len=None, prefill_buckets=None, block_size=None,
                 pool_blocks=0, spec_tokens=None, window_cap=0, tp=None,
                 model=None):
        self.cfg = copy.copy(cfg)
        self.cfg.is_test = True
        # the served model's module (models/gpt.py unless told): it builds
        # the programs and says what its cache holds (``cache_kinds``)
        self.model = model if model is not None else _gpt
        self.slots = int(_flag("decode_slots", slots))
        # tensor-parallel serving (parallel/spmd.py): tp > 1 runs every
        # session program through the GSPMD mesh path over a
        # {"model": tp} mesh — weights Megatron column/row-sharded, KV
        # pools split by heads (each row's last dim), slot indices and
        # block tables replicated. The host-side runtime (slot
        # management, block tables, prefix index) is unchanged: only
        # placement differs, and every device step stays ONE
        # exe.run(...) call
        self.tp = max(int(_flag("spmd_decode_tp", tp)), 1)
        self._tp_mesh = None
        if self.tp > 1:
            _require(self.model, "tp")
            from ..parallel import spmd as _spmd

            self._tp_mesh = _spmd.tp_mesh(self.tp)
        max_len = int(_flag("decode_max_len", max_len))
        if max_len <= 0:
            max_len = int(cfg.max_position_embeddings)
        if max_len > cfg.max_position_embeddings:
            raise ValueError(
                "decode max_len %d exceeds max_position_embeddings %d"
                % (max_len, cfg.max_position_embeddings)
            )
        if self.slots < 1 or max_len < 2:
            raise ValueError(
                "need slots >= 1 and max_len >= 2, got %d / %d"
                % (self.slots, max_len)
            )
        self.max_len = max_len
        # block-table addressing over ONE shared pool for live slots AND
        # the prefix index
        self.block_size = _block_size(block_size)
        self.spec_tokens = max(int(_flag("decode_spec_tokens",
                                         spec_tokens)), 0)
        if self.spec_tokens > 1:
            _require(self.model, "spec_tokens")
        width = max(self.spec_tokens, 1)
        # speculative verify writes/embeds positions up to
        # max_len + k - 2 (a slot one token from the wall still
        # feeds a full k-window; emission stops at the budget)
        if max_len + width - 1 > cfg.max_position_embeddings:
            raise ValueError(
                "paged decode needs max_len + spec_tokens - 1 <= "
                "max_position_embeddings (%d + %d - 1 > %d): lower "
                "decode_max_len or decode_spec_tokens"
                % (max_len, width, cfg.max_position_embeddings)
            )
        self.max_blocks = -(-(max_len + width - 1) // self.block_size)
        self.pool_blocks = int(pool_blocks) or (
            self.slots * self.max_blocks + 1
        )
        # block 0 is the SINK: reserved garbage target every idle /
        # prefilling slot's table points at, so the fused step's
        # unconditional scatter-writes can never touch a live block
        if self.pool_blocks < 2:
            raise ValueError(
                "paged pool needs >= 2 blocks (sink + 1), got %d"
                % self.pool_blocks
            )
        wcap = int(window_cap) or max_len
        self.buckets = prefill_ladder(
            min(max_len, max(wcap, 1)),
            _flag("decode_prefill_buckets", prefill_buckets) or None,
        )
        self.place = (place if place is not None
                      else fluid.core.default_place())
        self.scope = scope if scope is not None else fluid.core.Scope()
        # own executor: the session's program/plan caches never contend
        # with (or evict) a caller's LRU entries
        self.exe = fluid.Executor(self.place)
        # host work of the session's driver to put under each device
        # call: called once between the call's dispatch and the wait for
        # its fetch (``Executor._run``). The engine publishes the last
        # step's tokens there; None for a session driven directly
        self.while_device_runs = None
        # session-local activity tallies (the process-global profiler
        # counters aggregate every session in the process; per-engine
        # stats need the unshared view)
        self.prefills = 0
        self.steps = 0
        # one driver at a time: the engine's loop thread is naturally
        # exclusive, but greedy_generate funnels arbitrary caller
        # threads into one CACHED session per (scope, geometry) — they
        # serialize on this lock so interleaved window/step calls can
        # never cross-contaminate the one slot's blocks
        self.lock = threading.RLock()
        self._paged_window = {}
        self._paged_step = {}
        # one window program per bucket handles ALL prefill (a whole
        # prompt is just a window at offset 0), one fused step per
        # width (1 = plain decode, spec_tokens = the batched verify),
        # and one block-copy for COW
        for seq_len in self.buckets:
            with fluid.unique_name.guard():
                main, _s, feeds, nl = self.model.build_paged_window(
                    self.cfg, self.pool_blocks, self.block_size,
                    self.max_blocks, seq_len, slots=self.slots,
                )
            self._paged_window[seq_len] = (self._maybe_tp(main), nl.name)
            self._window_feeds = frozenset(feeds)
        widths = [1]
        if self.spec_tokens > 1:
            widths.append(self.spec_tokens)
        for w in widths:
            with fluid.unique_name.guard():
                main, startup, feeds, sl = self.model.build_paged_step(
                    self.cfg, self.slots, self.pool_blocks,
                    self.block_size, self.max_blocks, step_w=w,
                )
                pick, kept = step_tail(main, startup, sl, w)
            # a program may name values to fetch beside the ids
            # (``_step_stats``), which the model's ``step_stats`` reads
            self._paged_step[w] = (
                self._maybe_tp(main),
                [pick] + list(getattr(main, "_step_stats", ())),
                kept,
            )
            self._step_feeds = frozenset(feeds)
        with fluid.unique_name.guard():
            main, _s, _f, ok = self.model.build_paged_block_copy(
                self.cfg, self.pool_blocks, self.block_size, npairs=1
            )
        self._block_copy = (self._maybe_tp(main), ok.name)
        self._cols = np.arange(self.max_blocks * self.block_size)
        self.reset_caches()

    def _maybe_tp(self, main):
        """tp > 1: route the program through the GSPMD mesh path. The
        returned CompiledProgram runs through the SAME
        ``exe.run(main, feed=..., ...)`` call sites (Executor delegates),
        so every device step below is parallelism-agnostic. Each program
        gets its own sharding plan (its persistable set differs —
        a window sees weights and pools, block-copy only pools)."""
        if self._tp_mesh is None:
            return main
        from ..fluid import compiler as _compiler

        return _compiler.CompiledProgram(main).with_mesh(
            mesh=self._tp_mesh
        )

    # -- state ---------------------------------------------------------------
    def cache_names(self):
        """Per layer, the scope names of the vars it keeps (pools and
        per-slot states, in ``cache_kinds``' order)."""
        return [_cache_kinds.names(layer, self.pool_blocks,
                                   self.block_size, self.slots)
                for layer in self.model.cache_kinds(self.cfg)]

    def pool_names(self):
        """Per layer, the scope names of its paged pools (none for a
        layer that keeps only per-slot state)."""
        return [tuple(p.name(self.pool_blocks, self.block_size)
                      for p in _cache_kinds.pools(layer))
                for layer in self.model.cache_kinds(self.cfg)]

    def kv_pool_names(self):
        """Per layer its (K pool, V pool) names, for the modes that move
        a block as a pair of equal rows; ``TypeError`` where a layer
        keeps anything else."""
        return [tuple(p.name(self.pool_blocks, self.block_size)
                      for p in pair)
                for pair in _cache_kinds.kv_pools(
                    self.model.cache_kinds(self.cfg))]

    def reset_caches(self):
        """Zero every pool and every per-slot state in the scope
        (host-side: no program, no param re-init). Correctness never
        depends on this — nothing attends to a position its slot has not
        written, and a prompt's first window starts its state from zeros
        itself — but fresh buffers make warmup and tests deterministic."""
        geometry = (self.pool_blocks, self.block_size, self.slots)
        for layer in self.model.cache_kinds(self.cfg):
            for kind, name, shape in zip(
                    layer, _cache_kinds.names(layer, *geometry),
                    _cache_kinds.shapes(layer, *geometry)):
                self.scope.set(name, np.zeros(
                    shape, fluid.core.dtype_to_np(kind.dtype)))

    def bind_params(self, program):
        """Alias ``program``'s parameters onto this session's canonical
        names. A program built OUTSIDE a fresh ``unique_name.guard()``
        carries shifted numeric suffixes (``gpt_0_att_q.w_3``); the
        session's programs always say ``.w_0``. Aliasing the scope entry
        (same array object — params are read-only here) lets the decode
        runtime attach to any trained/initialized scope. Cheap;
        re-invoked per generate call so retrained params stay current.

        Contract: ``program`` is THE model of this scope — the alias
        targets the canonical name, so a scope deliberately holding two
        same-architecture models (one guard-built, one not) would see
        the guard-built one's params replaced by this program's. Give
        each model its own scope (the repo-wide convention) if both
        must stay live."""
        for v in program.list_vars():
            if not getattr(v, "is_parameter", False):
                continue
            canon = re.sub(r"_(\d+)$", "_0", v.name)
            if canon == v.name:
                continue
            val = self.scope.get(v.name)
            if val is not None:
                self.scope.set(canon, val)

    def bucket_for(self, prompt_len):
        for b in self.buckets:
            if b >= prompt_len:
                return b
        raise ValueError(
            "prompt of %d tokens exceeds the prefill ladder (max %d)"
            % (prompt_len, self.buckets[-1])
        )

    # -- device steps --------------------------------------------------------
    def _run(self, main, feed, fetches):
        """One device call: every program of the session runs through
        here, so whichever the driver makes first carries its
        ``while_device_runs``."""
        return self.exe._run(main, feed, fetches, self.scope,
                             while_device_runs=self.while_device_runs)

    # -- paged device steps --------------------------------------------------
    def paged_window(self, table, window_ids, offset, slot=0):
        """Prefill one prompt window (batch 1) THROUGH a fed block
        table: window token i lands at logical position ``offset + i``,
        which ``table`` maps to a physical pool block — the only
        prefill form (offset 0 = the whole prompt, later offsets the
        suffix after a shared prefix or one chunk of a chunked prefill).
        A model that keeps per-slot state is fed ``slot``'s state row
        (``slot + 1``; row 0 is the sink) and the window's real length:
        at offset 0 the state starts from zeros, later windows continue
        the row.
        The window pads to its bucket; the offset rides the feed, so the
        bucket ladder's compiled programs cover every placement. Returns
        the logits [vocab] at the window's last real token (the
        next-token logits when this is the prompt's final window)."""
        P = len(window_ids)
        if P < 1:
            raise ValueError("empty prefill window")
        T = self.bucket_for(P)
        offset = int(offset)
        span = self.max_blocks * self.block_size
        if offset < 0 or offset + T > span:
            raise ValueError(
                "paged window bucket [%d, %d) exceeds the table span %d"
                % (offset, offset + T, span)
            )
        main, fetch_name = self._paged_window[T]
        with _trace.span("step_feed", cat="serving", cpu=True):
            ids = np.zeros((1, T, 1), "int64")
            ids[0, :P, 0] = window_ids
            last_onehot = np.zeros((1, T, 1), "float32")
            last_onehot[0, P - 1, 0] = 1.0
            tbl = np.zeros((1, self.max_blocks), "int64")
            tbl[0, :len(table)] = table
            feed = {
                "ids": ids,
                "pos_ids": (offset + np.arange(T)).reshape(1, T, 1)
                .astype("int64"),
                "table": tbl,
                "window_pos": np.array([[offset]], "int64"),
                "last_onehot": last_onehot,
            }
            if "resume_bias" in self._window_feeds:
                # offset-shifted causal mask over the gathered logical
                # row; the -1e4 side also buries sink garbage past the
                # live length (a program that does not declare the feed
                # masks from ``pos_ids`` itself)
                allow = (self._cols[None, :]
                         <= (offset + np.arange(T))[:, None])
                feed["resume_bias"] = np.where(
                    allow, 0.0, -1e4).astype("float32")[None]
            if "state_row" in self._window_feeds:
                feed["state_row"] = np.array([[int(slot) + 1]], "int64")
                feed["window_len"] = np.array([[P]], "int64")
        t0 = time.perf_counter()
        with _trace.span("decode_paged_window", cat="serving", cpu=True,
                         bucket=T, rows=P, offset=offset) as sp:
            (lv,) = self._run(main, feed, [fetch_name])
            if hasattr(self.model, "window_stats"):
                sp.note(**self.model.window_stats(self.cfg, offset, P, T))
        _profiler.bump_counter("decode_prefills")
        self.prefills += 1
        _profiler.bump_histogram(
            "decode_prefill_ms", (time.perf_counter() - t0) * 1e3
        )
        with _trace.span("step_logits", cat="serving"):
            return np.asarray(lv)[0]

    def paged_step(self, tokens, positions, tables, active, width=1):
        """``paged_step_ids`` for a caller that compares logits (the
        reference paths, the probes): the same one device call, then the
        whole of what it left in the scope. Returns logits
        [slots, width, vocab]."""
        self.paged_step_ids(tokens, positions, tables, active, width=width)
        return self.step_logits(width=width)

    def step_logits(self, slot=None, width=1):
        """The logits the last step of ``width`` left on the device:
        [slots, width, vocab], or ``slot``'s [width, vocab] rows alone,
        sliced there, so a sampled stream costs the host its own rows."""
        with _trace.span("step_logits", cat="serving"):
            lv = self.scope.get(self._paged_step[width][2])
            if slot is None:
                return np.asarray(lv).reshape(self.slots, width, -1)
            return np.asarray(lv[slot * width:(slot + 1) * width])

    def paged_step_ids(self, tokens, positions, tables, active, width=1):
        """ONE fused paged step over all slots: slot s advances the
        ``width``-token window ``tokens[s]`` at contiguous logical
        positions ``positions[s] .. positions[s]+width-1`` through its
        block table ``tables[s]``. width=1 is the plain decode tick;
        width=k is the speculative VERIFY (all k draft positions scored
        in one call). Inactive slots feed an inert zero token and an
        all-sink table, so their unconditional scatter-writes land in
        reserved block 0 and can never corrupt a live block; their
        attention output is fully masked and ignored. Returns each
        query's greedy token, [slots, width] integers: the argmax of its
        float32 logits row, taken on the device (equal logits go to the
        lowest id, as ``numpy.argmax`` has it). The logits stay there:
        ``step_logits`` reads them."""
        if width not in self._paged_step:
            raise ValueError(
                "no paged step program of width %d (built: %s)"
                % (width, sorted(self._paged_step))
            )
        with _trace.span("step_feed", cat="serving", cpu=True):
            act = np.asarray(active, bool)
            pos = np.asarray(positions, "int64")
            tok = np.where(act[:, None],
                           np.asarray(tokens, "int64").reshape(self.slots,
                                                               width), 0)
            qpos = pos[:, None] + np.arange(width)[None, :]
            tbl = np.zeros((self.slots, self.max_blocks), "int64")
            for s in range(self.slots):
                row = tables[s] if tables is not None else ()
                if len(row):
                    tbl[s, :len(row)] = row
            main, fetches, _kept = self._paged_step[width]
            feed = {
                "step_ids": tok.reshape(self.slots, width, 1),
                "step_pos": qpos.reshape(self.slots, width, 1)
                .astype("int64"),
                "tables": tbl,
            }
            if "step_bias" in self._step_feeds:
                # query i of slot s sees logical cache positions
                # <= qpos[s, i]; inactive rows mask everything (finite
                # softmax over garbage, output ignored). A program that
                # does not declare the feed masks from ``step_pos`` itself
                feed["step_bias"] = (
                    ((self._cols[None, None, :] > qpos[:, :, None])
                     | ~act[:, None, None]).astype("float32") * -1e4
                )
            if "state_rows" in self._step_feeds:
                # an active slot steps its own state row; an idle or
                # prefilling one the sink row 0, so the fused step cannot
                # clobber a state that is between two prefill windows
                feed["state_rows"] = np.where(
                    act, np.arange(self.slots) + 1, 0
                ).astype("int64").reshape(self.slots, 1)
        t0 = time.perf_counter()
        with _trace.span("decode_paged_step", cat="serving", cpu=True,
                         active=int(act.sum()), width=width) as sp:
            picked, *stats = self._run(main, feed, fetches)
            if len(fetches) > 1:
                sp.note(**self.model.step_stats(
                    [np.asarray(v) for v in stats],
                    live_rows=int((pos[act] + width).sum()),
                    live_slots=int(act.sum()), cfg=self.cfg))
        _profiler.bump_counter("decode_steps")
        self.steps += 1
        _profiler.bump_histogram(
            "decode_step_ms", (time.perf_counter() - t0) * 1e3
        )
        return np.asarray(picked).reshape(self.slots, width)

    def block_copy(self, src_blocks, dst_blocks):
        """Pool-internal block copy (all layers, K and V):
        ``pool[dst[i]] = pool[src[i]]`` — the copy-on-write device op.
        The compiled program carries one pair; callers pass equal-length
        lists and pairs run back to back."""
        main, fetch_name = self._block_copy
        for src, dst in zip(src_blocks, dst_blocks):
            with _trace.span("decode_block_copy", cat="serving",
                             src=int(src), dst=int(dst)):
                self._run(
                    main,
                    {"src": np.array([[src]], "int64"),
                     "dst": np.array([[dst]], "int64")},
                    [fetch_name],
                )


# -- greedy_generate's session cache ----------------------------------------
# stored ON the scope object (not in a module registry): a session holds
# a strong reference to its scope, so any global map — even weak-keyed —
# would pin every scope it ever saw (WeakKeyDictionary values that
# reference their key are never collected). As a scope attribute, the
# scope→session→scope cycle is ordinary garbage for the cycle collector
# and sessions really do die with the scope. Keyed by model geometry +
# flash policy so distinct configs in one scope never share programs.
_GEN_LOCK = threading.Lock()


def session_for_generate(exe, cfg, scope, max_len, param_program):
    scope_obj = scope if scope is not None else fluid.core.global_scope()
    key = (
        cfg.vocab_size, cfg.hidden_size, cfg.num_layers, cfg.num_heads,
        cfg.intermediate_size, cfg.max_position_embeddings,
        repr(getattr(cfg, "use_flash_attention", False)),
        bool(getattr(cfg, "flash_interpret", False)),
        int(max_len), type(exe.place).__name__, _block_size(None),
    )
    with _GEN_LOCK:
        cache = getattr(scope_obj, "_decode_gen_sessions", None)
        if cache is None:
            cache = {"lock": threading.Lock(), "sessions": {}}
            scope_obj._decode_gen_sessions = cache
    # session construction (len(buckets)+1 graph builds) happens under
    # the PER-SCOPE lock only: first-time callers on unrelated scopes
    # build in parallel; same-scope callers serialize
    with cache["lock"]:
        sess = cache["sessions"].get(key)
        if sess is None:
            # spec_tokens pinned 0 and tp pinned 1: greedy_generate's
            # 1-slot sessions build one step width and stay
            # single-device whatever FLAGS_decode_spec_tokens /
            # FLAGS_spmd_decode_tp arm for a serving engine
            sess = DecodeSession(
                cfg, place=exe.place, scope=scope_obj, slots=1,
                max_len=max_len, spec_tokens=0, tp=1,
            )
            cache["sessions"][key] = sess
    sess.bind_params(param_program)
    return sess


# ---------------------------------------------------------------------------
# sampling — host-side, over a logits row FETCHED for the stream (greedy
# streams' step tokens are picked on the device: ``step_tail``)
# ---------------------------------------------------------------------------


def sample_token(logits, temperature=0.0, top_k=0, top_p=0.0, rng=None):
    """Pick one token id from a ``[vocab]`` logits row.

    Host-side by design: a prefill window fetches its row and a step
    keeps its logits where a sampled stream's rows can be read
    (``DecodeSession.step_logits``), so sampling over them adds zero
    graph surface — no new compiled program, no shape change, the
    strict-compile gate never sees it. ``temperature <= 0`` is GREEDY
    (argmax), the default
    everywhere, which keeps every token-exact parity contract intact;
    ``top_k``/``top_p`` only apply when temperature sampling is on.
    ``rng`` is a ``np.random.RandomState`` (seeded per request by the
    engine) so a given (prompt, knobs, seed) replays the same completion.
    Filtering order matches the common serving convention: temperature
    scale -> top-k cut -> softmax -> nucleus (top-p) cut -> renormalize.

    RNG-consumption CONTRACT (what makes mid-stream resume replayable):
    a temperature-sampled pick consumes EXACTLY ONE uniform draw
    (``rng.random_sample()`` — the inverse-CDF selection below is
    explicit, never ``rng.choice`` whose internal consumption is an
    implementation detail); a greedy pick consumes ZERO. So a
    generation resumed after k emitted tokens reproduces the
    uninterrupted run exactly by seeding the same RandomState and
    ``fast_forward_rng(rng, k)`` — no logits needed for the skipped
    draws.
    """
    z = np.asarray(logits, np.float64).ravel()
    if temperature is None or temperature <= 0.0:
        return int(z.argmax())
    z = z / float(temperature)
    if top_k and 0 < int(top_k) < z.size:
        kth = np.partition(z, -int(top_k))[-int(top_k)]
        z = np.where(z < kth, -np.inf, z)
    z = z - z.max()
    probs = np.exp(z)
    probs /= probs.sum()
    if top_p and 0.0 < float(top_p) < 1.0:
        order = np.argsort(-probs)
        csum = np.cumsum(probs[order])
        # keep the minimal prefix whose mass reaches top_p: a token stays
        # if the mass BEFORE it is still short of top_p (the first token
        # always stays, so the cut can never empty the distribution)
        drop = order[(csum - probs[order]) >= float(top_p)]
        probs[drop] = 0.0
        probs /= probs.sum()
    if not np.isfinite(probs).all():
        # a denormal temperature (1e-308) overflows the scaled logits to
        # inf and the softmax to NaN; fail THIS request loudly instead
        # of handing np.random.choice a poisoned distribution
        raise ValueError(
            "sampling produced non-finite probabilities "
            "(temperature %r too extreme for the logits)" % (temperature,)
        )
    r = rng if rng is not None else np.random
    # one uniform, inverse-CDF: token i owns the interval
    # (cdf[i-1], cdf[i]] so zero-probability (filtered) tokens have a
    # zero-width interval and can never be drawn; scaling u by cdf[-1]
    # absorbs float summation error instead of leaving a dead tail.
    # The nextafter clamp keeps the scaled draw STRICTLY below cdf[-1]:
    # u < 1, but u * cdf[-1] can round UP to exactly cdf[-1], and
    # side="right" would then land past the flat zero-probability tail
    # (a filtered token) instead of on the last positive one
    u = float(r.random_sample())
    cdf = np.cumsum(probs)
    x = min(u * cdf[-1], np.nextafter(cdf[-1], 0.0))
    return int(min(np.searchsorted(cdf, x, side="right"),
                   probs.size - 1))


def fast_forward_rng(rng, n):
    """Advance ``rng`` past ``n`` sampled-token draws — the explicit
    resume API: by the consumption contract above, discarding ``n``
    uniforms puts a freshly seeded RandomState in EXACTLY the state the
    uninterrupted run's RNG held after emitting its first ``n``
    temperature-sampled tokens (greedy tokens consume nothing, so a
    greedy resume never calls this). One vectorized draw, not ``n``
    dummy ``sample_token`` calls into the void."""
    n = int(n)
    if n < 0:
        raise ValueError("cannot fast-forward a negative draw count")
    if n:
        rng.random_sample(n)
    return rng


# ---------------------------------------------------------------------------
# streaming handle
# ---------------------------------------------------------------------------

_SENTINEL = object()


class GenerationStream(object):
    """Per-request streaming handle. The engine pushes tokens as they are
    generated; the caller iterates (``for tok in stream``) for live
    streaming, or blocks on ``tokens()`` / ``result()`` for the whole
    completion. Single consumer. ``finish_reason`` is ``"eos"`` /
    ``"length"`` once done."""

    def __init__(self, prompt_ids, max_new_tokens=None, eos_id=None,
                 temperature=0.0, top_k=0, top_p=0.0, seed=None,
                 resume_tokens=None, priority=None, tenant=None):
        self.prompt_ids = [int(t) for t in prompt_ids]
        # scheduling identity (weighted-fair dequeue + preemption):
        # interactive unless the caller says batch; tenant keys the
        # fair-share virtual time
        self.priority = "batch" if priority == "batch" else "interactive"
        self.tenant = str(tenant or "")
        # how many times this stream was preemption-evicted and
        # re-admitted token-exactly (journey fact; 0 for most streams)
        self.preemptions = 0
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        # sampling knobs (host-side over fetched logits — sample_token):
        # temperature <= 0 keeps the request greedy/argmax regardless of
        # top_k/top_p, so the token-exact default path is untouched. The
        # per-request RandomState makes a seeded request replay exactly
        # whatever other streams share its decode batch.
        self.temperature = float(temperature or 0.0)
        self.top_k = int(top_k or 0)
        self.top_p = float(top_p or 0.0)
        self.seed = seed
        # resume form: ``resume_tokens`` is the suffix an interrupted
        # run of this request already emitted. The engine re-prefills
        # prompt + resume_tokens (through the prefix/chunked admission
        # path) and this stream emits ONLY the continuation — token
        # exactly equal to what the uninterrupted run would have said
        # next, because the logits after caching prompt+emitted are the
        # same and the RNG is fast-forwarded past the emitted picks.
        self.resume_tokens = [int(t) for t in (resume_tokens or [])]
        self._rng = (
            np.random.RandomState(seed) if self.temperature > 0.0 else None
        )
        if self._rng is not None and self.resume_tokens:
            fast_forward_rng(self._rng, len(self.resume_tokens))
        self.finish_reason = None
        # engine tick bookkeeping (scheduler tests / fairness probes):
        # the tick a slot was admitted on and the last tick it decoded on
        self.first_tick = None
        self.last_tick = None
        # latency + prefix-cache facts, engine-stamped: ttft_ms is
        # submit -> first generated token, cached_prefix_tokens how many
        # prompt tokens the prefix cache served (0 on a miss / disabled)
        # — the gateway surfaces both on the SSE done event and the
        # access log. admit_windows counts the bucket-shaped prefill
        # windows the admission ran (1 = monolithic), so a resume
        # admission can prove it rode the chunked/prefix path
        self.ttft_ms = None
        self.cached_prefix_tokens = 0
        self.admit_windows = 0
        # speculative-decoding facts, engine-stamped (0 unless the
        # engine runs with decode_spec_tokens > 1): how many draft
        # tokens the verify program scored for this stream and how many
        # it accepted — the per-request acceptance rate the gateway
        # surfaces beside ttft_ms
        self.spec_drafted = 0
        self.spec_accepted = 0
        # distributed-trace hand-off: the stream is constructed on the
        # SUBMITTING thread (the gateway handler inside its
        # trace_scope); the engine loop re-enters this context around
        # the slot's prefill windows and lists the trace_id on every
        # decode tick the slot is active in — the engine-side spans of
        # the request's cross-process tree
        self.trace_ctx = _trace.current_context()
        self._t_submit = time.monotonic()
        self._t_last_emit = None
        # the request's times on the spans' clock (perf_counter): submit,
        # dequeue (the engine's _admit), every pushed token (a list
        # beside _tokens; the first is the first token), finish. They go
        # out in the one decode_request record the stream leaves
        self.t_submit = time.perf_counter()
        # the engine's step counter as ``submit`` read it on the caller's
        # thread and as ``_admit`` read it at the dequeue: how many steps
        # the loop made while the request waited in the queue
        self.submit_tick = None
        self.dequeue_tick = None
        self.t_dequeue = None
        self.t_finish = None
        self._emit_times = []
        # SimpleQueue: a put is one C call that runs no Python code and
        # takes no Python-level lock (the loop thread does one a token)
        self._q = queue.SimpleQueue()
        self._tokens = []
        self._done = threading.Event()
        self._error = None
        self._cancelled = False

    def full_prompt(self):
        """What the engine actually prefills: the request prompt plus
        the resume suffix (every token whose K/V must be in the cache
        before the next token can be picked)."""
        return self.prompt_ids + self.resume_tokens

    @property
    def emitted_count(self):
        """Tokens of the LOGICAL generation emitted so far: the resumed
        suffix plus everything this stream pushed — what a transport
        needs to build the next resume form."""
        return len(self.resume_tokens) + len(self._tokens)

    def cancel(self):
        """Abandon the request: the engine retires its slot at the next
        tick boundary (finish_reason ``"cancelled"``) instead of
        decoding tokens nobody will read — a transport whose client
        timed out or disconnected MUST call this, or dead requests keep
        occupying decode slots to completion. Safe from any thread,
        idempotent, a no-op once the stream already finished."""
        self._cancelled = True

    # engine side
    def pick(self, logits):
        """Select this request's next token from a ``[vocab]`` logits
        row: greedy argmax unless the request armed temperature
        sampling (then ``sample_token`` with the per-request RNG)."""
        if self._rng is None:
            return int(np.asarray(logits).ravel().argmax())
        return sample_token(logits, temperature=self.temperature,
                            top_k=self.top_k, top_p=self.top_p,
                            rng=self._rng)

    # Each of the three decides (what the engine's next tick reads:
    # ``_tokens``, the stamps, ``finish_reason``, ``_error``, the
    # request's record) and then publishes (what wakes the consumer's
    # thread). With an ``outbox`` the publication waits there, in order,
    # until its owner hands it to ``_publish``: the engine's loop does
    # that once the next device call is dispatched, so the readers run
    # while the chip works.
    def _push(self, tok, outbox=None):
        self._emit_times.append(time.perf_counter())
        self._tokens.append(int(tok))
        self._publish(int(tok), outbox)

    def _finish(self, reason, outbox=None):
        self.finish_reason = reason
        self._record(reason)
        self._publish(_SENTINEL, outbox)

    def _fail(self, exc, outbox=None):
        self._error = exc
        self._record("error")
        self._publish(_SENTINEL, outbox)

    def _publish(self, item, outbox=None):
        """Hand ``item`` (a token, or the sentinel that ends the stream)
        to the consumer, or to ``outbox`` as ``(stream, item)``."""
        if outbox is not None:
            outbox.append((self, item))
            return
        if item is _SENTINEL:
            self._done.set()
        self._q.put(item)

    def _record(self, reason):
        """The one record a request leaves: a ``decode_request`` instant
        with its times on the spans' clock, under the request's trace id.
        An instant, not a span: a request-long interval on the loop
        thread would swallow every idle gap that no phase span explains."""
        self.t_finish = time.perf_counter()
        if not _trace.enabled():
            return
        deq = self.t_dequeue
        first = self._emit_times[0] if self._emit_times else None
        with _stream_scope(self):
            _trace.instant(
                "decode_request", cat="serving",
                submit=self.t_submit, dequeue=deq, first_token=first,
                finish=self.t_finish,
                queue_wait_ms=(None if deq is None
                               else (deq - self.t_submit) * 1e3),
                first_token_ms=(None if deq is None or first is None
                                else (first - deq) * 1e3),
                submit_tick=self.submit_tick,
                dequeue_tick=self.dequeue_tick,
                prefill_windows=self.admit_windows,
                tokens=len(self._tokens), finish_reason=reason,
                preempted=self.preemptions,
            )

    # consumer side
    @property
    def done(self):
        return self._done.is_set()

    def __iter__(self):
        return self.stream_tokens(timeout=None)

    def stream_tokens(self, timeout=None):
        """Like iteration, but the WHOLE stream must finish within
        ``timeout`` seconds (None = unbounded): raises ``TimeoutError``
        mid-iteration when the budget runs out, so a transport (the HTTP
        gateway's SSE writer) can bound a wedged stream instead of
        holding its connection open forever. Single consumer — don't mix
        with ``__iter__`` on the same stream."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            remaining = None
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError("generation still in flight")
            try:
                item = self._q.get(timeout=remaining)
            except queue.Empty:
                raise TimeoutError("generation still in flight")
            if item is _SENTINEL:
                if self._error is not None:
                    raise self._error
                return
            yield item

    def tokens(self, timeout=None):
        """Block until the request finishes; returns the GENERATED tokens
        (prompt excluded)."""
        if not self._done.wait(timeout):
            raise TimeoutError("generation still in flight")
        if self._error is not None:
            raise self._error
        return list(self._tokens)

    def result(self, timeout=None):
        """prompt + generated tokens — ``greedy_generate``'s contract.
        On a resume form this includes the resumed suffix, so the result
        is the SAME full sequence the uninterrupted run returns."""
        return self.prompt_ids + self.resume_tokens + self.tokens(timeout)


def _stream_scope(stream):
    """The ambient trace context of one request's stream, re-entered on
    the engine loop thread so the slot's prefill/copy/publish spans join
    the request's distributed tree. A no-op scope for untraced streams
    (duck-typed fakes included)."""
    ctx = getattr(stream, "trace_ctx", None) or (None, None)
    return _trace.trace_scope(*ctx)


class _Slot(object):
    __slots__ = ("stream", "pending_token", "next_pos", "generated")

    def __init__(self, stream, pending_token, next_pos, generated=1):
        self.stream = stream
        self.pending_token = pending_token  # emitted, not yet cached
        self.next_pos = next_pos            # cache position it writes next
        # LOGICAL tokens generated so far (prefill already emitted one;
        # a resume admission starts past its replayed suffix so
        # max_new/max_len budgets stay those of the original request)
        self.generated = generated


class _PrefillJob(object):
    """A slot mid-prefill: its prompt's remaining bucket-shaped windows.
    Multi-window jobs (chunked prefill) advance one window per engine
    tick; ``prefix_tokens`` is the cached-prefix length already copied
    into the row head."""

    __slots__ = ("stream", "windows", "wi", "prefix_tokens")

    def __init__(self, stream, windows, prefix_tokens):
        self.stream = stream
        self.windows = windows
        self.wi = 0
        self.prefix_tokens = prefix_tokens


# ---------------------------------------------------------------------------
# speculative drafters — host-side, correctness-neutral proposals
# ---------------------------------------------------------------------------


def _ngram_draft(history, k):
    """Self-draft from the stream's own history: find the most recent
    earlier occurrence of the trailing n-gram (n = 3 shrinking to 1)
    and propose the continuation that followed it, padded with its last
    token to exactly ``k`` tokens. A wrong draft only costs verify
    compute — the accept loop guarantees the emitted tokens match
    sequential decoding bit for bit — so the drafter optimizes for the
    repetition-heavy spans (code, templates, copied context) where
    n-gram continuation is usually right."""
    hist = [int(t) for t in history]
    draft = []
    for n in (3, 2, 1):
        if len(hist) <= n:
            continue
        key = tuple(hist[-n:])
        for i in range(len(hist) - n - 1, -1, -1):
            if tuple(hist[i:i + n]) == key:
                draft = hist[i + n:i + n + k]
                break
        if draft:
            break
    if not draft:
        draft = [hist[-1]] if hist else [0]
    while len(draft) < k:
        draft.append(draft[-1])
    return draft[:k]


def _repeat_draft(history, k):
    """Degenerate drafter: propose the last token ``k`` times — the
    cheapest possible proposal, right exactly on run-length spans."""
    last = int(history[-1]) if history else 0
    return [last] * k


# the FLAGS_decode_spec_draft seam: named built-ins here; a small-model
# drafter plugs in as DecodeEngine(drafter=callable(history, k) -> [k])
_SPEC_DRAFTERS = {"ngram": _ngram_draft, "repeat": _repeat_draft}


# ---------------------------------------------------------------------------
# continuous-batching engine
# ---------------------------------------------------------------------------


class DecodeEngine(object):
    """Continuous batching over a ``DecodeSession`` slot pool.

    One loop thread ticks: admit queued requests into free slots via
    prefill windows (mid-flight — active streams keep decoding across
    admissions), then run ONE fused decode step for every active slot,
    stream each new token out, and retire slots on EOS / max-tokens /
    max-length. Greedy (argmax) decoding — token-exact with
    ``gpt._reference_generate``.

    ``start()`` eagerly compiles every window bucket, every step width
    and the block copy inside a warmup window, then arms the PR 7 counted
    strict serving gate: with ``FLAGS_serving_strict_compiles`` any later
    request-path XLA compile raises ``SteadyStateRecompileError`` with
    the sentinel's attribution. Admission/retirement churn cannot trip
    it — no compiled shape depends on which slots are live."""

    def __init__(self, cfg, place=None, scope=None, slots=None,
                 max_len=None, prefill_buckets=None, queue_depth=None,
                 param_program=None,
                 prefix_cache_mb=None, prefill_chunk=None,
                 block_size=None, spec_tokens=None, spec_draft=None,
                 pool_blocks=0, drafter=None, tp=None, model=None):
        self._cfg = cfg
        # the served model's module: ``models/gpt.py`` unless told. Its
        # ``cache_kinds`` give the paged cache bytes a token costs over
        # all layers, which is what sizes the pool's accounting
        self._model = model if model is not None else _gpt
        kinds = self._model.cache_kinds(cfg)
        self.kv_bytes_per_token = _cache_kinds.bytes_per_token(kinds)
        # what a slot keeps whatever its length (a recurrent state):
        # counted beside the pools, never through the block allocator
        self.state_bytes_per_slot = _cache_kinds.state_bytes_per_slot(kinds)
        self._place = (place if place is not None
                       else fluid.core.default_place())
        # a Place that names no device of this process fails here, not
        # in start()
        fluid.core.get_jax_device(self._place)
        self._scope = scope
        # tensor-parallel serving over the GSPMD mesh: the replica's
        # device count; the session shards weights/KV over it
        self.tp = max(int(_flag("spmd_decode_tp", tp)), 1)
        self._slots_arg = slots
        self._max_len_arg = max_len
        self._buckets_arg = prefill_buckets
        self.queue_depth = int(_flag("decode_queue_depth", queue_depth))
        self._param_program = param_program
        # prefix caching + chunked prefill knobs: prefix_cache_mb bounds
        # the pool blocks the prefix index may pin (0 = prefix caching
        # off), prefill_chunk caps how many prompt tokens one tick may
        # prefill (0 = the whole prompt in one window)
        self.prefix_cache_mb = float(
            _flag("decode_prefix_cache_mb", prefix_cache_mb)
        )
        self.prefill_chunk = int(_flag("decode_prefill_chunk",
                                       prefill_chunk))
        if self.prefill_chunk < 0 or self.prefix_cache_mb < 0:
            raise ValueError(
                "prefill_chunk and prefix_cache_mb must be >= 0"
            )
        # tokens a KV block, which is also the prefix reuse granularity;
        # spec_tokens > 1 arms speculative decoding
        self.block_size = _block_size(block_size)
        self.spec_tokens = int(_flag("decode_spec_tokens", spec_tokens))
        self._spec_width = max(self.spec_tokens, 1)
        self._pool_blocks_arg = int(pool_blocks or 0)
        if drafter is not None:
            self._drafter = drafter
        else:
            name = str(_flag("decode_spec_draft", spec_draft) or "ngram")
            if name not in _SPEC_DRAFTERS:
                raise ValueError(
                    "unknown decode_spec_draft %r (built-ins: %s; pass "
                    "drafter= for a model-based one)"
                    % (name, sorted(_SPEC_DRAFTERS))
                )
            self._drafter = _SPEC_DRAFTERS[name]
        self.pindex = None  # PagedPrefixIndex once started (store enabled)
        self.allocator = None  # BlockAllocator once started
        self._slot_blocks = {}  # slot_idx -> [pool block ids]
        self.session = None
        self.started = False
        self.tick = 0
        self._pending = deque()
        self._active = {}
        self._prefilling = {}
        self._free = []
        self._cond = threading.Condition()
        self._stop = False
        self._thread = None
        # engine-local tallies: stats() must report THIS engine, not the
        # process-global counters shared with sibling sessions/engines
        self._counts = {"requests": 0, "admissions": 0,
                        "retirements": 0, "tokens": 0,
                        "prefix_hits": 0, "prefix_misses": 0,
                        "prefix_cached_tokens": 0, "prompt_tokens": 0,
                        "resume_admissions": 0, "resume_tokens": 0,
                        "spec_drafted": 0, "spec_accepted": 0,
                        "oom_sheds": 0,
                        "kv_readmits": 0, "kv_readmit_tokens": 0,
                        "preemptions": 0, "preempt_replayed_tokens": 0,
                        "published_overlapped": 0, "published_exposed": 0,
                        "picks_on_device": 0, "picks_on_host": 0}
        # what the loop thread has decided and not yet handed to the
        # streams' consumers: (stream, token or sentinel) in order.
        # ``_publish`` empties it once the next device call is
        # dispatched, or at once where none follows; the lock keeps a
        # stop() whose join timed out from interleaving with the loop
        self._outbox = deque()
        self._publish_lock = threading.Lock()
        # weighted-fair scheduler state (stride scheduling): per-tenant
        # virtual time + the global virtual clock a joining tenant
        # starts at (so a newcomer can't claim "unused" history)
        self._sched_vtime = {}
        self._sched_vclock = 0.0
        self._sched_weights = {}
        self._sched_weights_ver = None
        # fleet KV tier (kv_tier.py): host-spill store behind the
        # prefix index. Evicted device blocks spill D2H off the tick
        # thread; a later admission whose chain outruns the device index
        # re-admits the spilled payload H2D instead of re-prefilling.
        self.kv_host_mb = float(_flags.get_flag("kv_tier_host_mb"))
        self.kv_advert_k = int(_flags.get_flag("kv_tier_advert_k"))
        self.host_store = None   # kv_tier.HostBlockStore once started
        self._spill_worker = None
        # worker -> loop thread hand-back: block ids whose D2H read
        # finished (deque append/popleft are atomic — no lock needed)
        self._spill_done = deque()
        # gateway -> loop thread: chain-export jobs for the prefill-role
        # /v1/kv/prefill endpoint (the pool read must run on the single
        # mutator thread)
        self._export_jobs = deque()
        self._armed = False
        self._occ_gauge = None
        self._queue_gauge = None
        self._blocks_free_gauge = None
        self._blocks_shared_gauge = None
        self._spec_gauge = None
        self._state_slots_gauge = None
        self._state_bytes_gauge = None
        self._host_blocks_gauge = None
        self._host_bytes_gauge = None

    # -- lifecycle -----------------------------------------------------------
    def start(self, loop=True):
        """Build the session, warm every steady-state shape, register
        gauges, and (default) spawn the driver loop thread.
        ``loop=False`` skips the thread: the caller drives ``_tick()``
        itself — the deterministic harness the scheduler/preemption
        tests use to stop the engine at an exact token boundary."""
        if self.started:
            raise RuntimeError("decode engine already started")
        if self._thread is not None and self._thread.is_alive():
            # a previous stop()'s thread-join timed out (loop wedged in a
            # device call): refuse to spawn a second driver for the
            # (thread-unsafe) session — _stop stays latched, so the old
            # thread exits at its next loop-top check and a later start
            # succeeds
            raise RuntimeError(
                "previous decode-engine loop thread has not exited yet"
            )
        self.session = DecodeSession(
            self._cfg, place=self._place, scope=self._scope,
            slots=self._slots_arg, max_len=self._max_len_arg,
            prefill_buckets=self._buckets_arg,
            block_size=self.block_size,
            pool_blocks=self._pool_blocks_arg,
            spec_tokens=self.spec_tokens,
            window_cap=self.prefill_chunk,
            tp=self.tp, model=self._model,
        )
        self.allocator = BlockAllocator(self.session.pool_blocks)
        self.pindex = None
        if self.prefix_cache_mb > 0:
            if self.kv_host_mb > 0:
                _require(self._model, "kv_host_tier")
            _require(self._model, "prefix_cache")
            # the store is ZERO-copy (entries pin pool blocks slots
            # already wrote), so the mb budget caps how many blocks the
            # store may pin, not a separate allocation
            cap = max(1, int(
                self.prefix_cache_mb * 2 ** 20
                // (self.kv_bytes_per_token * self.block_size)
            ))
            self.pindex = PagedPrefixIndex(
                self.block_size, cap, self.allocator
            )
            if self.kv_host_mb > 0:
                # host tier behind the device index: eviction spills
                # instead of vanishing, admission walks here when
                # the device chain runs out
                self.host_store = _kv_tier.HostBlockStore(
                    int(self.kv_host_mb * 2 ** 20)
                )
                self.pindex.on_evict = self._on_index_evict
                self._spill_done.clear()
                self._spill_worker = _kv_tier.SpillWorker(
                    self._spill_batch
                )
        if self._param_program is not None:
            self.session.bind_params(self._param_program)
        self.session.while_device_runs = self._publish_overlapped
        self._warmup()
        self._free = list(range(self.session.slots))
        self._stop = False
        try:
            # telemetry mirrors InferenceServer: exporter lights up from
            # flags, occupancy/queue depth publish as scrape-time gauges,
            # and the steady-compile gate arms COUNTED (ownership-scoped)
            _obs_exporter.maybe_start_from_flags()
            # occupancy = slots unavailable for admission: decoding AND
            # mid-chunked-prefill — a fleet autoscaler reading 2/8 while
            # 6 more slots hold prefilling long prompts would see free
            # capacity that does not exist
            self._occ_gauge = lambda e=self: (len(e._active)
                                              + len(e._prefilling))
            _obs_registry.register_gauge(
                "serving_slot_occupancy", self._occ_gauge
            )
            self._queue_gauge = lambda e=self: len(e._pending)
            _obs_registry.register_gauge(
                "decode_queue_depth", self._queue_gauge
            )
            # pool pressure at a glance: free blocks left, and how
            # many are multiply-referenced (prefix sharing at work)
            self._blocks_free_gauge = lambda e=self: (
                e.allocator.free_blocks
            )
            _obs_registry.register_gauge(
                "decode_blocks_free", self._blocks_free_gauge
            )
            self._blocks_shared_gauge = lambda e=self: (
                e.allocator.shared_blocks
            )
            _obs_registry.register_gauge(
                "decode_blocks_shared", self._blocks_shared_gauge
            )
            if self.state_bytes_per_slot:
                # per-slot recurrent state: rows in use (decoding or
                # between prefill windows) and the bytes they hold
                self._state_slots_gauge = lambda e=self: (
                    len(e._active) + len(e._prefilling))
                _obs_registry.register_gauge(
                    "decode_state_slots_live", self._state_slots_gauge
                )
                self._state_bytes_gauge = lambda e=self: (
                    e._state_slots_gauge() * e.state_bytes_per_slot)
                _obs_registry.register_gauge(
                    "decode_state_bytes", self._state_bytes_gauge
                )
            if self._spec_width > 1:
                self._spec_gauge = lambda e=self: (
                    e._counts["spec_accepted"]
                    / max(e._counts["spec_drafted"], 1)
                )
                _obs_registry.register_gauge(
                    "decode_spec_acceptance", self._spec_gauge
                )
            if self.host_store is not None:
                # host-tier pressure at a glance: resident spilled
                # blocks and the bytes they hold against the cap
                self._host_blocks_gauge = lambda e=self: (
                    len(e.host_store) if e.host_store else 0
                )
                _obs_registry.register_gauge(
                    "kv_tier_host_blocks", self._host_blocks_gauge
                )
                self._host_bytes_gauge = lambda e=self: (
                    e.host_store.bytes_used if e.host_store else 0
                )
                _obs_registry.register_gauge(
                    "kv_tier_host_bytes", self._host_bytes_gauge
                )
            _xla_stats.arm_serving_steady()
            self._armed = True
            if loop:
                self._thread = threading.Thread(
                    target=self._loop, name="decode-engine", daemon=True
                )
                self._thread.start()
            # LAST: a half-started engine must never look started — a
            # failure above (thread exhaustion, gauge clash) would
            # otherwise leave submits feeding a queue nothing drains
            self.started = True
        except Exception:
            if self._armed:
                _xla_stats.disarm_serving_steady()
                self._armed = False
            self._drop_gauges()
            raise
        return self

    def _drop_gauges(self):
        """Unregister every gauge this engine published (start-failure
        unwind and stop share the teardown)."""
        for name, attr in (
            ("serving_slot_occupancy", "_occ_gauge"),
            ("decode_queue_depth", "_queue_gauge"),
            ("decode_blocks_free", "_blocks_free_gauge"),
            ("decode_blocks_shared", "_blocks_shared_gauge"),
            ("decode_spec_acceptance", "_spec_gauge"),
            ("decode_state_bytes", "_state_bytes_gauge"),
            ("decode_state_slots_live", "_state_slots_gauge"),
            ("kv_tier_host_blocks", "_host_blocks_gauge"),
            ("kv_tier_host_bytes", "_host_bytes_gauge"),
        ):
            fn = getattr(self, attr)
            if fn is not None:
                _obs_registry.unregister_gauge(name, fn)
                setattr(self, attr, None)

    def _warmup(self):
        """Compile every shape the steady state can touch: each window
        bucket, each step width (1 + the spec verify; a step's compiled
        shape is independent of WHICH slots are active, so one
        all-inactive step covers every future mix) and the COW block
        copy. All-sink tables (and the sink state row) make every warmup
        write inert garbage in reserved block 0 / row 0 — nothing live
        to reset, but zeroing the caches afterwards keeps tests
        deterministic."""
        sess = self.session
        with _xla_stats.warmup_window(), _trace.span(
            "decode_warmup", cat="serving"
        ):
            sink = [0] * sess.max_blocks
            for T in sess.buckets:
                # slot -1: the sink state row
                sess.paged_window(sink, [0] * T, 0, slot=-1)
            for w in sorted(sess._paged_step):
                sess.paged_step_ids(
                    np.zeros((sess.slots, w), "int64"),
                    [0] * sess.slots, [()] * sess.slots,
                    [False] * sess.slots, width=w,
                )
                # the slice a sampled stream's rows are read through
                sess.step_logits(0, width=w)
            sess.block_copy([0], [0])
            sess.reset_caches()

    def stop(self):
        if not self.started:
            return
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            # a still-wedged loop thread keeps its handle: start()
            # refuses to run a second driver beside it (see start())
            if not self._thread.is_alive():
                self._thread = None
        if self._spill_worker is not None:
            # finishes queued spill batches first (the loop thread is
            # gone, so the scope reads race nothing), then exits; the
            # pinned-block refs die with the allocator on next start
            self._spill_worker.stop()
            self._spill_worker = None
        if self._armed:
            _xla_stats.disarm_serving_steady()
            self._armed = False
        self._drop_gauges()
        # drain under the SAME lock submit() enqueues under, and flip
        # started inside it: a submit racing this stop either lands
        # before the drain (failed here) or observes stopped and raises —
        # it can never strand an unserved stream in a dead queue
        with self._cond:
            failed = [s.stream for s in self._active.values()]
            failed += [j.stream for j in self._prefilling.values()]
            self._active.clear()
            self._prefilling.clear()
            pending = list(self._pending)
            self._pending.clear()
            # block ownership dies with the session+allocator the
            # next start() rebuilds — just drop the host-side tables
            self._slot_blocks.clear()
            self.started = False
        # the tokens already decided, before the streams are failed
        self._publish()
        err = ServingError("decode engine stopped")
        for stream in failed:
            stream._fail(err)
        for stream in pending:
            stream._fail(err)

    def __enter__(self):
        return self if self.started else self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- request path --------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens=None, eos_id=None,
               temperature=0.0, top_k=0, top_p=0.0, seed=None,
               resume_tokens=None, priority=None, tenant=None):
        """Non-blocking admission; returns a ``GenerationStream``.
        ``priority`` ("interactive" default / "batch") and ``tenant``
        are the scheduling identity: dequeue order is interactive-first
        then weighted-fair across tenants, and under
        ``FLAGS_sched_preempt`` a pending interactive request evicts a
        running batch stream (token-exactly re-admitted later).
        Bounded queue: beyond ``queue_depth`` waiting requests, sheds
        with ``ServerOverloadedError`` (same backpressure contract as
        the micro-batcher). Sampling knobs are per-request and host-side
        (``sample_token``): greedy (``temperature=0``) is the default,
        and a seeded sampling request replays deterministically.

        ``resume_tokens`` is the RESUME form: the suffix an interrupted
        run of this exact request (same prompt, knobs, seed) already
        emitted elsewhere. The engine re-prefills prompt + suffix — one
        admission through the prefix-index/chunked path, so the
        re-prefill costs table edits plus bucket windows, never a
        recompile — fast-forwards the request RNG past the replayed
        picks, and the returned stream emits exactly the tokens the
        uninterrupted run would have emitted from there on. A sampled
        request (temperature > 0) MUST carry its seed to be resumable:
        without one the continuation could not replay the original
        draws."""
        prompt = [int(t) for t in prompt_ids]
        if not prompt:
            raise ValueError("empty prompt")
        resume = [int(t) for t in (resume_tokens or [])]
        if resume:
            if temperature is not None and float(temperature or 0.0) > 0.0 \
                    and seed is None:
                raise ValueError(
                    "resume of a temperature-sampled generation requires "
                    "its seed (the replayed picks are otherwise "
                    "unreproducible)"
                )
            if eos_id is not None and int(eos_id) in resume:
                raise ValueError(
                    "resume_tokens already contain eos_id %d — the "
                    "generation is finished, not resumable" % int(eos_id)
                )
            if max_new_tokens is not None and max_new_tokens <= len(resume):
                raise ValueError(
                    "resume_tokens (%d) meet or exceed max_new_tokens "
                    "(%d) — nothing left to generate"
                    % (len(resume), max_new_tokens)
                )
        if not self.started or self.session is None:
            raise ServingError("decode engine not started")
        if len(prompt) + len(resume) >= self.session.max_len:
            if resume:
                # the resumed generation already hit the max_len wall:
                # it is COMPLETE, not invalid. Unlike the eos/max_new
                # refusals above (budgets the CALLER set and can check),
                # max_len is server-side config a resuming router cannot
                # know — a replica dying between its final token and the
                # done frame would otherwise turn a fully-delivered
                # generation into a 400. Answer with an already-finished
                # stream (zero continuation, finish_reason "length");
                # no slot, no queue entry, no admission tallies.
                stream = GenerationStream(
                    prompt, max_new_tokens=max_new_tokens, eos_id=eos_id,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    seed=seed, resume_tokens=resume,
                    priority=priority, tenant=tenant,
                )
                stream._finish("length")
                return stream
            raise ValueError(
                "prompt of %d tokens leaves no room to generate "
                "(max_len %d)" % (len(prompt), self.session.max_len)
            )
        # windows tile ANY prompt length under max_len: the ladder only
        # shapes window buckets, so there is no length to check against it
        if max_new_tokens is not None and max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        stream = GenerationStream(prompt, max_new_tokens=max_new_tokens,
                                  eos_id=eos_id, temperature=temperature,
                                  top_k=top_k, top_p=top_p, seed=seed,
                                  resume_tokens=resume,
                                  priority=priority, tenant=tenant)
        stream.submit_tick = self.tick
        with self._cond:
            # re-checked under the lock stop() drains under: after the
            # drain, started is already False here and the stream can
            # never be stranded in a dead queue
            if not self.started or self._stop:
                raise ServingError("decode engine stopped")
            if len(self._pending) >= self.queue_depth:
                raise ServerOverloadedError(
                    "decode admission queue full (%d pending)"
                    % len(self._pending),
                    retry_after_ms=50,
                )
            self._pending.append(stream)
            # inside the lock: _counts is read-modify-write from
            # arbitrary caller threads here (everything else touching it
            # is the loop thread)
            self._counts["requests"] += 1
            self._cond.notify_all()
        _profiler.bump_counter("decode_requests")
        return stream

    def generate(self, prompt_ids, max_new_tokens=None, eos_id=None,
                 temperature=0.0, top_k=0, top_p=0.0, seed=None,
                 resume_tokens=None, priority=None, tenant=None):
        """Submit and return the streaming handle (iterate for tokens as
        they land; ``.tokens()`` / ``.result()`` to block)."""
        return self.submit(prompt_ids, max_new_tokens=max_new_tokens,
                           eos_id=eos_id, temperature=temperature,
                           top_k=top_k, top_p=top_p, seed=seed,
                           resume_tokens=resume_tokens,
                           priority=priority, tenant=tenant)

    def set_spec_width(self, width):
        """Runtime speculation toggle: switch the fused step
        between its COMPILED widths — 1 (plain decode) and
        ``spec_tokens`` (the batched verify). Both programs are built
        and warmed at ``start()``, so this is an ops lever, not a
        recompile: a workload whose measured ``decode_spec_acceptance``
        makes drafting a net loss drops to width 1 without an engine
        restart (and back). Token streams are identical either way —
        the verify path's accept loop guarantees it."""
        w = int(width)
        if w != 1 and w != max(self.spec_tokens, 1):
            raise ValueError(
                "width %d not compiled (this engine has 1%s)"
                % (w, " and %d" % self.spec_tokens
                   if self.spec_tokens > 1 else "")
            )
        self._spec_width = w

    def stats(self):
        """THIS engine's counters + live occupancy snapshot (the
        process-global profiler counters additionally aggregate every
        other decode session in the process — e.g. greedy_generate's
        cached 1-slot sessions)."""
        out = {
            "slots": self.session.slots if self.session else 0,
            "active": len(self._active),
            "prefilling": len(self._prefilling),
            "queued": len(self._pending),
            "ticks": self.tick,
            "requests": self._counts["requests"],
            "prefills": self.session.prefills if self.session else 0,
            "steps": self.session.steps if self.session else 0,
            "tokens": self._counts["tokens"],
            "admissions": self._counts["admissions"],
            "retirements": self._counts["retirements"],
            "prefix_hits": self._counts["prefix_hits"],
            "prefix_misses": self._counts["prefix_misses"],
            "prefix_cached_tokens": self._counts["prefix_cached_tokens"],
            "resume_admissions": self._counts["resume_admissions"],
            "resume_tokens": self._counts["resume_tokens"],
            "spec_drafted": self._counts["spec_drafted"],
            "spec_accepted": self._counts["spec_accepted"],
            "oom_sheds": self._counts["oom_sheds"],
            "preemptions": self._counts["preemptions"],
            "preempt_replayed_tokens":
                self._counts["preempt_replayed_tokens"],
            "published_overlapped": self._counts["published_overlapped"],
            "published_exposed": self._counts["published_exposed"],
            "picks_on_device": self._counts["picks_on_device"],
            "picks_on_host": self._counts["picks_on_host"],
        }
        if self._counts["spec_drafted"]:
            out["spec_acceptance"] = (
                self._counts["spec_accepted"]
                / self._counts["spec_drafted"]
            )
        out["prompt_tokens"] = self._counts["prompt_tokens"]
        out["kv_bytes_per_token"] = self.kv_bytes_per_token
        out["state_bytes_per_slot"] = self.state_bytes_per_slot
        if self.allocator is not None:
            paged = self.allocator.stats()
            paged["block_size"] = self.block_size
            out["paged"] = paged
        if self.pindex is not None:
            out["prefix_store"] = self.pindex.stats()
        if self.host_store is not None:
            kv = self.host_store.stats()
            kv["readmit_tokens"] = self._counts["kv_readmit_tokens"]
            out["kv_tier"] = kv
        return out

    # -- engine loop ---------------------------------------------------------
    def _loop(self):
        while True:
            with self._cond:
                if self._idle():
                    with _trace.span("engine_wait", cat="serving",
                                     cpu=True):
                        while self._idle():
                            self._cond.wait()
                if self._stop:
                    return
            try:
                self._tick()
            except Exception as e:  # noqa: BLE001 - fail the live streams
                # a failed device step (incl. SteadyStateRecompileError
                # from the strict gate) fails the requests it was serving;
                # the engine itself stays up for the next submission. The
                # freed slots COUNT as retirements so the documented
                # admissions == retirements + occupancy invariant holds
                # across recovered failures (prefilling slots were never
                # counted as admissions, so they free without a tally)
                # Each stream gets the tokens decided before the failure,
                # then the error: the outbox keeps that order
                for idx, slot in list(self._active.items()):
                    slot.stream._fail(e, self._outbox)
                    self._release_slot_blocks(idx)
                    _profiler.bump_counter("serving_slot_retirements")
                    self._counts["retirements"] += 1
                self._free.extend(self._active.keys())
                self._active.clear()
                for idx, job in list(self._prefilling.items()):
                    job.stream._fail(e, self._outbox)
                    self._release_slot_blocks(idx)
                self._free.extend(self._prefilling.keys())
                self._prefilling.clear()
                self._publish()

    def _idle(self):
        """Nothing to do and not stopping (under ``_cond``)."""
        return (not self._stop and not self._pending
                and not self._active and not self._prefilling
                and not self._export_jobs)

    def _tick(self):
        """One engine tick: reap cancellations, admit queued requests
        (prefix-index table edit + their first window; short prompts
        finish admission inline, long ones become chunked jobs), advance ONE
        chunked-prefill window, then ONE fused decode step over every
        active slot. The chunk cap is the inter-token latency bound: a
        max-length prompt costs in-flight streams one bucket-shaped
        window per tick instead of a monolithic prefill stall.

        ``engine_tick`` is the tick's one parent span and the phase
        spans its children, which tile it: reap, admit, prefill, then in
        ``_step`` build, the device call (``decode_tick``) and
        sample + emit. No span per token and none per slot.

        What a tick decides for a stream (tokens, endings) it publishes
        under a device call: ``tick_publish`` runs inside the first one
        dispatched after the decision, between its ``executor_run`` and
        its ``executor_fetch``, which for the step's own tokens is the
        next tick's. Where no device call follows, at the tick's end."""
        counts = self._counts
        pub0 = (counts["published_overlapped"], counts["published_exposed"])
        with _trace.span("engine_tick", cat="serving", cpu=True,
                         tick=self.tick) as sp:
            # on the ticks whose spans read their thread's CPU clock, the
            # CPU clock of ALL the process's threads over the tick too
            process0 = time.process_time() if sp.cpu else None
            with _trace.span("tick_reap", cat="serving", cpu=True):
                self._drain_spill_done()
                self._serve_export_jobs()
                self._reap_cancelled()
            with _trace.span("tick_admit", cat="serving", cpu=True):
                self._admit()
            with _trace.span("tick_prefill", cat="serving", cpu=True):
                self._advance_prefills()
            if self._active:
                self._step()
            if not self._next_tick_carries():
                self._publish()
            if _trace.enabled():
                facts = self._occupancy()
                if process0 is not None:
                    facts["process_cpu_ms"] = (
                        time.process_time() - process0) * 1e3
                sp.note(published_overlapped=(
                            counts["published_overlapped"] - pub0[0]),
                        published_exposed=(
                            counts["published_exposed"] - pub0[1]),
                        **facts)

    def _next_tick_carries(self):
        """Whether what this tick decided after its last device call may
        wait for the next tick's first one. Only on the loop thread,
        whose next tick follows at once, and only while a stream is
        active or prefilling, so that the next tick makes a device call
        (the step, or a window). A caller that drives ``_tick()`` itself
        reads the streams when it returns; an engine going idle or
        stopping has no next call to ride."""
        return (threading.current_thread() is self._thread
                and not self._stop
                and bool(self._active or self._prefilling))

    def _publish_overlapped(self):
        """The session's ``while_device_runs``: a device call of this
        thread has been dispatched and its fetch is not yet waited for."""
        self._publish(overlapped=True)

    def _publish(self, overlapped=False):
        """Hand everything decided and not yet published to the streams'
        consumers, in the order it was decided (a stream's tokens, then
        its end): this is what wakes the gateway's handler threads and,
        through their writes, the clients. ``overlapped`` says whether a
        device call of this thread is in flight, for the two counters
        ``decode_tokens_published_overlapped`` / ``_exposed``."""
        box = self._outbox
        if not box:
            return
        key = "published_overlapped" if overlapped else "published_exposed"
        counts = self._counts
        with self._publish_lock, \
                _trace.span("tick_publish", cat="serving", cpu=True,
                            overlapped=overlapped) as sp:
            tokens, streams = 0, set()
            while box:
                stream, item = box.popleft()
                if item is not _SENTINEL:
                    # counted before it is handed over: a reader that
                    # holds a token finds it in ``stats()``
                    counts[key] += 1
                    tokens += 1
                stream._publish(item)
                streams.add(stream)
            sp.note(tokens=tokens, streams=len(streams))
        if not tokens:
            return
        if overlapped:
            _profiler.bump_counter("decode_tokens_published_overlapped",
                                   tokens)
        else:
            _profiler.bump_counter("decode_tokens_published_exposed",
                                   tokens)

    def _occupancy(self):
        """What a tick leaves behind, for its span: streams by state,
        KV blocks handed out against the pool, and the tokens they hold
        (``next_pos`` summed over the active slots)."""
        out = {
            "active": len(self._active),
            "prefilling": len(self._prefilling),
            "queued": len(self._pending),
            "live_tokens": sum(s.next_pos for s in self._active.values()),
        }
        # block 0 is the sink: never handed out
        total = self.allocator.blocks - 1
        out["blocks_total"] = total
        out["blocks_in_use"] = total - self.allocator.free_blocks
        out["kv_bytes_per_token"] = self.kv_bytes_per_token
        if self.state_bytes_per_slot:
            out["state_bytes_per_slot"] = self.state_bytes_per_slot
        return out

    def _reap_cancelled(self):
        """Retire slots whose consumer abandoned the stream (transport
        timeout / client disconnect) — BEFORE spending a prefill or a
        decode step on them. Freed slots count as retirements so the
        admissions == retirements + occupancy invariant holds. The
        PENDING queue is swept too: a request cancelled while queued
        must release its bounded-admission-queue entry immediately, not
        sit shedding live traffic with 429s until a slot frees."""
        for idx, slot in list(self._active.items()):
            if slot.stream._cancelled:
                self._active.pop(idx, None)
                self._free.append(idx)
                self._release_slot_blocks(idx)
                _profiler.bump_counter("serving_slot_retirements")
                self._counts["retirements"] += 1
                slot.stream._finish("cancelled", self._outbox)
        for idx, job in list(self._prefilling.items()):
            if job.stream._cancelled:
                # cancelled mid-chunked-prefill: the slot frees without a
                # retirement tally — admission is only counted when the
                # first token emits, which never happened
                self._prefilling.pop(idx, None)
                self._free.append(idx)
                self._release_slot_blocks(idx)
                job.stream._finish("cancelled", self._outbox)
        with self._cond:
            if any(s._cancelled for s in self._pending):
                live = deque()
                for s in self._pending:
                    if s._cancelled:
                        s._finish("cancelled", self._outbox)
                    else:
                        live.append(s)
                self._pending = live

    def _plan_windows(self, prompt_len, prefix_tokens):
        """Bucket-shaped window plan covering [prefix, prompt_len):
        returns (usable_prefix, [(start, end), ...]). Every window's
        bucket must land within max_len (``dynamic_update_slice`` would
        otherwise clamp-and-shift the write); when the trailing suffix's
        bucket cannot fit after the cached prefix, the prefix shrinks a
        block at a time (recompute beats corrupt). A custom bucket
        ladder too sparse to tile the prompt degrades to one monolithic
        window — never an error."""
        sess = self.session
        chunk = self.prefill_chunk
        prefix = prefix_tokens
        while prefix >= 0:
            s, wins, ok = prefix, [], True
            while s < prompt_len:
                cand = [b for b in sess.buckets if s + b <= sess.max_len]
                if not cand:
                    ok = False
                    break
                length = prompt_len - s
                if chunk:
                    length = min(length, chunk)
                length = min(length, max(cand))
                wins.append((s, s + length))
                s += length
            if ok:
                return prefix, wins
            prefix -= self.block_size
        return 0, [(0, prompt_len)]

    # -- scheduler (weighted-fair dequeue + priority preemption) -------------
    def _tenant_weight(self, tenant):
        """Weight from ``FLAGS_sched_tenant_weights`` ("a:4,b:1");
        unlisted tenants weigh 1. Parsed once per flags version."""
        ver = _flags.version()
        if ver != self._sched_weights_ver:
            self._sched_weights_ver = ver
            table = {}
            spec = str(_flags.get_flag("sched_tenant_weights", "") or "")
            for part in spec.split(","):
                name, sep, w = part.strip().rpartition(":")
                if not sep:
                    continue
                try:
                    table[name.strip()] = max(float(w), 1e-3)
                except ValueError:
                    continue
            self._sched_weights = table
        return self._sched_weights.get(tenant, 1.0)

    def _dequeue_locked(self):
        """Scheduler pick from the pending queue (caller holds _cond):
        interactive class strictly before batch; within a class,
        preemption-evicted re-admissions first (their fair share was
        charged at first admission), then weighted-fair across tenants
        — stride scheduling, each fresh dequeue advancing the tenant's
        virtual time by 1/weight, lowest virtual time next, FIFO within
        a tenant. One tenant alone degenerates to exact FIFO (the
        historical order). O(queue) scan per admission — the queue is
        bounded by ``queue_depth``."""
        if not self._pending:
            return None
        best_i = best_key = None
        for i, s in enumerate(self._pending):
            cls = 0 if getattr(s, "priority", "interactive") != "batch" \
                else 1
            replay = 0 if getattr(s, "preemptions", 0) else 1
            if replay:
                t = getattr(s, "tenant", "") or ""
                v = max(self._sched_vtime.get(t, 0.0), self._sched_vclock)
            else:
                v = -1.0
            key = (cls, replay, v, i)
            if best_key is None or key < best_key:
                best_key, best_i = key, i
        stream = self._pending[best_i]
        del self._pending[best_i]
        if best_key[1]:  # fresh admission: charge its tenant's stride
            t = getattr(stream, "tenant", "") or ""
            v = best_key[2]
            self._sched_vclock = v
            self._sched_vtime[t] = v + 1.0 / self._tenant_weight(t)
            if len(self._sched_vtime) > 4096:
                # tenant names are caller data: a pathological stream
                # of one-shot tenants must not grow this forever —
                # resetting loses only relative history
                self._sched_vtime.clear()
        return stream

    def _preempt_for_pending(self):
        """Tick boundary, no free slot: when ``FLAGS_sched_preempt`` is
        on and an interactive request is pending, evict one BATCH
        stream — a still-prefilling job first (nothing emitted, nothing
        to replay), else the active slot with the least cached work.
        The victim goes back to the FRONT of the pending queue; its
        re-admission re-prefills prompt + emitted tokens, so the
        continuation is token-exact (the stream object, its RNG state
        and emitted list survive eviction untouched). Returns True when
        a slot was freed."""
        if not bool(_flags.get_flag("sched_preempt", True)):
            return False
        with self._cond:
            wanting = any(
                not s._cancelled
                and getattr(s, "priority", "interactive") != "batch"
                for s in self._pending
            )
        if not wanting:
            return False
        victim_idx = victim = None
        from_active = False
        for idx, job in self._prefilling.items():
            if getattr(job.stream, "priority", "interactive") == "batch":
                victim_idx, victim = idx, job.stream
                break
        if victim_idx is None:
            best = None
            for idx, slot in self._active.items():
                if getattr(slot.stream, "priority",
                           "interactive") != "batch":
                    continue
                cost = len(slot.stream.full_prompt()) \
                    + len(slot.stream._tokens)
                if best is None or cost < best[0]:
                    best = (cost, idx, slot.stream)
            if best is not None:
                _cost, victim_idx, victim = best
                from_active = True
        if victim_idx is None:
            return False
        if from_active:
            self._active.pop(victim_idx, None)
            # an evicted ACTIVE stream was admitted, so its slot exit is
            # a retirement — the admissions == retirements + occupancy
            # invariant survives; its re-admission counts again
            _profiler.bump_counter("serving_slot_retirements")
            self._counts["retirements"] += 1
        else:
            self._prefilling.pop(victim_idx, None)
        self._free.append(victim_idx)
        self._release_slot_blocks(victim_idx)
        victim.preemptions += 1
        replayed = len(victim._tokens)
        _profiler.bump_counter("decode_preemptions")
        _profiler.bump_counter("decode_preempt_replayed_tokens", replayed)
        self._counts["preemptions"] += 1
        self._counts["preempt_replayed_tokens"] += replayed
        with self._cond:
            # FRONT of the queue, bypassing the depth bound: this is an
            # internal re-queue of an already-admitted request, not new
            # load — shedding it here would break the durability
            # contract
            self._pending.appendleft(victim)
        return True

    def _admission_prompt(self, stream):
        """Every token whose K/V must be in the slot's cache before the
        next pick: prompt + resume suffix + whatever this stream already
        emitted HERE. The last part is non-empty only for a
        preemption-evicted stream re-admitting — re-prefilling its own
        emissions is what makes eviction token-exact (same logits, and
        the stream's live RNG is already past all its picks)."""
        return stream.full_prompt() + [
            int(t) for t in getattr(stream, "_tokens", ()) or ()
        ]

    def _admit(self):
        """Admit queued requests into free slots — mid-flight, between
        decode steps, never evicting an active stream (except the
        explicit preemption path: with ``FLAGS_sched_preempt`` and no
        free slot, a pending interactive request evicts one batch
        stream). Dequeue order is the scheduler's (interactive class
        first, weighted-fair across tenants within a class), not raw
        FIFO.

        A prefix hit EDITS the slot's block table (matched store blocks
        incref'd straight in — no device copy, no recompute), fresh
        blocks cover exactly ``ceil(len(prompt)/block)`` minus the hit,
        and the suffix prefills through bucket-shaped windows fed the
        table: single-window prompts inline, longer ones as a chunked
        ``_PrefillJob`` advanced one window per tick. Slot HBM footprint
        is the prompt's ceil, not max_len. Pool exhaustion (after
        refcount-eviction of store-only blocks) sheds the request with
        the overload contract instead of corrupting a neighbor. The
        resume form re-prefills prompt + emitted suffix through the same
        machinery, which is what makes a resumed re-prefill cost ~one
        suffix window instead of a stall."""
        if not self._free:
            self._preempt_for_pending()
        while self._free:
            with self._cond:
                stream = self._dequeue_locked()
            if stream is None:
                return
            if getattr(stream, "t_dequeue", 0) is None:
                # the first dequeue only: a preempted stream's second
                # wait is the scheduler's doing, not the queue's
                stream.t_dequeue = time.perf_counter()
                stream.dequeue_tick = self.tick
            if stream._cancelled:
                # cancelled while queued: never admitted, so no slot,
                # no retirement tally — just finish the dead handle
                stream._finish("cancelled", self._outbox)
                continue
            slot_idx = self._free.pop()
            prompt = self._admission_prompt(stream)
            entries, hit_tokens = [], 0
            if self.pindex is not None:
                # lookup increfs each matched block — those references ARE
                # the slot's table entries on success
                entries, hit_tokens = self.pindex.lookup(prompt)
                if self.host_store is not None:
                    # chain ran past the device index: spilled (or pulled)
                    # blocks re-admit H2D instead of re-prefilling — each
                    # re-admitted entry joins ``entries`` with the same
                    # slot reference lookup hands out
                    entries = self._readmit_from_host(prompt, entries)
                    hit_tokens = len(entries) * self.block_size
            prefix_tokens, wins = self._plan_windows(len(prompt), hit_tokens)
            bs = self.block_size
            if prefix_tokens < hit_tokens:
                keep = prefix_tokens // bs
                self.allocator.decref([e.block_idx for e in entries[keep:]])
                entries = entries[:keep]
            blocks = [e.block_idx for e in entries]
            need = -(-len(prompt) // bs) - len(blocks)
            owned = self._alloc_blocks(need)
            if owned is None:
                if blocks:
                    self.allocator.decref(blocks)
                self._free.append(slot_idx)
                _profiler.bump_counter("decode_paged_oom_sheds")
                self._counts["oom_sheds"] += 1
                stream._fail(ServerOverloadedError(
                    "paged KV pool exhausted (%d blocks short after "
                    "eviction)" % need, retry_after_ms=50,
                ), self._outbox)
                continue
            self._slot_blocks[slot_idx] = blocks + owned
            stream.cached_prefix_tokens = prefix_tokens
            # denominator for the fleet cached-token fraction: every prompt
            # token admitted, hit or miss
            _profiler.bump_counter("decode_prompt_tokens", len(prompt))
            self._counts["prompt_tokens"] += len(prompt)
            if self.pindex is not None:
                if prefix_tokens:
                    _profiler.bump_counter("decode_prefix_hits")
                    _profiler.bump_counter("decode_prefix_cached_tokens",
                                           prefix_tokens)
                    self._counts["prefix_hits"] += 1
                    self._counts["prefix_cached_tokens"] += prefix_tokens
                else:
                    _profiler.bump_counter("decode_prefix_misses")
                    self._counts["prefix_misses"] += 1
            stream.admit_windows = len(wins)
            job = _PrefillJob(stream, wins, prefix_tokens)
            if len(wins) == 1:
                with _stream_scope(stream):
                    self._run_prefill_window(slot_idx, job)
            else:
                # chunked: the first window runs via _advance_prefills on
                # THIS tick; in-flight streams decode between windows. Same
                # stop/drain re-check as _active insertion: if stop()'s
                # drain ran meanwhile, parking the job now would strand the
                # stream in a dead engine
                with self._cond:
                    if self._stop or not self.started:
                        self._free.append(slot_idx)
                        self._release_slot_blocks(slot_idx)
                        stream._fail(ServingError("decode engine stopped"),
                                     self._outbox)
                        continue
                    self._prefilling[slot_idx] = job

    # -- block bookkeeping ---------------------------------------------------
    def _alloc_blocks(self, n):
        """Allocator take with prefix-store pressure relief: when the
        free list runs dry, evict store entries whose block the store
        alone references (each decref actually frees a block) and retry.
        With the host tier armed an eviction doesn't free immediately —
        the spill pin holds the block until its D2H read completes — so
        the retry loop also reaps completed spills, and when allocation
        is still short with spills in flight it waits (bounded) for the
        worker's current batch. None = genuinely out of memory — the
        caller sheds."""
        got = self.allocator.alloc(n)
        while got is None:
            progressed = self._drain_spill_done()
            if self.pindex is not None \
                    and self.pindex.evict_one(need_free=True):
                progressed = True
            if not progressed and self._spill_worker is not None \
                    and self._spill_worker.pending:
                self._spill_worker.drain(timeout=0.2)
                progressed = self._drain_spill_done()
            if not progressed:
                return None
            got = self.allocator.alloc(n)
        return got

    # -- fleet KV tier (kv_tier.py) ------------------------------------------
    def _pool_arrays(self):
        """Host views of every per-layer (K, V) pool tensor, snapshotted
        once per call: [(k_host, v_host)] in layer order. ``np.asarray``
        on a device-resident array is one D2H copy; on a host-resident
        scope value (post reset/readmit) it is a zero-copy view."""
        sess = self.session
        out = []
        for k_name, v_name in sess.kv_pool_names():
            out.append((np.asarray(sess.scope.get(k_name)),
                        np.asarray(sess.scope.get(v_name))))
        return out

    def _on_index_evict(self, victim):
        """Device-index eviction hook (loop thread, before the index
        decrefs): pin the victim's block with one extra reference and
        hand it to the spill worker. The pin keeps the allocator from
        re-issuing the block — and since no program ever writes a block
        it didn't allocate (COW covers shared writes), the row's bytes
        stay frozen for the worker's D2H read."""
        if self._spill_worker is None:
            return
        self.allocator.incref([victim.block_idx])
        self._spill_worker.submit(
            (victim.key, victim.prev, victim.tokens, victim.block_idx)
        )

    def _spill_batch(self, jobs):
        """Spill-worker body: ONE pool snapshot covers every queued
        eviction, then each victim's rows copy into the host store.
        Donation race: a concurrently dispatched step may invalidate the
        pool array mid-read (jax raises on a deleted donated buffer) —
        re-fetching from the scope retries against the replacement
        array, whose pinned rows hold identical bytes. Every block id
        returns through ``_spill_done`` even on failure, so a lost
        spill never leaks a pin."""
        try:
            pools = None
            for _attempt in range(8):
                try:
                    pools = self._pool_arrays()
                    break
                except Exception:  # noqa: BLE001 - donated mid-read
                    time.sleep(0.005)
            if pools is None:
                return
            for key, prev, tokens, blk in jobs:
                payload = [(k[blk].copy(), v[blk].copy())
                           for k, v in pools]
                self.host_store.put(key, prev, tokens, payload)
        finally:
            for job in jobs:
                self._spill_done.append(job[3])

    def _drain_spill_done(self):
        """Reap completed spills (loop thread): drop the pin the evict
        hook took — for a store-only block this is the decref that
        actually frees it. Returns True when any block was released."""
        freed = False
        while True:
            try:
                blk = self._spill_done.popleft()
            except IndexError:
                return freed
            self.allocator.decref([blk])
            freed = True

    def _readmit_from_host(self, prompt, entries):
        """Extend a device-index hit from the host tier: walk the
        prompt's chain past the device entries, and for every spilled
        block found, allocate a fresh pool block, write the payload H2D,
        and re-register it in the device index. Returns the extended
        entries list (each new entry carries the caller's slot
        reference, same contract as ``lookup``).

        The H2D write scatters only the hit rows into the device pool
        (a cached jax row-scatter — never an executor program, so the
        strict steady-state gate never fires), falling back to a host
        round-trip when the pool is host-resident. All hit blocks batch
        into one scatter per layer tensor: the cost scales with the
        re-admitted bytes, not the pool size."""
        bs = self.block_size
        usable = (len(prompt) - 1) // bs
        hits = []  # (host_entry, fresh_block_idx)
        prev = entries[-1].key if entries else 0
        for b in range(len(entries), usable):
            toks = tuple(prompt[b * bs:(b + 1) * bs])
            key = _block_hash(prev, toks)
            if self.pindex._entries.get(key) is not None:
                break  # raced back into the device index — rare; stop
            he = self.host_store.get(key, prev, toks)
            if he is None:
                break
            got = self._alloc_blocks(1)
            if got is None:
                break  # pool pressure: keep what we have, prefill rest
            hits.append((he, got[0]))
            prev = key
        if not hits:
            return entries
        sess = self.session
        names = sess.kv_pool_names()
        idx = np.array([blk for _he, blk in hits], np.int32)
        for li, (k_name, v_name) in enumerate(names):
            k_rows = np.stack([he.payload[li][0] for he, _b in hits])
            v_rows = np.stack([he.payload[li][1] for he, _b in hits])
            k_cur = sess.scope.get(k_name)
            v_cur = sess.scope.get(v_name)
            # big pools scatter on device (cost ∝ re-admitted rows);
            # small pools take the host row-write — the fixed dispatch
            # cost of the scatter ops would exceed a full-pool copy
            if hasattr(k_cur, "at") and k_cur.nbytes > (4 << 20):
                sess.scope.set(k_name, k_cur.at[idx].set(k_rows))
                sess.scope.set(v_name, v_cur.at[idx].set(v_rows))
            else:
                k_host = np.array(k_cur)
                v_host = np.array(v_cur)
                k_host[idx] = k_rows
                v_host[idx] = v_rows
                sess.scope.set(k_name, k_host)
                sess.scope.set(v_name, v_host)
        out = list(entries)
        for he, blk in hits:
            e = self.pindex.admit(he.key, he.prev, he.tokens, blk)
            if e is None:
                # index refused (full of slot-shared blocks): the block
                # still serves THIS admission — wrap a detached entry;
                # the slot's decref at retirement frees it
                e = _PrefixEntry(he.key, he.prev, he.tokens, blk)
            else:
                # index took the allocated ref; the slot needs its own
                self.allocator.incref([blk])
            self.host_store.note_readmit(he)
            _profiler.bump_counter("kv_tier_readmit_tokens", bs)
            self._counts["kv_readmits"] += 1
            self._counts["kv_readmit_tokens"] += bs
            out.append(e)
        return out

    def prefix_heads(self, k=None):
        """The replica's cache-affinity advertisement: up to ``k`` hot
        chain-head keys, device index first (newest-first), then host
        tier. Gateway-thread safe — both reads are lock-free copies and
        a stale head only costs the router a mis-score within its
        staleness bound."""
        if k is None:
            k = self.kv_advert_k
        k = int(k)
        if k <= 0 or self.pindex is None:
            return []
        heads = self.pindex.head_keys(k)
        if self.host_store is not None and len(heads) < k:
            seen = set(heads)
            try:
                host_keys = list(self.host_store._entries.keys())
            except RuntimeError:
                host_keys = []
            for key in reversed(host_keys):
                if key not in seen:
                    heads.append(key)
                    seen.add(key)
                if len(heads) >= k:
                    break
        return heads

    def estimate_cached_tokens(self, prompt_ids):
        """Approximate cached-token count for ``prompt_ids`` across the
        device index and host tier — the gateway's pull-or-not signal.
        Lock-free dict reads off the gateway thread: a racing eviction
        at worst skews the estimate, and the admission path re-verifies
        every link anyway."""
        if self.pindex is None:
            return 0
        bs = self.block_size
        prompt = list(prompt_ids)
        cached = 0
        prev = 0
        for b in range((len(prompt) - 1) // bs):
            toks = tuple(prompt[b * bs:(b + 1) * bs])
            key = _block_hash(prev, toks)
            try:
                e = self.pindex._entries.get(key)
            except RuntimeError:
                break
            if e is None and self.host_store is not None:
                e = self.host_store.get(key, prev, toks)
            if e is None:
                break
            cached += bs
            prev = key
        return cached

    def block_row_shape(self):
        """[r0, block, r1]: one block of a pool as it lies on the device
        (``models/cache_kinds.py``) — the geometry exported chain blocks
        are advertised under, and the one a pulled blob must name."""
        _require(self._model, "block_export")
        rows = {tuple(pool.shape(1, self.block_size)[1:])
                for pair in _cache_kinds.kv_pools(
                    self._model.cache_kinds(self._cfg)) for pool in pair}
        if len(rows) != 1:
            raise TypeError("the layers' pools differ in their row: %s"
                            % sorted(rows))
        return list(rows.pop())

    def offer_blocks(self, entries):
        """Inject chain blocks pulled from a prefill-role peer
        (gateway thread). They land in the thread-safe host store —
        the very next admission whose chain reaches them re-admits
        H2D through the standard spilled-block path, with the same
        verification. Returns the number of blocks accepted."""
        _require(self._model, "block_export")
        if self.host_store is None:
            return 0
        n = 0
        for key, prev, tokens, payload in entries:
            if self.host_store.put(key, prev, tokens, payload,
                                   tally=False):
                n += 1
        return n

    def request_export(self, prompt_ids, timeout=5.0):
        """Serialize the prompt's published chain blocks (prefill-role
        endpoint, gateway thread). The pool read must run on the loop
        thread — the single mutator — so this parks a job the tick
        serves and waits (bounded). Returns [(key, prev, tokens,
        payload)] in chain order, or None on timeout/stopped."""
        _require(self._model, "block_export")
        if not self.started or self.pindex is None:
            return None
        ev = threading.Event()
        box = {}
        with self._cond:
            if self._stop or not self.started:
                return None
            self._export_jobs.append((list(prompt_ids), ev, box))
            self._cond.notify_all()
        if not ev.wait(timeout):
            return None
        return box.get("entries")

    def _serve_export_jobs(self):
        """Loop-thread half of ``request_export``: read the chain's
        blocks out of the pool (one snapshot per tick serves every
        queued job) and hand the payloads back."""
        if not self._export_jobs:
            return
        pools = None
        while True:
            try:
                prompt, ev, box = self._export_jobs.popleft()
            except IndexError:
                return
            try:
                bs = self.block_size
                chain = []
                prev = 0
                for b in range(len(prompt) // bs):
                    toks = tuple(prompt[b * bs:(b + 1) * bs])
                    key = _block_hash(prev, toks)
                    e = self.pindex._entries.get(key)
                    if e is not None and (e.tokens != toks
                                          or e.prev != prev):
                        break  # collision squatting on the key
                    if e is not None:
                        if pools is None:
                            pools = self._pool_arrays()
                        blk = e.block_idx
                        payload = [(k[blk].copy(), v[blk].copy())
                                   for k, v in pools]
                    elif self.host_store is not None:
                        # already spilled: the payload is host-resident
                        # — serve it straight from the tier, no pool
                        # read at all
                        he = self.host_store.get(key, prev, toks)
                        if he is None:
                            break
                        payload = he.payload
                    else:
                        break
                    chain.append((key, prev, toks, payload))
                    prev = key
                box["entries"] = chain
            except Exception:  # noqa: BLE001 - export is best-effort
                box["entries"] = None
            finally:
                ev.set()

    def _release_slot_blocks(self, slot_idx):
        """Drop the slot's reference on every block its table holds —
        owned blocks free, prefix-shared blocks survive under the
        store's (or another slot's) remaining references: retirement is
        a refcount decrement."""
        blocks = self._slot_blocks.pop(slot_idx, None)
        if blocks:
            self.allocator.decref(blocks)

    def _ensure_writable(self, slot_idx, block_i):
        """Copy-on-write: if logical block ``block_i`` of the slot's
        table is shared (refs > 1), duplicate it into a fresh block and
        swap the table entry before this tick writes it. Block-aligned
        admission never shares a block any writer touches, so this is a
        defensive invariant, not a hot path."""
        blocks = self._slot_blocks[slot_idx]
        blk = blocks[block_i]
        if self.allocator.refs(blk) <= 1:
            return
        got = self._alloc_blocks(1)
        if got is None:
            raise ServerOverloadedError(
                "paged KV pool exhausted during copy-on-write",
                retry_after_ms=50,
            )
        with _xla_stats.serving_request_window():
            self.session.block_copy([blk], got)
        blocks[block_i] = got[0]
        self.allocator.decref([blk])

    def _trim_blocks(self, slot_idx, next_pos):
        """Speculative rollback by table edit: free the slot's blocks
        strictly past the one its next write position lands in — the
        rejected draft tail's K/V becomes unreferenced pool garbage
        (the step bias already never let anything attend to it)."""
        blocks = self._slot_blocks.get(slot_idx)
        keep = next_pos // self.block_size + 1
        if blocks and len(blocks) > keep:
            tail = blocks[keep:]
            del blocks[keep:]
            self.allocator.decref(tail)

    def _advance_prefills(self):
        """Run ONE window of ONE chunked-prefill job — oldest first.
        One bucket-shaped window per tick total is the tick bound:
        however many long prompts are queued, live streams pay at most
        (one window + one fused step) of latency per token."""
        if not self._prefilling:
            return
        slot_idx = next(iter(self._prefilling))
        job = self._prefilling[slot_idx]
        with _stream_scope(job.stream):
            self._run_prefill_window(slot_idx, job)

    def _run_prefill_window(self, slot_idx, job):
        """Advance ``job`` by one window; on the prompt's final window,
        finish admission: publish the prompt's blocks to the prefix
        store, emit the first token, and join the decode batch."""
        stream = job.stream
        prompt = self._admission_prompt(stream)
        s, e = job.windows[job.wi]
        try:
            with _xla_stats.serving_request_window():
                # every prefill is a table-fed window (a whole prompt =
                # a window at offset 0)
                logits = self.session.paged_window(
                    self._slot_blocks[slot_idx], prompt[s:e], s,
                    slot=slot_idx,
                )
            job.wi += 1
            if job.wi < len(job.windows):
                # re-park under the drain lock: a stop() whose
                # thread-join timed out may have drained _prefilling
                # while this window ran — re-inserting would strand
                # the stream (same race _active insertion guards)
                with self._cond:
                    if self._stop or not self.started:
                        self._prefilling.pop(slot_idx, None)
                        self._free.append(slot_idx)
                        self._release_slot_blocks(slot_idx)
                        stream._fail(ServingError("decode engine stopped"),
                                     self._outbox)
                        return
                    self._prefilling[slot_idx] = job
                return
            # pick() INSIDE the per-request guard: a poisoned sampling
            # request (e.g. a denormal temperature) must fail alone, not
            # escape to the loop's handler and take every co-batched
            # stream down with it
            tok = stream.pick(logits)
        except Exception as exc:  # noqa: BLE001 - per-request failure
            self._prefilling.pop(slot_idx, None)
            self._free.append(slot_idx)
            self._release_slot_blocks(slot_idx)
            stream._fail(exc, self._outbox)
            return
        self._prefilling.pop(slot_idx, None)
        if self.pindex is not None:
            # zero-copy publish: the store indexes the slot's OWN blocks
            # (one incref each) — no device program runs, so there is no
            # failure mode to unwind
            self.pindex.publish(prompt, self._slot_blocks[slot_idx])
        # a resume (or preemption re-) admission's budget accounting
        # continues the ORIGINAL request: every replayed token counts
        # as already generated — len(prompt) - len(prompt_ids) is the
        # resume suffix plus this stream's own pre-eviction emissions
        slot = _Slot(stream, tok, next_pos=len(prompt),
                     generated=1 + len(prompt) - len(stream.prompt_ids))
        with self._cond:
            # stop() drains under this lock and flips started inside
            # it: if the drain happened while the prefill above was
            # in flight (stop's thread-join timed out), inserting
            # now would strand the stream in a dead engine — fail it
            # here instead
            if self._stop or not self.started:
                self._free.append(slot_idx)
                self._release_slot_blocks(slot_idx)
                stream._fail(ServingError("decode engine stopped"),
                             self._outbox)
                return
            self._active[slot_idx] = slot
        _profiler.bump_counter("serving_slot_admissions")
        self._counts["admissions"] += 1
        if stream.resume_tokens:
            # the facts a failover probe reads: how many generations
            # were resumed here and how much emitted suffix they
            # replayed through the prefill path instead of re-decoding
            _profiler.bump_counter("decode_resume_admissions")
            _profiler.bump_counter("decode_resume_tokens",
                                   len(stream.resume_tokens))
            self._counts["resume_admissions"] += 1
            self._counts["resume_tokens"] += len(stream.resume_tokens)
        if stream.ttft_ms is None:
            stream.first_tick = self.tick
            stream.ttft_ms = (time.monotonic() - stream._t_submit) * 1e3
            _profiler.bump_histogram("decode_ttft_ms", stream.ttft_ms)
        # else: a preemption re-admission — the stream's REAL first
        # token was already stamped; re-stamping would inflate the
        # fleet TTFT SLI with scheduler wait
        self._emit(slot_idx, slot, tok)

    def _emit(self, slot_idx, slot, tok):
        """Stream one generated token and retire the slot if finished:
        the one place a served token enters its stream. Everything the
        next tick reads is decided here; the token and the ending reach
        the consumer when the outbox is published (``_publish``)."""
        stream = slot.stream
        stream._push(tok, self._outbox)
        stream.last_tick = self.tick
        now = time.monotonic()
        if stream._t_last_emit is not None:
            # the latency a live stream actually feels per token — what
            # chunked prefill bounds while long prompts admit
            _profiler.bump_histogram(
                "decode_intertoken_ms", (now - stream._t_last_emit) * 1e3
            )
        stream._t_last_emit = now
        _profiler.bump_counter("decode_tokens")
        self._counts["tokens"] += 1
        reason = None
        if stream.eos_id is not None and tok == stream.eos_id:
            reason = "eos"
        elif (stream.max_new_tokens is not None
              and slot.generated >= stream.max_new_tokens):
            reason = "length"
        elif len(stream.prompt_ids) + slot.generated >= self.session.max_len:
            reason = "length"
        if reason is not None:
            # pop, not del: a stop() whose thread-join timed out may have
            # drained _active concurrently
            self._active.pop(slot_idx, None)
            self._free.append(slot_idx)
            # retirement is a refcount decrement: owned blocks free,
            # published blocks live on under the store's reference
            self._release_slot_blocks(slot_idx)
            _profiler.bump_counter("serving_slot_retirements")
            self._counts["retirements"] += 1
            stream._finish(reason, self._outbox)

    def _traced_ids(self):
        """A fused tick decodes EVERY traced stream at once: its
        ``decode_tick`` span is annotated with the slots' trace ids
        (like the batcher's dispatch span) so each request's merged
        tree shows the ticks it rode — skipped entirely for untraced
        traffic (greedy_generate et al.) and when span recording is off
        (gateway streams always carry trace ids for the header/log
        round-trip, but a disarmed tracer must cost the tick loop
        nothing)."""
        if not _trace.enabled():
            return None
        return sorted({
            s.stream.trace_ctx[0] for s in self._active.values()
            if getattr(s.stream, "trace_ctx", None)
        })

    def _step(self):
        """One fused tick over every active slot — the plain
        decode step when speculation is off, or the batched VERIFY when
        ``decode_spec_tokens`` = k > 1: each slot's window is its
        pending token plus a k-1-token draft, ONE program scores all k
        positions, and the host accepts the longest emitted prefix that
        matches what sequential decoding would have said.

        Token-exactness: query j's logits are computed with positions
        <= next_pos+j holding exactly the window tokens, and the accept
        loop only consumes logits[j+1] after confirming the token at
        position next_pos+j+1 (draft j+1) equals the one it just
        emitted — so every consumed logits row is bitwise the row the
        sequential engine would have produced. Each EMITTED token costs
        exactly one pick (greedy: the step's own argmax of that row,
        fetched as an id, zero RNG draws; sampled, which is a stream
        that holds an RNG: ``pick`` over the row read back from the
        device, the PR 13 one-uniform inverse-CDF draw), so
        ``fast_forward_rng`` resume and seeded replay hold unchanged;
        ``decode_picks_on_device`` / ``_on_host`` count which way the
        step's tokens were picked. The rejected tail's K/V is
        dead weight the step bias never exposes; ``_trim_blocks`` rolls
        whole rejected blocks back by table edit."""
        sess = self.session
        width = self._spec_width
        with _trace.span("tick_build", cat="serving", cpu=True):
            built = self._build_step(width)
            tids = self._traced_ids()
        if built is None:
            return
        tokens, positions, tables, active, windows = built
        if tids:
            with _trace.span("decode_tick", cat="serving",
                             tick=self.tick, trace_ids=tids), \
                    _xla_stats.serving_request_window():
                ids = sess.paged_step_ids(tokens, positions, tables,
                                          active, width=width)
        else:
            with _xla_stats.serving_request_window():
                ids = sess.paged_step_ids(tokens, positions, tables,
                                          active, width=width)
        self.tick += 1
        with _trace.span("tick_sample_emit", cat="serving", cpu=True) as sp:
            ids = ids.tolist()
            total = on_host = 0
            for idx in list(self._active.keys()):
                slot = self._active[idx]
                win = windows[idx]
                emitted = 0
                failed = False
                rows = None
                for j in range(width):
                    try:
                        if slot.stream._rng is None:
                            tok = ids[idx][j]
                        else:
                            if rows is None:
                                rows = sess.step_logits(idx, width=width)
                            tok = slot.stream.pick(rows[j])
                            on_host += 1
                    except Exception as e:  # noqa: BLE001 - this stream
                        self._active.pop(idx, None)
                        self._free.append(idx)
                        self._release_slot_blocks(idx)
                        _profiler.bump_counter("serving_slot_retirements")
                        self._counts["retirements"] += 1
                        slot.stream._fail(e, self._outbox)
                        failed = True
                        break
                    emitted += 1
                    slot.next_pos += 1
                    slot.generated += 1
                    slot.pending_token = tok
                    self._emit(idx, slot, tok)
                    if idx not in self._active:
                        break  # retired: eos / length budget hit mid-window
                    if j < width - 1 and tok != win[j + 1]:
                        break  # draft diverged — the tail is dead weight
                if width > 1 and not failed:
                    drafted = width - 1
                    accepted = max(emitted - 1, 0)
                    _profiler.bump_counter("decode_spec_drafted", drafted)
                    _profiler.bump_counter("decode_spec_accepted",
                                           accepted)
                    self._counts["spec_drafted"] += drafted
                    self._counts["spec_accepted"] += accepted
                    slot.stream.spec_drafted += drafted
                    slot.stream.spec_accepted += accepted
                if idx in self._active:
                    self._trim_blocks(idx, slot.next_pos)
                total += emitted
            on_device = total - on_host
            if on_device:
                _profiler.bump_counter("decode_picks_on_device", on_device)
                self._counts["picks_on_device"] += on_device
            if on_host:
                _profiler.bump_counter("decode_picks_on_host", on_host)
                self._counts["picks_on_host"] += on_host
            sp.note(tokens=total)

    def _build_step(self, width):
        """Before the device call of a tick: grow and unshare the
        slots' block tables, draft, and lay out the step's arguments.
        -> (tokens, positions, tables, active, windows), or None when
        every slot was shed."""
        sess = self.session
        bs = self.block_size
        # grow each active slot's table through this window's last
        # write; a slot the pool cannot cover (even after store
        # eviction) sheds with the overload contract
        for idx, slot in list(self._active.items()):
            need = (slot.next_pos + width - 1) // bs + 1
            blocks = self._slot_blocks[idx]
            shed = None
            if need > len(blocks):
                got = self._alloc_blocks(need - len(blocks))
                if got is None:
                    shed = ServerOverloadedError(
                        "paged KV pool exhausted mid-generation",
                        retry_after_ms=50,
                    )
                else:
                    blocks.extend(got)
            if shed is None:
                try:
                    for bi in range(slot.next_pos // bs, need):
                        self._ensure_writable(idx, bi)
                except Exception as exc:  # noqa: BLE001 - shed this slot
                    shed = exc
            if shed is not None:
                self._active.pop(idx, None)
                self._free.append(idx)
                self._release_slot_blocks(idx)
                _profiler.bump_counter("serving_slot_retirements")
                self._counts["retirements"] += 1
                _profiler.bump_counter("decode_paged_oom_sheds")
                self._counts["oom_sheds"] += 1
                slot.stream._fail(shed, self._outbox)
        if not self._active:
            return None
        tokens = np.zeros((sess.slots, width), "int64")
        positions = [0] * sess.slots
        active = [False] * sess.slots
        tables = [()] * sess.slots
        windows = {}
        for idx, slot in self._active.items():
            win = [slot.pending_token]
            if width > 1:
                hist = slot.stream.full_prompt() + slot.stream._tokens
                win += self._drafter(hist, width - 1)
            windows[idx] = win
            tokens[idx, :] = win
            positions[idx] = slot.next_pos
            active[idx] = True
            tables[idx] = self._slot_blocks[idx]
        # idle AND mid-prefill slots keep the all-sink default table:
        # their scatter-writes land in reserved block 0, so there is no
        # write position to aim
        return tokens, positions, tables, active, windows
