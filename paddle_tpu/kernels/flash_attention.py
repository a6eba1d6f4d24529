"""Flash attention as a Pallas TPU kernel — forward AND backward.

The hot op of every transformer (reference target: the CUDA
`multihead_matmul` fused kernel, fused_multihead_matmul_op.cu, built for
exactly this BERT attention pattern). A naive attention materializes the
[S, S] score matrix in HBM twice per direction — at seq 384+ that dwarfs
the useful traffic. These kernels keep the whole
softmax(QK^T·scale + bias)V pipeline in VMEM in both directions:

forward (online softmax, per (head, q-block) program):
  for each K/V block:  m' = max(m, rowmax(s))
                       acc = acc·e^(m-m') + e^(s-m') @ v_blk
                       l   = l·e^(m-m') + rowsum(e^(s-m'))
  o = acc / l;  lse = m + log(l)          (lse saved for the backward)

backward (two kernels, scores recomputed blockwise from q,k + lse — the
standard FlashAttention backward):
  delta = rowsum(dO ∘ O)                  (== rowsum(dP ∘ P), so the
                                           softmax jacobian needs no [S,S])
  p  = e^(s − lse)
  dq-kernel  (per q-block, sweep kv):  ds = p ∘ (dO V^T − delta)
                                       dq += ds @ K · scale
  dkv-kernel (per kv-block, sweep q):  dv += p^T @ dO
                                       dk += ds^T @ (q·scale)
                                       d(bias) accumulated blockwise

Layout [B, N, S, D] (batch, heads, seq, head_dim); fp32 accumulation
regardless of input dtype (MXU ``preferred_element_type``).

Which (q-block ib, kv-block kb) pairs a kernel visits is decided when it
is traced, from ``causal``, the padded lengths and the block sizes. Under
a causal mask a pair is one of three classes (``_block_class``):
- dead: the block's first column lies past its last row
  (``kb·BK > ib·BQ + BQ − 1``). Not computed: it would add exact zeros
  (p = e^(−1e30 − m) = 0), so leaving it out changes no bit of an output.
- interior: the block's last column is at or before its first row
  (``kb·BK + BK − 1 <= ib·BQ``). Computed with no causal mask at all.
- diagonal: the rest (8 of the 36 live pairs at seq 1024 with
  128-blocks). Computed with the mask.
Without ``causal`` every pair is interior. Every sweep is unrolled with a
Python-int trip count and holds no branch: a dynamic-trip loop compiles
far worse here (PERF_HISTORY.md "Negative result"), and so does a block
body under a predicate on a program id — the scheduler overlaps one
block's matmul latency with its neighbours' softmax only inside
straight-line code (PERF.md §6, PR 43). So a pair's class has to be known
at trace time, which it is where BOTH its indices are Python ints: a
causal call whose head has at most ``WHOLE_HEAD_BLOCKS`` blocks a side
(and no general bias) runs one program a head over all the head's blocks
(``_whole_head``) and unrolls the live pairs alone, 36 of 64 at seq 1024.
Where one index is a program id (longer sequences, a general bias, no
``causal``) a program holds one block as before and a causal pair is
computed with the mask, whatever its class.
One edge: a query row whose every visible key carries a key bias of
−1e30 (a wholly padded row) has no defined attention. It spreads its
weight over the keys of the blocks it visits, which with dead blocks
skipped are fewer than all ``Sk``; garbage either way, which the caller
slices or masks away.

Bias comes in two flavors, usable together:
- ``key_bias`` [B*N, Sk]: additive per KEY (BERT padding masks) —
  broadcast over query rows inside the kernel; gradient accumulated to
  the same [B*N, Sk] shape in the dkv kernel.
- ``bias``: a general additive tensor broadcastable to [B, N, Sq, Sk]
  (relative-position tables, ALiBi slopes). Normalized to [G, Sq, Sk]
  with G ∈ {1, B, B·N}; flat head h reads row h // (B·N // G), so heads
  sharing a row are CONSECUTIVE, and the dkv grid is transposed (kv-block
  axis outermost, head axis innermost) so its gradient block is revisited
  by consecutive programs — the TPU grid is a sequential loop, which
  makes blockwise accumulation across programs well-defined. A per-head
  bias shared across the batch ([1, N, Sq, Sk]) is handled by running
  the whole attention head-major (role swap B↔N in ``flash_attention``).

The kernels run when the trace lowers for the TPU (``lowers_for_tpu``) or
anywhere under ``interpret=True`` (tests); a trace that lowers for another
backend takes the jnp reference, so models stay portable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_Q = 128
BLOCK_K = 128
_NEG = -1e30

# splitmix32-style avalanche constants for the stateless dropout hash
_H1, _H2, _H3 = 0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D
_M1, _M2 = 0x2C1B3C6D, 0x297A2D39


def _hash_keep(rows, cols, head, seed_u32, rate):
    """Deterministic keep-mask from ABSOLUTE (row, col) coordinates, the
    flat head index and a per-call seed — a counter-based splitmix32-style
    scramble, so the forward kernel, both backward kernels and the dense
    fallback all regenerate bit-identical masks with no stored [S, S]
    tensor. ``rows``/``cols`` are broadcast-compatible int32 arrays;
    ``head`` may be a traced scalar (pl.program_id) or an array."""
    u = jnp.uint32
    n = (
        rows.astype(u) * u(_H1)
        + cols.astype(u) * u(_H2)
        + (seed_u32 + jnp.asarray(head, u) * u(_H3))
    )
    n = n ^ (n >> u(15))
    n = n * u(_M1)
    n = n ^ (n >> u(12))
    n = n * u(_M2)
    n = n ^ (n >> u(15))
    # keep iff hash < keep_prob * 2^32 (threshold is static)
    thresh = int((1.0 - float(rate)) * 4294967296.0)
    return n < u(min(thresh, 4294967295))


def lowers_for_tpu():
    """Kernel or reference, decided once per trace: by the backend the
    executor (or dygraph tracer) is lowering FOR, which it resolved from
    its Place — a CPUPlace program on a chip host takes the reference, a
    TPUPlace program takes the kernels and a kernel the chip's compiler
    refuses raises. Called outside any fluid trace (a bare
    ``flash_attention`` under the caller's own jit), jax's default
    backend is the only target there is."""
    from ..fluid.ops.registry import lowering_backend

    return (lowering_backend() or jax.default_backend()) == "tpu"


def reference_attention(q, k, v, bias=None, causal=False, scale=None):
    """Pure-jnp oracle, [B, N, S, D]; bias broadcastable to [B, N, S, S]."""
    d = q.shape[-1]
    s = jnp.einsum("bnqd,bnkd->bnqk", q, k).astype(jnp.float32)
    s = s * (scale if scale is not None else 1.0 / np.sqrt(d))
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        S = q.shape[2]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnqk,bnkd->bnqd", p.astype(q.dtype), v)


def _scores(q, kblk, scale, key_bias_row, bias_blk, row_off, col_off,
            causal, block_q, block_k):
    """[BQ, BK] masked scores. ``q``/``kblk`` stay in their INPUT dtype:
    the MXU runs bf16×bf16→fp32 at full rate but fp32×fp32 at a fraction
    of it, so the dot takes the raw operands and only the accumulator is
    fp32 (``preferred_element_type``); the softmax scale lands on the
    fp32 scores. ``key_bias_row`` is a [1, BK] row that broadcasts over
    query rows. ``causal`` says whether THIS block takes the causal mask
    (``_block_class``: an interior block is told "no mask"). Shared by all
    three kernels so forward and backward can never disagree on masking."""
    s = jax.lax.dot_general(
        q, kblk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s = s * scale + key_bias_row
    if bias_blk is not None:
        s = s + bias_blk.astype(jnp.float32)
    if causal:
        row, col = _block_coords(row_off, col_off, block_q, block_k)
        s = jnp.where(col <= row, s, _NEG)
    return s


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------


def _block_coords(row_off, col_off, block_q, block_k):
    rows = row_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = col_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return rows, cols


def _hash_head(h, head_swap):
    """Flat head index in the CALLER's [B, N] layout for the dropout hash.
    Under the head-major role swap (per-head shared bias) the kernels run
    with heads flattened as n·B + b; remapping to b·N + n keeps the mask
    bit-identical to the unswapped kernels and the dense fallback, so the
    swap never changes which attention entries drop."""
    if head_swap is None:
        return h
    B0, N0 = head_swap
    return (h % B0) * N0 + h // B0


DEAD, INTERIOR, DIAGONAL = "dead", "interior", "diagonal"

# q blocks (kv blocks in the dkv kernel) one program may hold: with every
# block of a head in one program both indices of a block pair are Python
# ints, and the kernel unrolls the live pairs alone: 36 bodies at 8 x 8
# blocks, about what the full 32-block sweep of a seq-4096 program unrolls
WHOLE_HEAD_BLOCKS = 8
# and the bytes of operands it may hold, half of what a v5e program may
# claim by default (the pipeline holds every operand twice)
WHOLE_HEAD_BYTES = 8 << 20


def _block_class(causal, ib, kb, block_q, block_k):
    """Class of the (q-block ``ib``, kv-block ``kb``) pair under a causal
    mask (module docstring). An index that is not a Python int (a program
    id) leaves the class open at trace time: the block is computed with
    the mask, which is right for all three."""
    if not causal:
        return INTERIOR
    if not (isinstance(ib, int) and isinstance(kb, int)):
        return DIAGONAL
    if kb * block_k > ib * block_q + block_q - 1:
        return DEAD         # first column past the last row
    if kb * block_k + block_k - 1 <= ib * block_q:
        return INTERIOR     # last column at or before the first row
    return DIAGONAL


def block_classes(causal, q_len, kv_len, block_q, block_k):
    """{class: number of (q-block, kv-block) pairs of one head in it}."""
    counts = {DEAD: 0, INTERIOR: 0, DIAGONAL: 0}
    for ib in range(q_len // block_q):
        for kb in range(kv_len // block_k):
            counts[_block_class(causal, ib, kb, block_q, block_k)] += 1
    return counts


def _whole_head(causal, bias, geom, d, itemsize):
    """Whether a program holds every block of its head, so that the
    kernels see each pair's class at trace time and visit the live pairs
    alone. Decided from what the trace can see: only a causal mask leaves
    dead blocks; the head's triangle has to be small enough to unroll; a
    general bias would ride in as the head's whole [Sq, Sk] table; and
    the head's rows have to fit the program's VMEM (the dkv program holds
    the most: q, dO, k, v, dk, dv and the lse / delta columns, an
    [S, 1] fp32 column padding to 512 B a row)."""
    _, _, _, _, q_len, kv_len, block_q, block_k = geom
    held = (2 * q_len + 4 * kv_len) * d * itemsize + 2 * q_len * 512
    return (causal and bias is None
            and 1 < q_len // block_q <= WHOLE_HEAD_BLOCKS
            and 1 < kv_len // block_k <= WHOLE_HEAD_BLOCKS
            and held <= WHOLE_HEAD_BYTES)


def _count_blocks(causal, bias, q, k, kernels):
    """Bookkeeping of one call of ``kernels`` kernels, made where the call
    is traced: block pairs a head they visit / would visit with nothing
    skipped. (A program a block visits every pair, whatever the mask.)"""
    from ..observability import registry

    geom = _geometry(q, k)
    whole = _whole_head(causal, bias, geom, q.shape[-1], q.dtype.itemsize)
    classes = block_classes(whole, *geom[4:])
    total = sum(classes.values())
    registry.counter("flash_blocks_visited").inc(
        kernels * (total - classes[DEAD]))
    registry.counter("flash_blocks_total").inc(kernels * total)


def _fwd_kernel(q_ref, k_ref, v_ref, key_bias_ref, bias_ref, seed_ref,
                o_ref, lse_ref, *, scale, causal, kv_len, block_q, block_k,
                dropout_rate, head_swap=None, q_blocks=1):
    """One (head, q-block) program, or with ``q_blocks`` > 1 one program a
    head over all its q blocks: online softmax over the kv blocks the
    causal mask leaves a q block; also writes the per-row logsumexp
    residual for the backward. Dropout masks the accumulated probabilities
    only — ``l``/``lse`` stay unmasked, so out = (1/keep)·Σ_j
    mask_ij·P_ij·V_j (standard non-renormalizing dropout) and the
    backward's rowsum(dO∘O) trick still yields delta."""
    from jax.experimental import pallas as pl

    n_kb = kv_len // block_k
    # (statements keep the order they had before a program could hold more
    # than one block, so a one-block program traces to the same op stream)
    for a in range(q_blocks):
        qs = slice(a * block_q, (a + 1) * block_q)
        q = q_ref[0, qs, :]                       # [BQ, D], input dtype
        h = pl.program_id(0)
        # the q block's index: a Python int where the program holds them all
        ib = a if q_blocks > 1 else pl.program_id(1)
        # read the SMEM seed only when dropout is live: the rate-0 kernel
        # traces to exactly the pre-dropout op stream (the operand is
        # still bound, just never loaded)
        seed_u = (seed_ref[0, 0].astype(jnp.int32).astype(jnp.uint32)
                  if dropout_rate > 0.0 else None)

        m = jnp.full((block_q, 1), _NEG, jnp.float32)
        l = jnp.zeros((block_q, 1), jnp.float32)
        acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

        for kb in range(n_kb):
            cls = _block_class(causal, ib, kb, block_q, block_k)
            if cls == DEAD:
                continue
            ks = slice(kb * block_k, (kb + 1) * block_k)
            s = _scores(
                q, k_ref[0, ks, :], scale, key_bias_ref[0, :, ks],
                None if bias_ref is None else bias_ref[0, :, ks],
                ib * block_q, kb * block_k, cls == DIAGONAL,
                block_q, block_k,
            )
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + p.sum(axis=-1, keepdims=True)
            if dropout_rate > 0.0:
                rows, cols = _block_coords(
                    ib * block_q, kb * block_k, block_q, block_k
                )
                p = jnp.where(
                    _hash_keep(rows, cols, _hash_head(h, head_swap), seed_u,
                               dropout_rate),
                    p, 0.0,
                )
            # p rounds to the value dtype for the MXU (as the dense
            # reference does with p.astype(q.dtype) @ v); accumulation
            # stays fp32
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, ks, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m = m_new
        l_safe = jnp.maximum(l, 1e-30)
        if dropout_rate > 0.0:
            l_safe = l_safe * (1.0 - dropout_rate)
        o_ref[0, qs, :] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[0, qs, :] = m + jnp.log(jnp.maximum(l, 1e-30))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, key_bias_ref, bias_ref, do_ref,
                   lse_ref, delta_ref, seed_ref, dq_ref, *, scale, causal,
                   kv_len, block_q, block_k, dropout_rate, head_swap=None,
                   q_blocks=1):
    """One (head, q-block) program, or one a head over all its q blocks
    (``q_blocks`` > 1): dq = Σ_kv (p∘(dO V^T − delta)) K·scale over the kv
    blocks the causal mask leaves. With dropout the mask/keep lands on dp
    (= d out/d P path); p itself stays unmasked — that IS the softmax
    jacobian of the dropped output."""
    from jax.experimental import pallas as pl

    n_kb = kv_len // block_k
    inv_keep = 1.0 / (1.0 - dropout_rate) if dropout_rate > 0.0 else 1.0
    for a in range(q_blocks):
        qs = slice(a * block_q, (a + 1) * block_q)
        q = q_ref[0, qs, :]                         # [BQ, D], input dtype
        do = do_ref[0, qs, :]                       # [BQ, D], input dtype
        lse = lse_ref[0, qs, :]                     # [BQ, 1]
        delta = delta_ref[0, qs, :]                 # [BQ, 1]
        h = pl.program_id(0)
        ib = a if q_blocks > 1 else pl.program_id(1)
        # read the SMEM seed only when dropout is live: the rate-0 kernel
        # traces to exactly the pre-dropout op stream (the operand is
        # still bound, just never loaded)
        seed_u = (seed_ref[0, 0].astype(jnp.int32).astype(jnp.uint32)
                  if dropout_rate > 0.0 else None)

        dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
        for kb in range(n_kb):
            cls = _block_class(causal, ib, kb, block_q, block_k)
            if cls == DEAD:
                continue
            ks = slice(kb * block_k, (kb + 1) * block_k)
            kblk = k_ref[0, ks, :]                  # [BK, D], input dtype
            s = _scores(
                q, kblk, scale, key_bias_ref[0, :, ks],
                None if bias_ref is None else bias_ref[0, :, ks],
                ib * block_q, kb * block_k, cls == DIAGONAL,
                block_q, block_k,
            )
            p = jnp.exp(s - lse)                    # [BQ, BK]
            dp = jax.lax.dot_general(               # dO @ V^T
                do, v_ref[0, ks, :].astype(do.dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if dropout_rate > 0.0:
                rows, cols = _block_coords(
                    ib * block_q, kb * block_k, block_q, block_k
                )
                dp = jnp.where(
                    _hash_keep(rows, cols, _hash_head(h, head_swap), seed_u,
                               dropout_rate),
                    dp * inv_keep, 0.0,
                )
            # ds rounds to the key dtype for the MXU (standard flash
            # backward); fp32 accumulation via preferred_element_type
            ds = p * (dp - delta)
            dq = dq + jax.lax.dot_general(          # ds @ K
                ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        dq_ref[0, qs, :] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, key_bias_ref, bias_ref, do_ref,
                    lse_ref, delta_ref, seed_ref, dk_ref, dv_ref, dkb_ref,
                    dbias_ref, *, scale, causal, q_len, block_q, block_k,
                    bias_group, dropout_rate, head_swap=None,
                    head_major=False, kv_blocks=1):
    """One (kv-block, head) program, or one a head over all its kv blocks
    (``kv_blocks`` > 1, head-major), sweeping the q blocks the causal mask
    leaves a kv block. Two grid orders:

    - shared-bias path (``head_major=False``): TRANSPOSED grid, kv axis
      outermost / head axis innermost, so the shared-bias gradient block
      is revisited by consecutive programs (safe sequential accumulation
      on TPU);
    - KeyBias-only path (``head_major=True``): head axis outermost, so
      the full q/dO row blocks (index maps keyed on the head only) are
      REUSED across the inner kv sweep instead of refetched from HBM on
      every program — at seq 4096 that's ~1 MB of q+dO per program saved."""
    from jax.experimental import pallas as pl

    if head_major:
        h = pl.program_id(0)    # flat head index
        kv = pl.program_id(1)   # kv-block index
    else:
        kv = pl.program_id(0)   # kv-block index
        h = pl.program_id(1)    # flat head index
    n_qb = q_len // block_q
    inv_keep = 1.0 / (1.0 - dropout_rate) if dropout_rate > 0.0 else 1.0
    for b in range(kv_blocks):
        ks = slice(b * block_k, (b + 1) * block_k)
        # the kv block's index: a Python int where the program holds them all
        kj = b if kv_blocks > 1 else kv
        k = k_ref[0, ks, :]                         # [BK, D], input dtype
        v = v_ref[0, ks, :]                         # [BK, D], input dtype
        key_bias_row = key_bias_ref[0, :, ks]       # [1, BK]
        # read the SMEM seed only when dropout is live: the rate-0 kernel
        # traces to exactly the pre-dropout op stream (the operand is
        # still bound, just never loaded)
        seed_u = (seed_ref[0, 0].astype(jnp.int32).astype(jnp.uint32)
                  if dropout_rate > 0.0 else None)

        dk = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
        dv = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
        dkb = jnp.zeros((1, block_k), jnp.float32)
        # per-q-block ds tiles of the general-bias gradient column block;
        # joined on the (tile-aligned) row axis at the end — Mosaic has no
        # dynamic_update_slice on values
        ds_blocks = []

        for ib in range(n_qb):
            cls = _block_class(causal, ib, kj, block_q, block_k)
            if cls == DEAD:
                continue
            qs = slice(ib * block_q, (ib + 1) * block_q)
            q = q_ref[0, qs, :]                     # [BQ, D], input dtype
            do = do_ref[0, qs, :]                   # [BQ, D], input dtype
            lse = lse_ref[0, qs, :]                 # [BQ, 1]
            delta = delta_ref[0, qs, :]             # [BQ, 1]
            s = _scores(
                q, k, scale, key_bias_row,
                None if bias_ref is None else bias_ref[0, qs, :],
                ib * block_q, kj * block_k, cls == DIAGONAL,
                block_q, block_k,
            )
            p = jnp.exp(s - lse)                    # [BQ, BK]
            dp = jax.lax.dot_general(               # dO @ V^T
                do, v.astype(do.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # fp32 intermediates round to the operand dtype for the MXU;
            # accumulators (dk/dv/dkb/dbias) stay fp32
            if dropout_rate > 0.0:
                rows, cols = _block_coords(
                    ib * block_q, kj * block_k, block_q, block_k
                )
                keep = _hash_keep(rows, cols, _hash_head(h, head_swap),
                                  seed_u, dropout_rate)
                dv = dv + jax.lax.dot_general(      # (mask∘p/keep)^T @ dO
                    jnp.where(keep, p * inv_keep, 0.0).astype(do.dtype), do,
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dp = jnp.where(keep, dp * inv_keep, 0.0)
            else:
                dv = dv + jax.lax.dot_general(      # p^T @ dO
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            ds = p * (dp - delta)
            dk = dk + jax.lax.dot_general(          # ds^T @ q (·scale at write)
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dkb = dkb + ds.sum(axis=0, keepdims=True)
            if dbias_ref is not None:
                ds_blocks.append(ds)

        dk_ref[0, ks, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, ks, :] = dv.astype(dv_ref.dtype)
        dkb_ref[0, :, ks] = dkb
    if dbias_ref is not None:
        dbias = jnp.concatenate(ds_blocks, axis=0)     # [q_len, BK]

        # heads h with equal h // bias_group share one gradient row;
        # they are consecutive on the (innermost) head axis
        @pl.when(h % bias_group == 0)
        def _init():
            dbias_ref[0] = dbias

        @pl.when(h % bias_group != 0)
        def _accumulate():
            dbias_ref[0] += dbias


# keys a paged-decode program takes at least: one lane tile of scores, so
# the online softmax touches its scratch once per PAGED_KEYS keys and a
# program's DMAs are large enough to be worth their descriptors
PAGED_KEYS = 128


def _live_blocks(length, block):
    """Table entries of a slot that hold a live key: ceil(length / block)
    (``flash_decode_paged_attention`` holds a length to 1 .. the table's
    keys, so at least one — an inactive slot still reads one sink block —
    and at most the table)."""
    return (length + block - 1) // block


def _held_blocks(tables, lengths, block, pages):
    """[B, programs*pages] int32: the PHYSICAL block that K/V operand
    j = e % pages of a slot's program i = e // pages holds. Table entry e
    itself while it is live; past the live entries the operand stays on
    the last live block it held (or, never having held one, on the slot's
    last live block) — an index that does not change from one program to
    the next is not fetched again, and a dead entry's own block is never
    read. Computed once a call, outside the kernel: the index maps only
    look it up."""
    MB = tables.shape[1]
    last = _live_blocks(lengths, block)[:, None] - 1
    e = jnp.arange(-(-MB // pages) * pages, dtype=jnp.int32)[None, :]
    j = e % pages
    held = jnp.where(j <= last, last - (last - j) % pages, last)
    return jnp.take_along_axis(tables, jnp.minimum(e, held), axis=1)


def _decode_paged_kernel(held_ref, lengths_ref, q_ref, *refs, scale,
                         pages, d_head, per_head):
    """Paged decode step, one (slot, group of ``pages`` logical blocks)
    program over ALL heads of the slot. A token's keys (and values) are
    ONE row of the pool, its heads side by side on the lanes, so the
    heads are the ROWS of one product, as in ``_mla_decode_paged_kernel``:
    the block-diagonal query [heads, hidden] (row h holds ``q_h`` on head
    h's lanes, zeros elsewhere) against the program's keys [keys, hidden]
    gives the [heads, keys] scores, and ``p . V`` [heads, hidden] holds
    head h's answer on row h's own ``d_head`` lanes (the other lanes of a
    row mix heads and are dropped in ``_emit``). The zeros add nothing to
    any sum. The pool rides in ``pages`` times for K and ``pages`` times
    for V; operand j's index map looks up the slot's block table (scalar
    prefetch, as ``_held_blocks`` laid it out) for logical block
    i*pages + j, so the pipeline's own DMAs chase the indirection and
    each pulls the [block, hidden] of one physical block in one piece.
    The slot's live length rides beside the table: a logical block past
    the last live one is not fetched (its entry names a block the operand
    already holds) and not computed — a program with no live block does
    nothing, and inside the last live program the dead columns are masked
    to ``_NEG`` before the key bias could matter. Inside the live blocks
    the key bias carries ALL masking, the last block's unfilled tail
    included (positions at or beyond the slot's length ride in at -1e4;
    no causal flag, no dropout, no lse: nothing differentiates through
    decode). Online softmax state (m, l, acc a head) lives in VMEM scratch
    across a slot's programs and is touched once per program; the output
    row is written on the slot's last program. A per-slot bias rides as
    the slot's whole [programs, pages*block] table (a block whose
    trailing dims equal the array's, which the Mosaic (8, 128) rule
    admits where a lone (1, block) strip is refused) and the program
    picks its own row; a per-head bias (``per_head``) as
    [programs, heads, pages*block], one row a head."""
    from jax.experimental import pallas as pl

    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    kb_ref, o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b, i = pl.program_id(0), pl.program_id(1)
    block = k_refs[0].shape[1]
    keys = pages * block

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # live blocks from this program's first one on
    live = _live_blocks(lengths_ref[b], block) - i * pages

    @pl.when(live > 0)
    def _attend():
        # operands in their INPUT dtype, the accumulator fp32, as in
        # ``_scores``
        kblk = (k_refs[0][0] if pages == 1 else
                jnp.concatenate([r[0] for r in k_refs], axis=0))
        vblk = (v_refs[0][0] if pages == 1 else
                jnp.concatenate([r[0] for r in v_refs], axis=0))
        dead = jax.lax.broadcasted_iota(
            jnp.int32, (1, keys), 1) >= live * block
        s = jax.lax.dot_general(
            q_ref[0], kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        bias = kb_ref[0, i] if per_head else kb_ref[0, pl.ds(i, 1), :]
        s = s * scale + jnp.where(dead, _NEG, bias)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _emit():
        # row h keeps its own head's lanes; the rows then add up to the
        # merged-heads [1, hidden] row
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        first = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0) * d_head
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        own = (lane >= first) & (lane < first + d_head)
        o_ref[0] = jnp.where(own, out, 0.0).sum(
            axis=0, keepdims=True).astype(o_ref.dtype)


def flash_decode_paged_attention(q, k_pool, v_pool, tables, key_bias=None,
                                 scale=None, interpret=None, lengths=None):
    """Decode-mode attention reading K/V THROUGH a block table: ``q``
    [B, N, 1, D] (one live token per slot) against a shared paged pool
    ``k_pool``/``v_pool`` [blocks, block, N*D] — a token's keys are one
    row, head h on lanes h*D .. (h+1)*D, the order ``q``'s heads have —
    with ``tables`` [B, max_blocks] int32 mapping each slot's logical
    block number to a physical pool block. -> [B, N, 1, D]. ``key_bias``
    [B, S] (S = max_blocks*block; [B*N, S] for one mask a head)
    additively masks positions at/beyond the slot's live length — which
    also covers any garbage the mapped blocks hold (the serving layer
    parks idle table entries on a sink block). ``lengths`` [B] int32,
    live keys a slot, says how far a table row is worth reading: entries
    at or past ceil(length / block) are neither fetched nor computed
    (so they may point anywhere in the pool), and the kernel's work
    follows the live keys instead of the table's width. Without it every
    table entry is live. Tables and lengths are runtime data: on TPU
    they ride scalar prefetch, so the index maps resolve the indirection
    before each DMA and ONE compiled kernel serves every table layout
    and every mix of lengths. The tiling comes from the shapes: all N
    heads of a slot and ``PAGED_KEYS`` keys (or the whole table, where it
    is shorter) a program. GROUPED heads: a pool row of fewer key
    heads than ``q`` has query heads ([blocks, block, G*D], G dividing N;
    query head h reads key head h // (N / G)) goes to
    ``_decode_paged_grouped`` (``lengths`` required, no ``key_bias``).
    Forward-only; dense gather-then-softmax
    fallback off TPU — bit-compatible math with ``reference_attention``
    over the gathered logical rows."""
    from jax.experimental import pallas as pl  # noqa: F401 (dispatch)
    from jax.experimental.pallas import tpu as pltpu

    B, N, Sq, D = q.shape
    blocks, blk, H = k_pool.shape
    MB = tables.shape[1]
    S = MB * blk
    if Sq != 1:
        raise ValueError(
            "flash_decode_paged_attention is the single-query path, "
            "got Sq=%d" % Sq
        )
    if (H % D or N % (H // D) or H > N * D
            or v_pool.shape != k_pool.shape):
        raise ValueError(
            "pool geometry %r / %r does not match q heads x depth (%d x %d)"
            % (k_pool.shape, v_pool.shape, N, D)
        )
    scale = scale if scale is not None else 1.0 / float(np.sqrt(D))
    if H != N * D:
        # grouped queries: fewer key heads than query heads
        if key_bias is not None or lengths is None:
            raise ValueError(
                "grouped heads (%d queries on %d key heads) mask by "
                "lengths= alone" % (N, H // D))
        return _decode_paged_grouped(q, k_pool, v_pool, tables, lengths,
                                     scale, interpret)
    kb = _normalize_key_bias(key_bias, B, N, S)
    on_tpu = lowers_for_tpu()
    tables = tables.astype(jnp.int32)
    if lengths is not None:
        lengths = jnp.clip(lengths.astype(jnp.int32).reshape(B), 1, S)
    if interpret is None and not on_tpu:
        # dense fallback: gather the logical rows, then dense softmax
        # attention. With lengths, a dead entry reads the slot's last
        # live block instead and is masked.
        dead = None
        if lengths is not None:
            tables = _held_blocks(tables, lengths, blk, MB)
            dead = jnp.repeat(
                jnp.arange(MB)[None, :]
                >= _live_blocks(lengths, blk)[:, None], blk, axis=1)
        rows_k = k_pool[tables].reshape(B, S, N, D)
        rows_v = v_pool[tables].reshape(B, S, N, D)
        s = jnp.einsum("bnqd,bknd->bnqk", q, rows_k).astype(
            jnp.float32
        ) * scale
        if kb is not None:
            s = s + kb.reshape(B, N, 1, S)
        if dead is not None:
            s = jnp.where(dead[:, None, None, :], _NEG, s)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bnqk,bknd->bnqd", p.astype(q.dtype), rows_v)
    P = min(MB, -(-PAGED_KEYS // blk))         # logical blocks a program
    programs = -(-MB // P)
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    Np = _round_up(N, 8)                       # Mosaic sublane minimum
    # one mask per slot (what the engine feeds) rides as
    # [B, programs, P*block] and is NOT expanded over heads — its block
    # index ignores the program, so it is fetched once a slot; one mask a
    # head as [B, programs, Np, P*block]
    if key_bias is not None and key_bias.size == B * S:
        kb = key_bias.astype(jnp.float32)
    elif kb is None:
        kb = jnp.zeros((B, S), jnp.float32)
    per_head = kb.size != B * S
    kb = kb.reshape(B, -1, S)
    kb = jnp.pad(kb, ((0, 0), (0, 0), (0, programs * P * blk - S)))
    kb = kb.reshape(B, -1, programs, P * blk)
    if per_head:
        kb = jnp.pad(kb.transpose(0, 2, 1, 3),
                     ((0, 0), (0, 0), (0, Np - N), (0, 0)))
    else:
        kb = kb.reshape(B, programs, P * blk)
    kb_spec = pl.BlockSpec(
        (1,) + kb.shape[1:],
        lambda b, i, held, lens: (b,) + (0,) * (kb.ndim - 1),
        memory_space=pltpu.VMEM)
    # block-diagonal query: row h holds q_h on head h's lanes, so one
    # [Np, H] x [keys, H] product scores every head against its own keys
    qd = (q[:, :, 0, None, :]
          * jnp.eye(N, dtype=q.dtype)[None, :, :, None]).reshape(B, N, H)
    qd = jnp.pad(qd, ((0, 0), (0, Np - N), (0, 0))).astype(k_pool.dtype)
    kernel = functools.partial(_decode_paged_kernel, scale=scale, pages=P,
                               d_head=D, per_head=per_head)

    def pool_spec(j):
        # index maps receive the grid indices first, then the prefetched
        # scalar refs: operand j of program i pulls the physical block the
        # held table names for it
        return pl.BlockSpec(
            (1, blk, H),
            lambda b, i, held, lens: (held[b, i * P + j], 0, 0),
            memory_space=pltpu.VMEM,
        )

    pool_specs = [pool_spec(j) for j in range(P)]
    slot = lambda b, i, held, lens: (b, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, programs),
        in_specs=[
            pl.BlockSpec((1, Np, H), slot, memory_space=pltpu.VMEM),
            *pool_specs, *pool_specs,
            kb_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, H), slot, memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((Np, 1), jnp.float32),
            pltpu.VMEM((Np, 1), jnp.float32),
            pltpu.VMEM((Np, H), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="flash_decode_paged",
        out_shape=jax.ShapeDtypeStruct((B, 1, H), q.dtype),
        grid_spec=grid_spec,
        interpret=bool(interpret),
    )(_held_blocks(tables, lengths, blk, P), lengths, qd,
      *([k_pool] * P), *([v_pool] * P), kb)
    return out.reshape(B, N, 1, D)


def _decode_paged_grouped_kernel(held_ref, lengths_ref, q_ref, *refs, scale,
                                 pages):
    """Grouped-query paged decode step, one (slot, group of ``pages``
    logical blocks) program over all heads of the slot. A token's keys
    (and values) are one pool row of G key heads side by side, ``D`` lanes
    each; the ``N / G`` query heads that read key head g are the ROWS of
    one product against that head's lanes of the program's keys
    (``q_ref`` [1, G, rows, D], the rows padded to the operand's sublane
    tile with zeros), so a key is fetched once for all its queries and
    nothing of another head's lanes is multiplied. Dead table entries are
    neither fetched nor computed (``_held_blocks``); inside the live
    blocks the slot's live length masks, as in
    ``_mla_decode_paged_kernel``. Online softmax state lives in VMEM
    scratch across a slot's programs."""
    from jax.experimental import pallas as pl

    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b, i = pl.program_id(0), pl.program_id(1)
    block = k_refs[0].shape[1]
    keys = pages * block
    kv_heads, rows, d = q_ref.shape[1:]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    live = _live_blocks(length, block) - i * pages

    @pl.when(live > 0)
    def _attend():
        kblk = (k_refs[0][0] if pages == 1 else
                jnp.concatenate([r[0] for r in k_refs], axis=0))
        vblk = (v_refs[0][0] if pages == 1 else
                jnp.concatenate([r[0] for r in v_refs], axis=0))
        s = jnp.concatenate([
            jax.lax.dot_general(
                q_ref[0, g], kblk[:, g * d:(g + 1) * d],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            for g in range(kv_heads)], axis=0) * scale
        col = i * keys + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        s = jnp.where(col < length, s, _NEG)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = jnp.concatenate([
            jax.lax.dot_general(
                p[g * rows:(g + 1) * rows].astype(vblk.dtype),
                vblk[:, g * d:(g + 1) * d], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for g in range(kv_heads)], axis=0)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _decode_paged_grouped(q, k_pool, v_pool, tables, lengths, scale,
                          interpret):
    """``flash_decode_paged_attention`` for grouped heads: ``q``
    [B, N, 1, D], pools [blocks, block, G*D], ``lengths`` [B] live keys a
    slot (held to 1 .. the table's keys). -> [B, N, 1, D]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, N, _, D = q.shape
    blocks, blk, H = k_pool.shape
    MB = tables.shape[1]
    G = H // D
    grp = N // G
    tables = tables.astype(jnp.int32)
    lengths = jnp.clip(lengths.astype(jnp.int32).reshape(B), 1, MB * blk)
    qg = q.reshape(B, G, grp, D).astype(k_pool.dtype)
    if interpret is None and not lowers_for_tpu():
        held = _held_blocks(tables, lengths, blk, MB)
        rows_k = k_pool[held].reshape(B, MB * blk, G, D)
        rows_v = v_pool[held].reshape(B, MB * blk, G, D)
        s = jnp.einsum("bgrd,bkgd->bgrk", qg, rows_k,
                       preferred_element_type=jnp.float32) * scale
        seen = jnp.arange(MB * blk)[None, :] < lengths[:, None]
        p = jax.nn.softmax(
            jnp.where(seen[:, None, None, :], s, _NEG), axis=-1)
        out = jnp.einsum("bgrk,bkgd->bgrd", p.astype(rows_v.dtype), rows_v,
                         preferred_element_type=jnp.float32)
        return out.astype(q.dtype).reshape(B, N, 1, D)
    P = min(MB, -(-PAGED_KEYS // blk))
    programs = -(-MB // P)
    # the sublane tile of the query operand: 8 rows of 32 bits
    tile = 8 * 4 // jnp.dtype(k_pool.dtype).itemsize
    rows = _round_up(grp, tile)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - grp), (0, 0)))
    kernel = functools.partial(_decode_paged_grouped_kernel, scale=scale,
                               pages=P)

    def pool_spec(j):
        return pl.BlockSpec(
            (1, blk, H), lambda b, i, held, lens: (held[b, i * P + j], 0, 0),
            memory_space=pltpu.VMEM,
        )

    pool_specs = [pool_spec(j) for j in range(P)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, programs),
        in_specs=[
            pl.BlockSpec((1, G, rows, D),
                         lambda b, i, held, lens: (b, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            *pool_specs, *pool_specs,
        ],
        out_specs=pl.BlockSpec((1, G * rows, D),
                               lambda b, i, held, lens: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((G * rows, 1), jnp.float32),
            pltpu.VMEM((G * rows, 1), jnp.float32),
            pltpu.VMEM((G * rows, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="flash_decode_paged_gqa",
        out_shape=jax.ShapeDtypeStruct((B, G * rows, D), q.dtype),
        grid_spec=grid_spec,
        interpret=bool(interpret),
    )(_held_blocks(tables, lengths, blk, P), lengths, qg,
      *([k_pool] * P), *([v_pool] * P))
    return out.reshape(B, G, rows, D)[:, :, :grp].reshape(B, N, 1, D)


def _mla_decode_paged_kernel(held_ref, lengths_ref, q_ref, *refs, scale,
                             pages, value_width):
    """Latent (MLA) paged decode step, one (slot, group of ``pages``
    logical blocks) program. All H query heads of the slot are the ROWS
    of one product: q [H, W] against the block's latent rows [keys, W]
    (one shared key row a token, W = latent ‖ rope key ‖ zero pad), and
    the value is the first ``value_width`` lanes of the same block, so a
    block is one DMA, not two. Dead table entries are neither fetched nor
    computed (``_held_blocks``); inside the live blocks the slot's live
    length masks, so no key bias rides in. Online softmax state lives in
    VMEM scratch across a slot's programs, as in
    ``_decode_paged_kernel``."""
    from jax.experimental import pallas as pl

    pool_refs = refs[:pages]
    o_ref, m_ref, l_ref, acc_ref = refs[pages:]
    b, i = pl.program_id(0), pl.program_id(1)
    block = pool_refs[0].shape[1]
    keys = pages * block

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    live = _live_blocks(length, block) - i * pages

    @pl.when(live > 0)
    def _attend():
        rows = (pool_refs[0][0] if pages == 1 else
                jnp.concatenate([r[0] for r in pool_refs], axis=0))
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        col = i * keys + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        s = jnp.where(col < length, s, _NEG)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def mla_decode_paged_attention(q, pool, tables, lengths, value_width,
                               scale, interpret=None):
    """Absorbed latent attention of one query token a slot THROUGH a
    block table: ``q`` [B, H, W] (per head the absorbed query over the
    latent, then the rotated rope query, then zeros up to the pool's
    row), ``pool`` [blocks, block, W] (per token the normed latent ``c``,
    the rotated shared rope key, zeros), ``tables`` [B, max_blocks],
    ``lengths`` [B] live keys a slot (held to 1 .. the table's keys: an
    inactive slot reads one sink block). Score of key j for head h is
    ``q[h] . pool_row[j] * scale``; the result [B, H, value_width] is the
    softmax-weighted sum of the rows' first ``value_width`` lanes (the
    latent), float32. Dense gather-then-softmax off TPU, the same
    arithmetic."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, W = q.shape
    blocks, blk, Wp = pool.shape
    MB = tables.shape[1]
    if Wp != W:
        raise ValueError("pool row %d does not match q width %d" % (Wp, W))
    tables = tables.astype(jnp.int32)
    lengths = jnp.clip(lengths.astype(jnp.int32).reshape(B), 1, MB * blk)
    if interpret is None and not lowers_for_tpu():
        rows = pool[_held_blocks(tables, lengths, blk, MB)].reshape(
            B, MB * blk, W)
        s = jnp.einsum("bhw,bkw->bhk", q, rows,
                       preferred_element_type=jnp.float32) * scale
        live = jnp.arange(MB * blk)[None, None, :] < lengths[:, None, None]
        p = jax.nn.softmax(jnp.where(live, s, _NEG), axis=-1)
        return jnp.einsum("bhk,bkv->bhv", p.astype(rows.dtype),
                          rows[..., :value_width],
                          preferred_element_type=jnp.float32)
    P = min(MB, -(-PAGED_KEYS // blk))
    programs = -(-MB // P)
    Hp = _round_up(H, 8)
    qp = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))
    kernel = functools.partial(_mla_decode_paged_kernel, scale=scale,
                               pages=P, value_width=value_width)

    def pool_spec(j):
        return pl.BlockSpec(
            (1, blk, W), lambda b, i, held, lens: (held[b, i * P + j], 0, 0),
            memory_space=pltpu.VMEM,
        )

    slot = lambda b, i, held, lens: (b, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, programs),
        in_specs=[
            pl.BlockSpec((1, Hp, W), slot, memory_space=pltpu.VMEM),
            *[pool_spec(j) for j in range(P)],
        ],
        out_specs=pl.BlockSpec((1, Hp, value_width), slot,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((Hp, 1), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
            pltpu.VMEM((Hp, value_width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="mla_decode_paged",
        out_shape=jax.ShapeDtypeStruct((B, Hp, value_width), jnp.float32),
        grid_spec=grid_spec,
        interpret=bool(interpret),
    )(_held_blocks(tables, lengths, blk, P), lengths, qp, *([pool] * P))
    return out[:, :H, :]


# --------------------------------------------------------------------------
# padding / plumbing
# --------------------------------------------------------------------------


def _round_up(x, m):
    return (x + m - 1) // m * m


def _pad_to(S, block):
    Sp = _round_up(S, 8)
    return _round_up(Sp, min(block, Sp))


def _geometry(q, k):
    """(B, N, Sq, Sk, padded Sq, padded Sk, block_q, block_k) of a call."""
    B, N, Sq, _ = q.shape
    Sk = k.shape[2]
    Sqp, Skp = _pad_to(Sq, BLOCK_Q), _pad_to(Sk, BLOCK_K)
    return B, N, Sq, Sk, Sqp, Skp, min(BLOCK_Q, Sqp), min(BLOCK_K, Skp)


def _prep(q, k, v, key_bias, bias, g=None):
    """Flatten heads, pad seq lens to tile multiples. Padded KEYS get
    key-bias −inf (never receive weight); padded QUERY rows are sliced
    away by the caller. Returns the padded operands + geometry."""
    B, N, Sq, Sk, Sqp, Skp, bq, bk = geom = _geometry(q, k)
    D = q.shape[-1]
    qf = q.reshape(B * N, Sq, D)
    kf = k.reshape(B * N, Sk, D)
    vf = v.reshape(B * N, Sk, D)
    kb = jnp.broadcast_to(key_bias, (B * N, Sk))
    if Sqp != Sq:
        qf = jnp.pad(qf, ((0, 0), (0, Sqp - Sq), (0, 0)))
    if Skp != Sk:
        kf = jnp.pad(kf, ((0, 0), (0, Skp - Sk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, Skp - Sk), (0, 0)))
        kb = jnp.pad(kb, ((0, 0), (0, Skp - Sk)), constant_values=_NEG)
    bf = None
    if bias is not None:
        bf = bias
        if Sqp != Sq or Skp != Sk:
            # zero-padded: padded keys are already excluded via key-bias
            bf = jnp.pad(bf, ((0, 0), (0, Sqp - Sq), (0, Skp - Sk)))
    if g is not None and Sqp != Sq:
        g = jnp.pad(g.reshape(B * N, Sq, D), ((0, 0), (0, Sqp - Sq), (0, 0)))
    elif g is not None:
        g = g.reshape(B * N, Sq, D)
    return qf, kf, vf, kb, bf, g, geom


def _common_in_specs(pl, pltpu, geom, G, D, rq):
    """in_specs for (q, k, v, key_bias[, bias]) shared by the two
    (head, q-block)-grid kernels (forward and dq); a program holds ``rq``
    query rows (one block, or the head's). Vector operands ride
    with an explicit singleton dim ([BN, 1, S] rows / [BN, S, 1] columns)
    so every block's trailing two dims satisfy the Mosaic (8, 128) tiling
    rule (a (1, S) block of a rank-2 array does not)."""
    B, N, Sq, Sk, Sqp, Skp, bq, bk = geom
    specs = [
        pl.BlockSpec((1, rq, D), lambda h, i: (h, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, Skp, D), lambda h, i: (h, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, Skp, D), lambda h, i: (h, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, Skp), lambda h, i: (h, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    if G is not None:
        group = (B * N) // G
        specs.append(
            pl.BlockSpec((1, bq, Skp), lambda h, i: (h // group, i, 0),
                         memory_space=pltpu.VMEM)
        )
    return specs


# --------------------------------------------------------------------------
# custom-vjp core
# --------------------------------------------------------------------------


def _seed_spec(pl, pltpu):
    # scalar param rides SMEM — the canonical Pallas-TPU scalar pattern,
    # exempt from the (8, 128) VMEM tiling rules
    return pl.BlockSpec((1, 1), lambda *_: (0, 0), memory_space=pltpu.SMEM)


def _flash_fwd(q, k, v, key_bias, bias, seed, causal, scale, dropout_rate,
               interpret, head_swap=None):
    """One forward call: counted here, a call, and handed to the memoized
    ``_flash_fwd_impl``."""
    _count_blocks(causal, bias, q, k, kernels=1)
    return _flash_fwd_impl(q, k, v, key_bias, bias, seed, causal, scale,
                           dropout_rate, interpret, head_swap)


# jitted: the calls of one signature in a program (a model's layers) are
# traced and lowered once and share one function of the module, where each
# traced and lowered its own copy of the kernel
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _flash_fwd_impl(q, k, v, key_bias, bias, seed, causal, scale,
                    dropout_rate, interpret, head_swap):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qf, kf, vf, kb, bf, _, geom = _prep(q, k, v, key_bias, bias)
    B, N, Sq, Sk, Sqp, Skp, bq, bk = geom
    D = q.shape[-1]
    G = None if bf is None else bf.shape[0]

    whole = _whole_head(causal, bf, geom, D, q.dtype.itemsize)
    rq = Sqp if whole else bq       # query rows a program holds
    kernel = functools.partial(
        _fwd_kernel if bf is not None else _no_bias(_fwd_kernel),
        scale=scale, causal=causal, kv_len=Skp, block_q=bq, block_k=bk,
        dropout_rate=dropout_rate, head_swap=head_swap, q_blocks=rq // bq,
    )
    in_specs = (_common_in_specs(pl, pltpu, geom, G, D, rq)
                + [_seed_spec(pl, pltpu)])
    operands = (
        [qf, kf, vf, kb[:, None, :]]
        + ([bf] if bf is not None else []) + [seed]
    )
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        out_shape=[
            jax.ShapeDtypeStruct((B * N, Sqp, D), q.dtype),
            jax.ShapeDtypeStruct((B * N, Sqp, 1), jnp.float32),
        ],
        grid=(B * N, Sqp // rq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, rq, D), lambda h, i: (h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, rq, 1), lambda h, i: (h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        interpret=interpret,
    )(*operands)
    return out[:, :Sq, :].reshape(B, N, Sq, D), lse[:, :Sq, 0]


def _no_bias(kernel):
    """Adapter: drop the bias ref from a kernel's signature (Pallas passes
    exactly one ref per operand, so the no-bias variant has one fewer)."""
    @functools.wraps(kernel)
    def wrapped(q_ref, k_ref, v_ref, key_bias_ref, *rest, **kw):
        return kernel(q_ref, k_ref, v_ref, key_bias_ref, None, *rest, **kw)
    return wrapped


def _flash_bwd(causal, scale, dropout_rate, interpret, head_swap, res, g,
               g_lse):
    """One backward call (dq and dkv kernels), counted as ``_flash_fwd``."""
    q, k, _, _, bias = res[:5]
    _count_blocks(causal, bias, q, k, kernels=2)
    return _flash_bwd_core(causal, scale, dropout_rate, interpret, head_swap,
                           res, g, g_lse)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _flash_bwd_core(causal, scale, dropout_rate, interpret, head_swap, res,
                    g, g_lse):
    """Shared backward. ``g_lse`` is the logsumexp cotangent from the
    with-lse entry point (ring attention's combine differentiates through
    each block's lse): d s_ij gains p_ij·g_lse_i, which folds into the
    delta term — ds = p∘(dp − (delta − g_lse)) — so the kernels run
    unchanged with an adjusted delta operand. With dropout, delta =
    rowsum(dO∘O) already equals Σ_j P·dP̂ (O carries the mask), so the
    trick survives; the kernels regenerate the mask from the seed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, key_bias, bias, seed, out, lse = res
    qf, kf, vf, kb, bf, gf, geom = _prep(q, k, v, key_bias, bias, g=g)
    B, N, Sq, Sk, Sqp, Skp, bq, bk = geom
    D = q.shape[-1]
    G = None if bf is None else bf.shape[0]

    # delta = rowsum(dO ∘ O): tiny elementwise pass XLA fuses on its own
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    delta = delta.reshape(B * N, Sq)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32).reshape(B * N, Sq)
    if Sqp != Sq:
        delta = jnp.pad(delta, ((0, 0), (0, Sqp - Sq)))
        lse_p = jnp.pad(lse, ((0, 0), (0, Sqp - Sq)))
    else:
        lse_p = lse

    # a program holds one block of a head, or (``_whole_head``) all of them
    whole = _whole_head(causal, bf, geom, D, q.dtype.itemsize)
    rq, rk = (Sqp, Skp) if whole else (bq, bk)

    # ---- dq: same (head, q-block) grid as the forward ----
    dq_kernel = functools.partial(
        _bwd_dq_kernel if bf is not None else _no_bias(_bwd_dq_kernel),
        scale=scale, causal=causal, kv_len=Skp, block_q=bq, block_k=bk,
        dropout_rate=dropout_rate, head_swap=head_swap, q_blocks=rq // bq,
    )
    row_spec = pl.BlockSpec((1, rq, D), lambda h, i: (h, i, 0),
                            memory_space=pltpu.VMEM)
    col_spec = pl.BlockSpec((1, rq, 1), lambda h, i: (h, i, 0),
                            memory_space=pltpu.VMEM)
    kb3 = kb[:, None, :]                       # [BN, 1, Skp]
    lse3 = lse_p[:, :, None]                   # [BN, Sqp, 1]
    delta3 = delta[:, :, None]
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        out_shape=jax.ShapeDtypeStruct((B * N, Sqp, D), q.dtype),
        grid=(B * N, Sqp // rq),
        in_specs=_common_in_specs(pl, pltpu, geom, G, D, rq)
        + [row_spec, col_spec, col_spec, _seed_spec(pl, pltpu)],
        out_specs=row_spec,
        interpret=interpret,
    )(*([qf, kf, vf, kb3] + ([bf] if bf is not None else [])
        + [gf, lse3, delta3, seed]))

    # ---- dk/dv/dkey_bias/dbias ----
    # Grid order depends on the bias mode (see _bwd_dkv_kernel): shared
    # bias needs the transposed (kv, head) grid for safe dbias
    # accumulation; the KeyBias-only path runs (head, kv) so the full
    # q/dO row blocks are reused across the inner kv sweep.
    head_major = bf is None
    group = None if G is None else (B * N) // G
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel if bf is not None else _no_bias(_bwd_dkv_kernel),
        scale=scale, causal=causal, q_len=Sqp, block_q=bq, block_k=bk,
        bias_group=group or 1, dropout_rate=dropout_rate,
        head_swap=head_swap, head_major=head_major, kv_blocks=rk // bk,
    )
    if bf is None:
        # adapter also has to drop the dbias OUT ref
        base = dkv_kernel

        def dkv_kernel(q_ref, k_ref, v_ref, key_bias_ref, do_ref, lse_ref,
                       delta_ref, seed_ref, dk_ref, dv_ref, dkb_ref):
            return base(q_ref, k_ref, v_ref, key_bias_ref, do_ref, lse_ref,
                        delta_ref, seed_ref, dk_ref, dv_ref, dkb_ref, None)

    # index maps below are written head-first; the transposed grid swaps
    # the program-id arguments, the head-major grid uses them verbatim
    if head_major:
        def hj(f):
            return f
    else:
        def hj(f):
            return lambda j, h: f(h, j)

    in_specs = [
        pl.BlockSpec((1, Sqp, D), hj(lambda h, j: (h, 0, 0)),
                     memory_space=pltpu.VMEM),       # q (full rows)
        pl.BlockSpec((1, rk, D), hj(lambda h, j: (h, j, 0)),
                     memory_space=pltpu.VMEM),       # k block(s)
        pl.BlockSpec((1, rk, D), hj(lambda h, j: (h, j, 0)),
                     memory_space=pltpu.VMEM),       # v block(s)
        pl.BlockSpec((1, 1, rk), hj(lambda h, j: (h, 0, j)),
                     memory_space=pltpu.VMEM),       # key bias block(s)
    ]
    if bf is not None:
        in_specs.append(
            pl.BlockSpec((1, Sqp, bk), hj(lambda h, j: (h // group, 0, j)),
                         memory_space=pltpu.VMEM)    # bias column block
        )
    in_specs += [
        pl.BlockSpec((1, Sqp, D), hj(lambda h, j: (h, 0, 0)),
                     memory_space=pltpu.VMEM),       # dO (full rows)
        pl.BlockSpec((1, Sqp, 1), hj(lambda h, j: (h, 0, 0)),
                     memory_space=pltpu.VMEM),       # lse
        pl.BlockSpec((1, Sqp, 1), hj(lambda h, j: (h, 0, 0)),
                     memory_space=pltpu.VMEM),       # delta
        _seed_spec(pl, pltpu),                       # dropout seed
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B * N, Skp, D), k.dtype),      # dk
        jax.ShapeDtypeStruct((B * N, Skp, D), v.dtype),      # dv
        jax.ShapeDtypeStruct((B * N, 1, Skp), jnp.float32),  # dkey_bias
    ]
    out_specs = [
        pl.BlockSpec((1, rk, D), hj(lambda h, j: (h, j, 0)),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, rk, D), hj(lambda h, j: (h, j, 0)),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, rk), hj(lambda h, j: (h, 0, j)),
                     memory_space=pltpu.VMEM),
    ]
    if bf is not None:
        out_shape.append(jax.ShapeDtypeStruct((G, Sqp, Skp), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, Sqp, bk), hj(lambda h, j: (h // group, 0, j)),
                         memory_space=pltpu.VMEM)
        )
    outs = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        out_shape=out_shape,
        grid=(
            (B * N, Skp // rk) if head_major else (Skp // bk, B * N)
        ),
        in_specs=in_specs,
        out_specs=out_specs,
        interpret=interpret,
    )(*([qf, kf, vf, kb3] + ([bf] if bf is not None else [])
        + [gf, lse3, delta3, seed]))
    if bf is not None:
        dkf, dvf, dkb, dbias = outs
        dbias = dbias[:, :Sq, :Sk]
    else:
        dkf, dvf, dkb = outs
        dbias = None

    dq = dq[:, :Sq, :].reshape(q.shape)
    dk = dkf[:, :Sk, :].reshape(k.shape)
    dv = dvf[:, :Sk, :].reshape(v.shape)
    dkey_bias = dkb[:, 0, :Sk].astype(key_bias.dtype)
    return dq, dk, dv, dkey_bias, dbias, jnp.zeros_like(seed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_lse(q, k, v, key_bias, bias, seed, causal, scale, dropout_rate,
               interpret, head_swap):
    """(out, lse) variant: lse [B*N, Sq] is the per-row logsumexp of the
    masked scores — the residual blockwise/ring attention needs to
    combine per-block outputs across hops without renormalizing."""
    return _flash_fwd(q, k, v, key_bias, bias, seed, causal, scale,
                           dropout_rate, interpret, head_swap)


def _flash_lse_fwd(q, k, v, key_bias, bias, seed, causal, scale,
                   dropout_rate, interpret, head_swap):
    out, lse = _flash_fwd(q, k, v, key_bias, bias, seed, causal, scale,
                               dropout_rate, interpret, head_swap)
    return (out, lse), (q, k, v, key_bias, bias, seed, out, lse)


def _flash_lse_bwd(causal, scale, dropout_rate, interpret, head_swap, res,
                   cotangents):
    g, g_lse = cotangents
    return _flash_bwd(causal, scale, dropout_rate, interpret, head_swap,
                           res, g, g_lse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------


def _normalize_bias(bias, B, N, Sq, Sk):
    """-> (bias [G, Sq, Sk] with G ∈ {1, B, B·N}, head_major_swap)."""
    b = jnp.asarray(bias, jnp.float32)
    if b.ndim == 2:
        return b[None], False
    if b.ndim == 3:
        if b.shape[0] in (1, B * N) or (b.shape[0] == B and N == 1):
            return b, False
        raise ValueError(
            "3-D flash-attention bias must have leading dim 1 or B*N, got %r"
            % (b.shape,)
        )
    if b.ndim == 4:
        b0, b1 = b.shape[:2]
        if (b0, b1) == (1, 1):
            return b.reshape(1, Sq, Sk), False
        if b1 == 1 and b0 == B:
            return b.reshape(B, Sq, Sk), False          # per-batch rows
        if b0 == 1 and b1 == N:
            # per-head shared across batch: run attention head-major so
            # heads sharing a bias row stay consecutive (role swap B<->N)
            return b.reshape(N, Sq, Sk), True
        if (b0, b1) == (B, N):
            return b.reshape(B * N, Sq, Sk), False
        raise ValueError(
            "4-D flash-attention bias must broadcast from (1|B, 1|N, Sq, Sk),"
            " got %r" % (b.shape,)
        )
    raise ValueError("flash-attention bias must be 2-/3-/4-D, got %r"
                     % (b.shape,))


def _fallback_keep(B, N, Sq, Sk, seed, rate):
    """[B, N, Sq, Sk] keep-mask, bit-identical to what the kernels
    regenerate from the same seed (flat head h = b·N + n, absolute
    row/col — padding sits past the valid region so coords agree)."""
    heads = jnp.arange(B * N, dtype=jnp.int32).reshape(B, N, 1, 1)
    rows = jnp.arange(Sq, dtype=jnp.int32).reshape(1, 1, Sq, 1)
    cols = jnp.arange(Sk, dtype=jnp.int32).reshape(1, 1, 1, Sk)
    seed_u = seed.reshape(()).astype(jnp.uint32)
    return _hash_keep(rows, cols, heads, seed_u, rate)


def _norm_seed(dropout_seed):
    """Normalize any user seed (python int of any size, or traced int/f32
    scalar) to a (1, 1) f32 carrying a 23-bit value. A plain ``% 2^23``
    would ALIAS seeds (s and s + 2^23 give identical masks, and f32
    rounding collapses seeds ≥ 2^24 before the mod), so the full value is
    avalanche-mixed down to 23 bits first — distinct seeds give
    decorrelated masks."""
    s = 0 if dropout_seed is None else dropout_seed
    if isinstance(s, (int, np.integer)):
        # fold arbitrary-width python ints into 32 bits before the mix
        s = int(s)
        s = (s ^ (s >> 32) ^ (s >> 64)) & 0xFFFFFFFF
    u = jnp.asarray(s).reshape(()).astype(jnp.uint32)
    u = u ^ (u >> jnp.uint32(16))
    u = u * jnp.uint32(0x7FEB352D)
    u = u ^ (u >> jnp.uint32(15))
    u = u * jnp.uint32(0x846CA68B)
    u = u ^ (u >> jnp.uint32(16))
    return (u >> jnp.uint32(9)).astype(jnp.float32).reshape(1, 1)


def _normalize_key_bias(key_bias, B, N, Sk):
    """Raw key bias ([Sk] / [1, Sk] / [B, Sk] / [B*N, Sk] / broadcastable)
    -> the kernels' canonical [B*N, Sk] fp32 layout."""
    if key_bias is None:
        return None
    kb = key_bias.astype(jnp.float32)
    if kb.ndim == 1:
        kb = kb[None]
    kb = kb.reshape(-1, Sk)
    if kb.shape[0] == B and N > 1:
        kb = jnp.broadcast_to(kb[:, None, :], (B, N, Sk)).reshape(-1, Sk)
    return jnp.broadcast_to(kb, (B * N, Sk))


def flash_attention_bwd_from_residuals(q, k, v, key_bias, seed, out, lse, g,
                                       causal=False, scale=None,
                                       dropout_rate=0.0, interpret=None):
    """Backward kernels driven by SAVED forward residuals (out, lse and
    the dropout seed) instead of a forward replay.

    The fluid ``flash_attention_grad`` lowering uses this: its generic
    grad machinery re-traces the forward under jax.vjp, which XLA CSE's
    for pure ops but NOT for Pallas custom calls — so the forward kernel
    ran twice per training step (verified by custom-call count in the
    lowered module). The reference saves softmax statistics on its fused
    attention ops for exactly this reason (multihead_matmul_op.cu).

    KeyBias-only entry (no general [S, S] bias — callers with one take
    the replay path). ``seed`` is the RAW dropout seed exactly as the
    caller passed it to the forward entry (None when dropout was off) —
    it is re-normalized through the same ``_norm_seed`` pipeline here,
    so the backward kernels hash the identical keep-mask. Returns
    (dq, dk, dv, dkey_bias[B*N, Sk] fp32)."""
    B, N, Sq, d = q.shape
    Sk = k.shape[2]
    rate = float(dropout_rate or 0.0)
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    kb = _normalize_key_bias(key_bias, B, N, Sk)
    if kb is None:
        kb = jnp.zeros((B * N, Sk), jnp.float32)
    seed = _norm_seed(seed)
    lse = lse.reshape(B * N, Sq)
    res = (q, k, v, kb, None, seed, out, lse)
    dq, dk, dv, dkb, _dbias, _dseed = _flash_bwd(
        causal, scale, rate, bool(interpret), None, res, g, None
    )
    return dq, dk, dv, dkb


def flash_attention_lse(q, k, v, key_bias=None, bias=None, causal=False,
                        scale=None, dropout_rate=0.0, dropout_seed=None,
                        interpret=None):
    """Like ``flash_attention`` but also returns the per-row logsumexp
    [B, N, Sq] of the masked scores. This is the building block for
    blockwise/ring attention: per-hop block outputs combine as
    out = Σ_b o_b · exp(lse_b − logaddexp_b(lse)) with no [S, S] tensor
    and no renormalization pass. Fully differentiable (the lse cotangent
    folds into the backward's delta term).

    ``dropout_rate``/``dropout_seed``: standard attention-probability
    dropout (mask∘P/keep, no renormalization; lse reports the undropped
    distribution). The mask is a stateless counter-based hash of
    (head, row, col, seed) regenerated inside every kernel AND the dense
    fallback — bit-identical across all paths, nothing stored. The rate
    is static (recompile on change); the seed is traced (vary per step
    for free)."""
    B, N, Sq, d = q.shape
    Sk = k.shape[2]
    if causal and Sq != Sk:
        raise ValueError("causal flash attention needs Sq == Sk")
    rate = float(dropout_rate or 0.0)
    if not 0.0 <= rate < 1.0:
        raise ValueError("dropout_rate must be in [0, 1), got %r" % rate)
    if rate > 0.0 and dropout_seed is None:
        import warnings

        # a None seed normalizes to one CONSTANT seed: every call drops
        # the identical (head, row, col) entries — in a training loop
        # that is a frozen mask (biased training), not dropout. The fluid
        # op lowering threads a fresh per-step seed; direct users must too.
        warnings.warn(
            "flash_attention: dropout_rate > 0 with dropout_seed=None "
            "reuses ONE fixed dropout mask on every call; pass a "
            "per-step seed for real dropout", stacklevel=3)
    seed = _norm_seed(dropout_seed)
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    kb = _normalize_key_bias(key_bias, B, N, Sk)
    on_tpu = lowers_for_tpu()
    if interpret is None and not on_tpu:
        # dense fallback with an explicit lse (same math as the kernels)
        s = jnp.einsum("bnqd,bnkd->bnqk", q, k).astype(jnp.float32) * scale
        if kb is not None:
            s = s + kb.reshape(B, N, 1, Sk)
        if bias is not None:
            nb, swap = _normalize_bias(bias, B, N, Sq, Sk)
            G = nb.shape[0]
            if swap:
                s = s + nb.reshape(1, N, Sq, Sk)
            elif G == 1:
                s = s + nb.reshape(1, 1, Sq, Sk)
            elif G == B * N:
                s = s + nb.reshape(B, N, Sq, Sk)
            else:
                s = s + nb.reshape(B, 1, Sq, Sk)
        if causal:
            mask = jnp.tril(jnp.ones((Sq, Sk), bool))
            s = jnp.where(mask[None, None], s, _NEG)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        # bit-identical to reference_attention (softmax then cast), so the
        # no-lse entry point's fallback contract — "transparently the jnp
        # reference" — holds exactly
        p = jax.nn.softmax(s, axis=-1)
        if rate > 0.0:
            p = jnp.where(_fallback_keep(B, N, Sq, Sk, seed, rate),
                          p / (1.0 - rate), 0.0)
        out = jnp.einsum("bnqk,bnkd->bnqd", p.astype(q.dtype), v)
        return out, lse
    if kb is None:
        kb = jnp.zeros((B * N, Sk), jnp.float32)
    bf, swap = (None, False) if bias is None else _normalize_bias(
        bias, B, N, Sq, Sk
    )
    if swap:
        qT = q.transpose(1, 0, 2, 3)
        kT = k.transpose(1, 0, 2, 3)
        vT = v.transpose(1, 0, 2, 3)
        kbT = kb.reshape(B, N, Sk).transpose(1, 0, 2).reshape(N * B, Sk)
        # head_swap remaps the dropout-hash head ids back to the caller's
        # b*N+n layout so the swap never changes the mask (and the shared
        # bias needs no B-fold expansion)
        out, lse = _flash_lse(qT, kT, vT, kbT, bf, seed, causal, scale,
                              rate, bool(interpret),
                              (B, N) if rate > 0.0 else None)
        return (
            out.transpose(1, 0, 2, 3),
            lse.reshape(N, B, Sq).transpose(1, 0, 2),
        )
    out, lse = _flash_lse(q, k, v, kb, bf, seed, causal, scale, rate,
                          bool(interpret), None)
    return out, lse.reshape(B, N, Sq)


def flash_attention(q, k, v, key_bias=None, bias=None, causal=False,
                    scale=None, dropout_rate=0.0, dropout_seed=None,
                    interpret=None):
    """Fused attention, [B, N, S, D] -> [B, N, S, D].

    ``key_bias``: optional additive mask over KEYS, shape [B*N, S] or
    broadcastable — BERT-style padding masks ((mask-1)*1e4 per key).
    ``bias``: optional general additive bias broadcastable to
    [B, N, Sq, Sk] (relative-position / ALiBi). Both may be given.
    ``dropout_rate``/``dropout_seed``: in-kernel attention dropout (see
    ``flash_attention_lse``) — training with dropout rides the kernels.
    ``interpret``: force the Pallas interpreter (tests); default runs the
    kernels on TPU and the jnp reference elsewhere. Forward AND backward
    are Pallas kernels — no [S, S] tensor ever reaches HBM.

    Single implementation: this is ``flash_attention_lse`` with the
    logsumexp dropped (its zero cotangent folds away in the backward), so
    the two entry points can never diverge on normalization/dispatch.
    """
    out, _lse = flash_attention_lse(
        q, k, v, key_bias=key_bias, bias=bias, causal=causal, scale=scale,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        interpret=interpret,
    )
    return out
