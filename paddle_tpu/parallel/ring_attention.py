"""Ring attention — sequence/context parallelism over a mesh axis.

Long sequences are sharded across devices on the sequence dimension; each
device computes attention for its Q shard while K/V shards rotate around
the ring via ``lax.ppermute`` (one hop per step, bandwidth rides ICI).
Softmax is accumulated online (flash-attention-style running max/sum), so
the full attention matrix never materializes.

The reference (2019-era) scales sequence length via LoD ragged batching
only (SURVEY.md §5.7 — ring/context parallelism ABSENT); this module is the
TPU-native long-context machinery the task calls for. Designed after the
public blockwise/ring-attention formulation (Liu et al.; jax shard_map
idiom from the scaling-book recipe).

Usage (inside shard_map over a mesh with a sequence axis "sp")::

    out = ring_attention(q, k, v, axis_name="sp", causal=True)

where q, k, v are the LOCAL shards [B, H, S_local, D] and the global
sequence is the concatenation over the axis in device order.
"""

from __future__ import annotations

import functools


def _online_combine(acc, new_max, new_sum, new_out):
    """Merge a new block into the running (max, sum, out) accumulator."""
    import jax.numpy as jnp

    run_max, run_sum, run_out = acc
    m = jnp.maximum(run_max, new_max)
    alpha = jnp.exp(run_max - m)
    beta = jnp.exp(new_max - m)
    s = run_sum * alpha + new_sum * beta
    out = run_out * alpha[..., None] + new_out * beta[..., None]
    return m, s, out


def _block_attn(q, k, v, bias, scale):
    """Unnormalized block attention: returns (block_max, block_sum,
    block_out) for the online-softmax combine."""
    import jax.numpy as jnp

    # q [B,H,Sq,D] x k [B,H,Sk,D] -> scores [B,H,Sq,Sk]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if bias is not None:
        scores = scores + bias
    m = jnp.max(scores, axis=-1)  # [B,H,Sq]
    p = jnp.exp(scores - m[..., None])
    s = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m, s, out


def ring_attention(q, k, v, axis_name, causal=False, scale=None,
                   use_flash=None, interpret=None):
    """Attention over a sequence sharded on ``axis_name``.

    q/k/v: local shards [B, H, S_local, D]. Returns the local output shard
    [B, H, S_local, D]. With ``causal=True``, block (i attends j) is masked
    by global block order (devices earlier on the axis hold earlier
    positions); intra-block causal masking applies on the diagonal block.

    ``use_flash``: run each hop's block attention through the Pallas
    flash kernels (forward AND backward) instead of the dense jnp block —
    the per-hop [S_local, S_local] score tile then never leaves VMEM, and
    the scan residuals shrink from O(S_local^2) to O(S_local·D) per hop.
    Default (None): flash on the TPU backend, dense elsewhere;
    ``interpret`` forces the Pallas interpreter for tests.
    """
    import jax.lax as lax
    import jax.numpy as jnp

    if use_flash is None:
        from ..kernels.flash_attention import lowers_for_tpu

        use_flash = lowers_for_tpu() or bool(interpret)

    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    s_local = q.shape[2]
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)

    perm = [(i, (i + 1) % n) for i in range(n)]

    if use_flash:
        from ..kernels.flash_attention import flash_attention_lse

        def combine(acc, lse_b, o_b):
            # merge a normalized block output by its logsumexp weight
            lse_run, out_run = acc
            lse_new = jnp.logaddexp(lse_run, lse_b)
            out = (
                out_run * jnp.exp(lse_run - lse_new)[..., None]
                + o_b.astype(out_run.dtype)
                * jnp.exp(lse_b - lse_new)[..., None]
            )
            return lse_new, out

        # hop 0 is always the DIAGONAL block (K/V start local), so the
        # kernel's own static causal flag handles intra-block masking —
        # no [S_local, S_local] bias ever materializes, keeping the scan
        # residuals at O(S_local·D) per hop; it seeds the accumulator
        # directly (combining into a (-inf, 0) identity would just burn
        # an extra logaddexp/exp pass)
        o0, lse0 = flash_attention_lse(
            q, k, v, causal=causal, scale=scale, interpret=interpret,
        )
        acc0 = (lse0, o0.astype(jnp.float32))

        def step(carry, _):
            kv, src_idx, acc = carry
            k_blk = lax.ppermute(kv[0], axis_name, perm)
            v_blk = lax.ppermute(kv[1], axis_name, perm)
            src_idx = lax.ppermute(src_idx, axis_name, perm)
            o_b, lse_b = flash_attention_lse(
                q, k_blk, v_blk, scale=scale, interpret=interpret,
            )
            if causal:
                # off-diagonal hops are all-or-nothing: blocks from later
                # positions are erased by zeroing their combine weight
                lse_b = jnp.where(src_idx < my_idx, lse_b, -1e30)
            acc = combine(acc, lse_b, o_b)
            return ((k_blk, v_blk), src_idx, acc), None

        carry0 = ((k, v), my_idx, acc0)
        (_, _, (_lse, out)), _ = lax.scan(step, carry0, None, length=n - 1)
        return out.astype(q.dtype)

    neg = jnp.asarray(-1e9, q.dtype)

    def step(carry, _):
        kv, src_idx, acc = carry
        k_blk, v_blk = kv
        bias = None
        if causal:
            rows = jnp.arange(s_local)[:, None] + my_idx * s_local
            cols = jnp.arange(k_blk.shape[2])[None, :] + src_idx * s_local
            bias = jnp.where(cols <= rows, 0.0, neg).astype(q.dtype)
        m, s, out = _block_attn(q, k_blk, v_blk, bias, scale)
        acc = _online_combine(acc, m, s, out)
        # rotate K/V to the next device; the index travels with the block
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        idx_next = lax.ppermute(src_idx, axis_name, perm)
        return ((k_next, v_next), idx_next, acc), None

    init_acc = (
        jnp.full(q.shape[:3], -jnp.inf, q.dtype),          # running max
        jnp.zeros(q.shape[:3], q.dtype),                   # running sum
        jnp.zeros(q.shape, q.dtype),                       # running out
    )
    carry0 = ((k, v), my_idx, init_acc)
    (_, _, (m, s, out)), _ = lax.scan(step, carry0, None, length=n)
    return out / s[..., None]


def full_attention(q, k, v, causal=False, scale=None):
    """Single-device reference implementation (same math, materialized)."""
    import jax.numpy as jnp

    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        scores = jnp.where(mask, scores, -1e9)
    return jnp.einsum("bhqk,bhkd->bhqd", _softmax(scores), v)


def _softmax(x):
    import jax.numpy as jnp

    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def ring_attention_sharded(mesh, axis_name="sp", **kwargs):
    """Build a shard_map-wrapped ring attention over ``mesh``: takes GLOBAL
    [B, H, S, D] arrays sharded on S and returns the global output.
    ``kwargs`` (use_flash / interpret / scale) forward to
    ``ring_attention``."""
    from jax.sharding import PartitionSpec as P

    from .mesh import shard_map as _shard_map

    spec = P(None, None, axis_name, None)

    def fn(q, k, v, causal=False):
        inner = functools.partial(
            ring_attention, axis_name=axis_name, causal=causal, **kwargs
        )
        return _shard_map(
            lambda a, b, c: inner(a, b, c),
            mesh, (spec, spec, spec), spec,
        )(q, k, v)

    return fn
