"""Ulysses-style all-to-all sequence parallelism.

Complement to ring attention (parallel/ring_attention.py): instead of
rotating K/V blocks around the ring, one ``lax.all_to_all`` re-shards the
activations from sequence-sharded to HEAD-sharded, each device runs FULL
attention for its head group, and a second all_to_all restores sequence
sharding. Two collectives per attention layer (vs steps-1 permutes for
ring) — the better trade when head count >= sp and the sequence fits HBM;
ring attention remains the long-context fallback.

The reference (2019 CUDA/NCCL era) has no sequence parallelism at all
(SURVEY §5.7) — this is TPU-native new capability, not a port. Pattern
reference: DeepSpeed-Ulysses (arXiv:2309.14509), re-derived for
jax shard_map + ICI collectives.
"""

from __future__ import annotations

from .mesh import shard_map


def _attention(q, k, v, scale, causal=False):
    import jax
    import jax.numpy as jnp

    logits = jnp.einsum("bsnh,btnh->bnst", q, k) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnst,btnh->bsnh", probs, v)


def ulysses_attention(mesh, axis_name="sp", causal=False, use_flash=None,
                      interpret=None):
    """Returns fn(q, k, v) for GLOBAL arrays [B, S, N, H] sharded on S over
    ``axis_name``; computes exact full attention via two all_to_alls.

    ``use_flash``: after the head-scatter each device holds the FULL
    sequence for its head group, so the dense path materializes a
    [B, N/sp, S, S] score tensor — the Pallas flash kernels (forward and
    backward) keep it in VMEM instead. Default (None): flash on the TPU
    backend, dense elsewhere; ``interpret`` forces the Pallas interpreter
    for tests. ``causal`` masks by global position (exact, since the
    sequence is whole on each device here)."""
    import jax.lax as lax
    from jax.sharding import PartitionSpec as P

    sp = mesh.shape[axis_name]

    def local_fn(q, k, v):
        flash = use_flash
        if flash is None:
            from ..kernels.flash_attention import lowers_for_tpu

            flash = lowers_for_tpu() or bool(interpret)
        if q.shape[2] % sp != 0:
            raise ValueError(
                "ulysses_attention: head count %d must divide by sp=%d"
                % (q.shape[2], sp)
            )
        # [B, S/sp, N, H] -> all_to_all over heads -> [B, S, N/sp, H]
        def scatter_heads(x):
            # split axis 2 (heads) across the group, concat axis 1 (seq)
            return lax.all_to_all(
                x, axis_name, split_axis=2, concat_axis=1, tiled=True
            )

        def gather_heads(x):
            return lax.all_to_all(
                x, axis_name, split_axis=1, concat_axis=2, tiled=True
            )

        qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
        scale = qh.shape[-1] ** -0.5
        if flash:
            from ..kernels.flash_attention import flash_attention

            # kernel layout is [B, N, S, D]
            out = flash_attention(
                qh.transpose(0, 2, 1, 3), kh.transpose(0, 2, 1, 3),
                vh.transpose(0, 2, 1, 3), causal=causal, scale=scale,
                interpret=interpret,
            ).transpose(0, 2, 1, 3)
        else:
            out = _attention(qh, kh, vh, scale, causal)  # [B, S, N/sp, H]
        return gather_heads(out)  # [B, S/sp, N, H]

    spec = P(None, axis_name, None, None)
    return shard_map(
        local_fn, mesh, in_specs=(spec, spec, spec), out_specs=spec
    )



def reference_attention(q, k, v):
    """Single-device oracle for tests."""
    return _attention(q, k, v, q.shape[-1] ** -0.5)
