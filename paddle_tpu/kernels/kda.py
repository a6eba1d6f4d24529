"""One step of the gated delta rule (Kimi Delta Attention, arXiv:2510.26692)
for one token a slot, against a per-slot recurrent state.

    S~ = Diag(a) S;   u = b (v - S~^T k);   S' = S~ + k u^T;   o = S'^T q

``S`` is ``[key, value]`` float32 per head, ``a`` the per-KEY-CHANNEL decay
in (0, 1], ``b`` the scalar write strength in [0, 2). The state is read
once and written once, in place: 7 operations and 8 bytes a state element.
"""

import functools

import jax
import jax.numpy as jnp

from .flash_attention import lowers_for_tpu

# heads a program takes: the sublane tile of the [heads, 128] vector blocks
HEAD_BLOCK = 8


def kda_step_reference(state, q, k, v, a, b):
    """The step on gathered states ``state`` [B, H, dk, dv]; ``q``, ``k``,
    ``a`` [B, H, dk], ``v`` [B, H, dv], ``b`` [B, H]. Elementwise float32,
    no matmul: exact whatever the backend's default precision.
    -> (o [B, H, dv], state')."""
    sd = a[..., None] * state
    r = (k[..., None] * sd).sum(-2)
    u = b[..., None] * (v - r)
    s1 = sd + k[..., None] * u[..., None, :]
    return (q[..., None] * s1).sum(-2), s1


def _kda_decode_kernel(rows_ref, s_ref, q_ref, k_ref, a_ref, v_ref, b_ref,
                       o_ref, so_ref):
    """One (slot, block of heads) program. The vectors ride in as rows
    ``[heads, 128]``; ``q``, ``k`` and ``a`` index the state's SUBLANES
    (the key axis), so they are turned into columns by one product with
    the identity (exact in float32: the identity's ones and zeros are
    exact in every pass) and then broadcast over the value lanes. A
    head's state is 16 vector registers; everything else is elementwise
    and two sublane sums."""
    del rows_ref  # only the index maps read it
    hb, dk = q_ref.shape[1], q_ref.shape[2]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
           ).astype(jnp.float32)

    def columns(ref):
        return jax.lax.dot_general(
            eye, ref[0], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)          # [dk, heads]

    qc, kc, ac = columns(q_ref), columns(k_ref), columns(a_ref)
    outs = []
    for j in range(hb):
        kj = kc[:, j:j + 1]
        sd = ac[:, j:j + 1] * s_ref[0, j]                # [dk, dv]
        r = (kj * sd).sum(axis=0, keepdims=True)         # [1, dv]
        u = b_ref[0, j:j + 1, :] * (v_ref[0, j:j + 1, :] - r)
        s1 = sd + kj * u
        so_ref[0, j] = s1
        outs.append((qc[:, j:j + 1] * s1).sum(axis=0, keepdims=True))
    o_ref[0] = jnp.concatenate(outs, axis=0)


def kda_decode(state, rows, q, k, v, a, b, interpret=None):
    """The T = 1 step of every slot against the state var ``state``
    [R, H, dk, dv] float32, slot ``i`` on row ``rows[i]`` (runtime data:
    scalar prefetch on TPU, so the index maps chase it and ONE compiled
    kernel serves every mix of live slots). ``q``, ``k``, ``a``
    [B, H, dk], ``v`` [B, H, dv], ``b`` [B, H], float32. Slots that feed
    the same row (idle slots all feed the sink row 0) leave garbage
    there by contract. -> (o [B, H, dv] float32, state with the fed rows
    rewritten; on TPU the input buffer itself, ``input_output_aliases``).
    Dense gather-update-scatter off TPU, the same arithmetic."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, dk = q.shape
    dv = v.shape[-1]
    rows = rows.astype(jnp.int32).reshape(B)
    f32 = jnp.float32
    q, k, v, a = (t.astype(f32) for t in (q, k, v, a))
    b = b.astype(f32)
    if interpret is None and not lowers_for_tpu():
        o, s1 = kda_step_reference(state[rows], q, k, v, a, b)
        return o, state.at[rows].set(s1)
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 else H
    vec = lambda s, h, rows: (s, h, 0)            # noqa: E731
    srow = lambda s, h, rows: (rows[s], h, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H // hb),
        in_specs=[
            pl.BlockSpec((1, hb, dk, dv), srow),
            pl.BlockSpec((1, hb, dk), vec), pl.BlockSpec((1, hb, dk), vec),
            pl.BlockSpec((1, hb, dk), vec), pl.BlockSpec((1, hb, dv), vec),
            pl.BlockSpec((1, hb, dv), vec),
        ],
        out_specs=[pl.BlockSpec((1, hb, dv), vec),
                   pl.BlockSpec((1, hb, dk, dv), srow)],
    )
    o, state = pl.pallas_call(
        _kda_decode_kernel,
        name="kda_decode",
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        grid_spec=grid_spec,
        # operand 0 is the prefetched ``rows``; the state is operand 1
        input_output_aliases={1: 1},
        interpret=bool(interpret),
    )(rows, state, q, k, a, v, jnp.broadcast_to(b[..., None], (B, H, dv)))
    return o, state
