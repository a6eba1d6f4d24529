"""Ops of a pre-norm decoder with latent (MLA) attention and routed
experts (the ``deepseek_v3`` block): RMSNorm, rotary positions at fed
positions, the gated SiLU product, one routed-expert op, and latent
attention in its two forms (up-projected for a window of queries,
absorbed for one query a slot read through a block table).

The ops a trained model of this kind needs register a gradient:
``rms_norm``, ``rotary_embedding``, ``swiglu`` and ``gated_short_conv``
through the generic maker (the vjp of the lowering), ``moe_ffn`` through
a lowering of its own (``moe_ffn_grad``); the attention, delta-rule and
paged ops are inference ops and register none. Matmuls take their
operands in the dtype they come in (bfloat16 in a served program, and
under AMP in a trained one) and accumulate in float32; norms, softmax
and the router compute in float32.
"""

from __future__ import annotations

import functools
import math

from .registry import (GRAD_SUFFIX, generic_grad_maker, in_var, op,
                       same_shape_infer, set_out)

_NEG = -1e30
_WINDOW_BLOCK = 512   # queries a block, keys a chunk of ``mla_window``


def rms_norm(x, w, eps):
    """w * x / sqrt(mean(x^2) + eps) over the last axis, statistics in
    float32, result in x's dtype."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


@op("rms_norm", infer_shape=same_shape_infer("X"), grad="generic")
def _rms_norm(ctx, op_):
    """RMSNorm over the last axis: ``Scale * X / sqrt(mean(X^2) +
    epsilon)``."""
    ctx.out(op_, "Out", rms_norm(
        ctx.in1(op_, "X"), ctx.in1(op_, "Scale"),
        float(op_.attr("epsilon", 1e-6))))


def rotary(x, pos, head_dim, rope_dim, theta, interleaved):
    """Rotate the LAST ``rope_dim`` values of every ``head_dim``-wide head
    of ``x`` [N, T, heads*head_dim] by the fed positions ``pos`` [N, T].
    ``interleaved``: the values come as pairs (x0, y0, x1, y1, ...) and
    are regrouped to (x0, x1, ..., y0, y1, ...) before the rotation by
    halves, as ``apply_rotary_pos_emb_interleave`` of the ``deepseek_v3``
    modelling code does; the result keeps the regrouped order."""
    import jax.numpy as jnp

    n, t, c = x.shape
    xh = x.reshape(n, t, c // head_dim, head_dim)
    keep, r = xh[..., :head_dim - rope_dim], xh[..., head_dim - rope_dim:]
    r = r.astype(jnp.float32)
    half = rope_dim // 2
    if interleaved:
        r = r.reshape(r.shape[:-1] + (half, 2))
        r = jnp.swapaxes(r, -1, -2).reshape(r.shape[:-2] + (rope_dim,))
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.reshape(n, t, 1, 1).astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    r1, r2 = r[..., :half], r[..., half:]
    rot = jnp.concatenate([r1 * cos - r2 * sin, r2 * cos + r1 * sin], -1)
    return jnp.concatenate([keep, rot.astype(x.dtype)], -1).reshape(n, t, c)


@op("rotary_embedding", infer_shape=same_shape_infer("X"), grad="generic")
def _rotary_embedding(ctx, op_):
    """Rotary positions at FED positions (``Pos`` [N, T] or [N, T, 1]) on
    the last ``rope_dim`` values of each ``head_dim`` chunk of ``X``
    [N, T, C]; see ``rotary``. The gradient is the inverse rotation of
    the cotangent (``Pos`` takes none)."""
    ctx.out(op_, "Out", rotary(
        ctx.in1(op_, "X"), ctx.in1(op_, "Pos"),
        int(op_.attr("head_dim")), int(op_.attr("rope_dim")),
        float(op_.attr("theta", 10000.0)),
        bool(op_.attr("interleaved", False))))


@op("swiglu", infer_shape=same_shape_infer("Gate"), grad="generic")
def _swiglu(ctx, op_):
    """silu(Gate) * Up, computed in float32, in Gate's dtype."""
    import jax
    import jax.numpy as jnp

    g = ctx.in1(op_, "Gate")
    u = ctx.in1(op_, "Up")
    ctx.out(op_, "Out", (jax.nn.silu(g.astype(jnp.float32))
                         * u.astype(jnp.float32)).astype(g.dtype))


def route(x, wg, bias, k, scaling, scoring="sigmoid", norm_topk=True,
          norm_eps=1e-20):
    """-> (experts [T, k] int32, gates [T, k] float32). Scores are
    sigmoid(x Wg), or with ``scoring="softmax"`` softmax(x Wg) over all
    the router's outputs, in float32 from the float32-cast input; the k
    experts are the top k of score + bias; gates are the chosen SCORES
    (the bias chooses and does not weigh), renormalised to sum 1 (over
    their sum + ``norm_eps``) unless ``norm_topk`` is false, and scaled.
    Under a gradient the gates differentiate in ``x`` and ``wg``; the
    choice and the bias do not."""
    import jax
    import jax.numpy as jnp

    score = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[scoring]
    s = score(jnp.dot(
        x.astype(jnp.float32), wg.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(s + bias.astype(jnp.float32)[None, :], k)
    chosen = jnp.take_along_axis(s, experts, axis=1)
    if norm_topk:
        gates = scaling * chosen / (chosen.sum(-1, keepdims=True)
                                    + norm_eps)
    else:
        gates = scaling * chosen
    return experts.astype(jnp.int32), gates


def grouped_experts(x, experts, gates, w1, w3, w2, expert_offset,
                    out_dtype=None):
    """The part of sum_e gate_e * E_e(x) that the HELD experts give
    (global numbers ``expert_offset`` .. ``expert_offset + E_held - 1``),
    and the assignments each held expert received. ``x`` [T, H],
    ``experts``/``gates`` [T, k], ``w1``/``w3`` [E_held, H, I], ``w2``
    [E_held, I, H]. Assignments are sorted by expert and each group runs
    as one product (``jax.lax.ragged_dot``): work follows the assignments,
    nothing is dropped, and nothing of shape [T, E, I] exists. An
    assignment to any other number (an expert held elsewhere, an identity
    expert) takes no row of a group. The sum comes in ``out_dtype``
    (``x``'s unless told)."""
    import jax
    import jax.numpy as jnp

    t, k = experts.shape
    held, order, sizes = _sort_by_expert(experts, w1.shape[0], expert_offset)
    xs = x[order // k]
    f32 = jnp.float32
    a = jax.lax.ragged_dot(xs, w1, sizes, preferred_element_type=f32)
    b = jax.lax.ragged_dot(xs, w3, sizes, preferred_element_type=f32)
    h = (jax.nn.silu(a) * b).astype(x.dtype)
    y = jax.lax.ragged_dot(h, w2, sizes, preferred_element_type=f32)
    weight = jnp.where(held, gates, 0.0).reshape(-1)[order]
    y = jnp.where(weight[:, None] != 0.0, y * weight[:, None], 0.0)
    # back to assignment order, then the k parts of a token add up
    y = y[jnp.argsort(order)].reshape(t, k, -1).sum(1)
    return y.astype(out_dtype or x.dtype), sizes


def _sort_by_expert(experts, held_n, expert_offset):
    """-> (held [T, k] bool: the assignment is to an expert held here,
    order [T*k]: the assignments sorted by held expert, sizes int32
    [E_held]: the rows of each group)."""
    import jax.numpy as jnp

    local = experts - expert_offset
    held = (local >= 0) & (local < held_n)
    # an assignment to an expert held elsewhere sorts past every group
    flat = jnp.where(held, local, held_n).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=held_n + 1)[:held_n].astype(jnp.int32)
    return held, order, sizes


def grouped_experts_grad(x, experts, gates, w1, w3, w2, expert_offset, dout):
    """The transposes of ``grouped_experts`` for the cotangent ``dout``
    [T, H] of its sum: -> (dx [T, H] float32, dgates [T, k] float32, dW1,
    dW3 [E_held, H, I], dW2 [E_held, I, H] in the weights' dtype). The
    sort and the two up products are the forward's expressions again,
    which the compiler shares where both passes are in one program (the
    step compiled for a v5e runs 9 grouped products a layer, not 11, and
    keeps the two float32 [T*k, I] products between the passes); their
    SiLU, the gated product and the down product are not kept. The
    products take ``x``'s dtype (bfloat16 under AMP) and accumulate in
    float32. dX and
    dW run over the held experts' groups alone
    (``jax.lax.ragged_dot_general``): nothing of shape [T, E, I], no
    assignment dropped. The gate's cotangent is <E_e(x), dout> computed
    as <h, dout W2_e^T>, so the unweighted down product is never
    formed."""
    import jax
    import jax.numpy as jnp

    t, k = experts.shape
    f32, dt = jnp.float32, x.dtype
    held, order, sizes = _sort_by_expert(experts, w1.shape[0], expert_offset)
    token = order // k
    # rows past the last group belong to no held expert: what a grouped
    # product leaves there is not defined, so they are zeroed
    grouped = (jnp.arange(t * k) < sizes.sum())[:, None]
    xs = x[token]
    up = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                           preferred_element_type=f32)
    a = jnp.where(grouped, up(xs, w1), 0.0)
    b = jnp.where(grouped, up(xs, w3), 0.0)
    sig = jax.nn.sigmoid(a)
    silu = a * sig
    h = silu * b
    weight = jnp.where(held, gates, 0.0).reshape(-1)[order][:, None]
    g = dout[token].astype(dt)
    dh = jnp.where(grouped, up(g, jnp.swapaxes(w2, 1, 2)), 0.0)
    dgates = (h * dh).sum(-1)
    dh = dh * weight
    da = (dh * b * sig * (1.0 + a * (1.0 - sig))).astype(dt)
    db = (dh * silu).astype(dt)

    def per_group(rows, cots, like):
        """[E_held, rows' width, cots' width]: rows^T cots a group."""
        dims = jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=(0,), rhs_group_dimensions=())
        return jax.lax.ragged_dot_general(
            rows, cots, sizes, dims,
            preferred_element_type=f32).astype(like.dtype)

    dw2 = per_group((h * weight).astype(dt), g, w2)
    dw1, dw3 = per_group(xs, da, w1), per_group(xs, db, w3)
    dxs = (up(da, jnp.swapaxes(w1, 1, 2)) + up(db, jnp.swapaxes(w3, 1, 2)))
    back = jnp.argsort(order)
    dx = jnp.where(grouped, dxs, 0.0)[back].reshape(t, k, -1).sum(1)
    dgates = jnp.where(held, dgates[back].reshape(t, k), 0.0)
    return dx, dgates, dw1, dw3, dw2


def identity_experts(x, experts, gates, num_experts):
    """What the IDENTITY experts give (router outputs ``num_experts`` and
    above: ``E(x) = x``, no weights): ``(sum of their gates) * x`` a token,
    float32, and how many assignments went to them."""
    import jax.numpy as jnp

    zero = experts >= num_experts
    gate = jnp.where(zero, gates, 0.0).sum(-1, keepdims=True)
    return gate * x.astype(jnp.float32), zero.sum().astype(jnp.int32)


def _moe_ffn_infer(op_, block):
    x = in_var(op_, block, "X")
    w1 = in_var(op_, block, "W1")
    set_out(op_, block, "Out", list(x.shape), x.dtype)
    set_out(op_, block, "Counts", [int(w1.shape[0])], "int32")
    if op_.outputs.get("ZeroCount"):
        set_out(op_, block, "ZeroCount", [1], "int32")


def _moe_route_attrs(op_):
    return dict(k=int(op_.attr("experts_per_token")),
                scaling=float(op_.attr("scaling", 1.0)),
                scoring=op_.attr("scoring", "sigmoid"),
                norm_topk=bool(op_.attr("norm_topk", True)),
                norm_eps=float(op_.attr("norm_eps", 1e-20)))


def _moe_ffn_grad_maker(op_):
    """``moe_ffn_grad``: the forward's inputs and ``Out@GRAD`` in,
    gradients of ``X``, ``RouterW`` and the three expert stacks out. The
    choice is not differentiable: ``RouterBias`` gets no gradient, and
    ``Counts`` / ``ZeroCount`` carry none back."""
    spec = generic_grad_maker(op_)[0]
    for slot in ("Out", "Counts", "ZeroCount", "Counts" + GRAD_SUFFIX,
                 "ZeroCount" + GRAD_SUFFIX):
        spec["inputs"].pop(slot, None)
    spec["outputs"].pop("RouterBias" + GRAD_SUFFIX, None)
    return [spec]


@op("moe_ffn", infer_shape=_moe_ffn_infer, grad=_moe_ffn_grad_maker)
def _moe_ffn(ctx, op_):
    """The routed part of a sparse expert layer. ``X`` [..., H] tokens,
    ``RouterW`` [H, num_experts], ``RouterBias`` [num_experts] (added to
    the scores to CHOOSE, not to weigh), stacked held experts ``W1``,
    ``W3`` [E_held, H, I] and ``W2`` [E_held, I, H] (``E_e(x) =
    (silu(x W1_e) * (x W3_e)) W2_e``). Every token routes over all
    ``num_experts`` (float32 sigmoid scores, top ``experts_per_token`` of
    score + bias, gates renormalised and times ``scaling``); the op adds
    up what the experts it HOLDS give, global numbers ``expert_offset`` ..
    ``expert_offset + E_held - 1`` — which is what expert parallelism
    asks of one shard; shards' results add up to the whole layer. No
    capacity, no token dropped. ``Counts`` int32 [E_held]: assignments
    each held expert received. Attributes whose defaults are the above:
    ``scoring`` (``sigmoid`` | ``softmax`` over all the router's outputs),
    ``norm_topk`` (false: the chosen scores weigh as they are) and
    ``zero_experts``: ``RouterW``/``RouterBias`` are then ``num_experts +
    zero_experts`` wide and the last ``zero_experts`` outputs are IDENTITY
    experts, which add ``gate * x``, hold no weights and take no row of a
    grouped product; their part is computed for EVERY token given, as a
    shared expert's would be (under expert parallelism a token's home
    shard adds it, no exchange), and ``ZeroCount`` int32 [1] gives the
    assignments that went to them; ``norm_eps`` (1e-20) is what the
    renormalisation adds to the chosen scores' sum. The gradient is
    ``moe_ffn_grad``: of ``X``, ``RouterW`` (through the scores and the
    gates) and the held experts' stacks; the choice, ``RouterBias`` and
    the counts take none."""
    import jax
    import jax.numpy as jnp

    x = ctx.in1(op_, "X")
    n, zeros = int(op_.attr("num_experts")), int(op_.attr("zero_experts", 0))
    with jax.named_scope("moe_ffn"):
        x2 = x.reshape(-1, x.shape[-1])
        experts, gates = route(
            x2, ctx.in1(op_, "RouterW"), ctx.in1(op_, "RouterBias"),
            **_moe_route_attrs(op_))
        y, counts = grouped_experts(
            x2, experts, gates, ctx.in1(op_, "W1"), ctx.in1(op_, "W3"),
            ctx.in1(op_, "W2"), int(op_.attr("expert_offset", 0)),
            out_dtype=jnp.float32 if zeros else None)
        if zeros:
            same, zero_count = identity_experts(x2, experts, gates, n)
            y = (y + same).astype(x.dtype)
            ctx.out(op_, "ZeroCount", zero_count.reshape(1))
    ctx.out(op_, "Out", y.reshape(x.shape))
    ctx.out(op_, "Counts", counts)


@op("moe_ffn_grad")
def _moe_ffn_grad(ctx, op_):
    """The gradient of ``moe_ffn`` from its inputs and ``Out@GRAD``:
    ``route`` again (its vjp gives dX and dRouterW from the gates'
    cotangent) and ``grouped_experts_grad``. A lowering of its own and
    not the vjp of the forward's: that one keeps, beside the float32
    [T*k, I] products of both up projections, their SiLU, the gated
    product and the unweighted [T*k, H] down product from the forward
    pass to the backward (1.5 GB more temporaries in the step of
    ``lfm2-8b-a1b`` at 8,192 tokens: PERF.md, PR 44)."""
    import jax
    import jax.numpy as jnp

    if int(op_.attr("zero_experts", 0)):
        raise NotImplementedError(
            "moe_ffn_grad: identity experts (zero_experts) are not "
            "differentiated; no trained model has them")
    x, dout = ctx.in1(op_, "X"), ctx.in1(op_, "Out" + GRAD_SUFFIX)
    wg, bias = ctx.in1(op_, "RouterW"), ctx.in1(op_, "RouterBias")
    w1, w3, w2 = (ctx.in1(op_, n) for n in ("W1", "W3", "W2"))
    attrs = _moe_route_attrs(op_)
    with jax.named_scope("moe_ffn_grad"):
        x2 = x.reshape(-1, x.shape[-1])
        gates, route_vjp, experts = jax.vjp(
            lambda x_, wg_: route(x_, wg_, bias, **attrs)[::-1],
            x2, wg, has_aux=True)
        dx, dgates, dw1, dw3, dw2 = grouped_experts_grad(
            x2, experts, gates, w1, w3, w2,
            int(op_.attr("expert_offset", 0)),
            dout.reshape(-1, dout.shape[-1]))
        dx_route, dwg = route_vjp(dgates)
        dx = (dx + dx_route.astype(jnp.float32)).astype(x.dtype)
    for slot, grad in (("X", dx.reshape(x.shape)), ("RouterW", dwg),
                       ("W1", dw1), ("W3", dw3), ("W2", dw2)):
        ctx.out(op_, slot + GRAD_SUFFIX, grad)


def mla_window(q, rows, wkvb, qpos, heads, nope, rope, vdim):
    """Up-projected latent attention of a window of queries. ``q``
    [N, T, heads*(nope+rope)] (rope part rotated), ``rows`` [N, S, W]
    latent rows (normed latent, rotated shared rope key, padding),
    ``wkvb`` [latent, heads*(nope+vdim)], ``qpos`` [N, T]: query i sees
    the keys at positions <= qpos[i]. -> [N, T, heads*vdim]. Blocks of
    queries go one after another over chunks of keys with a running
    softmax, and a block stops at the last chunk one of its queries can
    see: up-projection, scores and softmax follow the keys a window can
    see, not the row's capacity. Blocks and chunks are the largest
    divisors of T and S up to ``_WINDOW_BLOCK``."""
    import jax
    import jax.numpy as jnp

    n, t, _ = q.shape
    s, latent = rows.shape[1], wkvb.shape[0]
    tq, tk = math.gcd(t, _WINDOW_BLOCK), math.gcd(s, _WINDOW_BLOCK)
    f32 = jnp.float32
    scale = 1.0 / float(nope + rope) ** 0.5
    # [blocks of queries, N, tq, ...]
    q = q.reshape(n, t // tq, tq, heads, nope + rope).swapaxes(0, 1)
    qpos = qpos.reshape(n, t // tq, 1, tq, 1).astype(jnp.int32).swapaxes(0, 1)

    def attend(queries):
        q, qpos = queries              # [N, tq, heads, d], [N, 1, tq, 1]

        def chunk(i, carry):
            top, total, acc = carry
            r = jax.lax.dynamic_slice_in_dim(rows, i * tk, tk, axis=1)
            c, kr = r[..., :latent], r[..., latent:latent + rope]
            kv = jnp.einsum("nsl,lf->nsf", c, wkvb,
                            preferred_element_type=f32)
            kv = kv.astype(q.dtype).reshape(n, tk, heads, nope + vdim)
            sc = jnp.einsum("nthd,nshd->nhts", q[..., :nope], kv[..., :nope],
                            preferred_element_type=f32)
            sc = sc + jnp.einsum("nthr,nsr->nhts", q[..., nope:], kr,
                                 preferred_element_type=f32)
            seen = i * tk + jnp.arange(tk)[None, None, None, :] <= qpos
            sc = jnp.where(seen, sc * scale, _NEG)
            # every query sees key 0, so ``top`` is a real score from the
            # first chunk on and a masked score's weight is exp(-huge) = 0
            new_top = jnp.maximum(top, sc.max(-1, keepdims=True))
            p = jnp.exp(sc - new_top)
            keep = jnp.exp(top - new_top)
            pv = jnp.einsum("nhts,nshv->nhtv", p.astype(q.dtype),
                            kv[..., nope:], preferred_element_type=f32)
            return (new_top, keep * total + p.sum(-1, keepdims=True),
                    keep * acc + pv)

        init = (jnp.full((n, heads, tq, 1), _NEG, f32),
                jnp.zeros((n, heads, tq, 1), f32),
                jnp.zeros((n, heads, tq, vdim), f32))
        _top, total, acc = jax.lax.fori_loop(
            0, qpos.max() // tk + 1, chunk, init)
        return (acc / total).astype(q.dtype)

    o = jax.lax.map(attend, (q, qpos))           # [blocks, N, heads, tq, v]
    return o.transpose(1, 0, 3, 2, 4).reshape(n, t, heads * vdim)


def _mla_out_infer(op_, block):
    q = in_var(op_, block, "Q")
    heads, vdim = int(op_.attr("num_heads")), int(op_.attr("v_dim"))
    set_out(op_, block, "Out", list(q.shape[:2]) + [heads * vdim], q.dtype)


@op("mla_window_attention", infer_shape=_mla_out_infer)
def _mla_window_attention(ctx, op_):
    """Latent attention, the UP-PROJECTED form (a prefill window, or a
    whole prompt without a cache): keys and values of every row come from
    ``Rows`` [N, S, W] through ``Wkvb``; ``QPos`` [N, T] (or [N, T, 1])
    gives the causal mask, key j visible to query i iff j <= QPos[i]. See
    ``mla_window``. Inference only (no grad op)."""
    import jax

    with jax.named_scope("mla_window"):
        out = mla_window(
            ctx.in1(op_, "Q"), ctx.in1(op_, "Rows"), ctx.in1(op_, "Wkvb"),
            ctx.in1(op_, "QPos"), int(op_.attr("num_heads")),
            int(op_.attr("nope_dim")), int(op_.attr("rope_dim")),
            int(op_.attr("v_dim")))
    ctx.out(op_, "Out", out)


def mla_absorbed(q, pool, tables, lengths, wkvb, heads, nope, rope, vdim,
                 interpret=None):
    """Absorbed latent attention of ONE query a slot against the paged
    latent pool: the key up-projection is folded into the query
    (``q_nope Wkvb_K^T``, latent wide), scores and the weighted sum run
    over the latent rows as they lie in the pool (kernel
    ``mla_decode_paged``), and the value up-projection is applied to the
    sum. ``q`` [B, 1, heads*(nope+rope)], ``pool`` [blocks, 1, block, W],
    ``tables`` [B, max_blocks], ``lengths`` [B] live keys a slot.
    -> [B, 1, heads*vdim]."""
    import jax.numpy as jnp

    from ...kernels.flash_attention import mla_decode_paged_attention

    b = q.shape[0]
    latent = wkvb.shape[0]
    width = pool.shape[-1]
    f32 = jnp.float32
    q = q.reshape(b, heads, nope + rope)
    w = wkvb.reshape(latent, heads, nope + vdim)
    qa = jnp.einsum("bhd,lhd->bhl", q[..., :nope], w[..., :nope],
                    preferred_element_type=f32)
    qfull = jnp.concatenate([
        qa.astype(pool.dtype), q[..., nope:].astype(pool.dtype),
        jnp.zeros((b, heads, width - latent - rope), pool.dtype)], -1)
    u = mla_decode_paged_attention(
        qfull, pool.reshape(pool.shape[0], pool.shape[2], width), tables,
        lengths, latent, 1.0 / float(nope + rope) ** 0.5,
        interpret=interpret)
    o = jnp.einsum("bhl,lhv->bhv", u.astype(q.dtype), w[..., nope:],
                   preferred_element_type=f32)
    return o.astype(q.dtype).reshape(b, 1, heads * vdim)


@op("mla_decode_paged_attention", infer_shape=_mla_out_infer)
def _mla_decode_paged_attention(ctx, op_):
    """Latent attention, the ABSORBED form (the T = 1 step): one query a
    slot against the latent ``Pool`` [blocks, 1, block, W] read through
    ``Tables`` [slots, max_blocks] up to ``Lengths`` [slots] live keys;
    see ``mla_absorbed``. Inference only (no grad op)."""
    import jax

    with jax.named_scope("mla_absorb"):
        out = mla_absorbed(
            ctx.in1(op_, "Q"), ctx.in1(op_, "Pool"),
            ctx.in1(op_, "Tables"), ctx.in1(op_, "Lengths"),
            ctx.in1(op_, "Wkvb"), int(op_.attr("num_heads")),
            int(op_.attr("nope_dim")), int(op_.attr("rope_dim")),
            int(op_.attr("v_dim")),
            interpret=bool(op_.attr("interpret", False)) or None)
    ctx.out(op_, "Out", out)


# --------------------------------------------------------------------------
# gated delta rule (Kimi Delta Attention) and grouped-query softmax layers
# --------------------------------------------------------------------------

_KDA_CHUNK = 64       # tokens a chunk of ``kda_chunked``
_KDA_SUB = 16         # rows a sub-block of a chunk's triangular matrices


def short_conv(x, tail, w):
    """Depthwise causal convolution over time, no bias: ``x`` [T, C] the
    new rows, ``tail`` [K-1, C] the K-1 rows before them (zeros at the
    start of a sequence), ``w`` [K, C] (tap K-1 multiplies the current
    row). float32. -> (y [T, C], padded [T+K-1, C], the rows the next
    call's tail is cut from)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    taps = w.shape[0]
    padded = jnp.concatenate([tail.astype(f32), x.astype(f32)], axis=0)
    t = x.shape[0]
    y = sum(w[j].astype(f32)[None, :] * padded[j:j + t] for j in range(taps))
    return y, padded


def _gated_short_conv_infer(op_, block):
    x = in_var(op_, block, "X")
    set_out(op_, block, "Out", list(x.shape[:-1]) + [x.shape[-1] // 3],
            x.dtype)


@op("gated_short_conv", infer_shape=_gated_short_conv_infer, grad="generic")
def _gated_short_conv(ctx, op_):
    """The mixing of a gated short-convolution layer over whole
    sequences: ``X`` [N, T, 3*C] is ``B ‖ C ‖ x`` (one input projection),
    ``ConvW`` [K, C] the taps of a depthwise causal convolution over time
    (``short_conv``: tap K-1 on the current row, zeros before the
    sequence, no bias). ``Out = C * conv(B * x)`` [N, T, C], float32
    inside, in ``X``'s dtype."""
    import jax
    import jax.numpy as jnp

    x, w = ctx.in1(op_, "X"), ctx.in1(op_, "ConvW")
    c = x.shape[-1] // 3
    tail = jnp.zeros((w.shape[0] - 1, c), jnp.float32)

    def one(row):
        row = row.astype(jnp.float32)
        y, _padded = short_conv(row[:, :c] * row[:, 2 * c:], tail, w)
        return row[:, c:2 * c] * y

    with jax.named_scope("short_conv"):
        out = jax.vmap(one)(x).astype(x.dtype)
    ctx.out(op_, "Out", out)


def kda_inputs(y, f, bt, a_log, dt_bias, heads, head_dim):
    """From the convolved rows ``y`` [..., 3*H*D] (q' ‖ k' ‖ v' before the
    SiLU), the decay pre-activation ``f`` [..., H*D] and the write
    pre-activation ``bt`` [..., H], all float32:
    q = L2norm(SiLU q') * D^-1/2, k = L2norm(SiLU k'), v = SiLU v',
    g = -exp(A_log) * softplus(f + dt_bias) (log of the per-key-channel
    decay), b = 2 sigmoid(bt). -> (q, k, v, g [..., H, D], b [..., H])."""
    import jax
    import jax.numpy as jnp

    lead = y.shape[:-1]
    y = jax.nn.silu(y).reshape(lead + (3, heads, head_dim))
    q, k, v = y[..., 0, :, :], y[..., 1, :, :], y[..., 2, :, :]

    def l2(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    g = -jnp.exp(a_log.astype(jnp.float32))[:, None] * jax.nn.softplus(
        (f + dt_bias.astype(jnp.float32)).reshape(lead + (heads, head_dim)))
    return (l2(q) * head_dim ** -0.5, l2(k), v, g,
            2.0 * jax.nn.sigmoid(bt))


def kda_chunked(q, k, v, g, b, s0, chunk=_KDA_CHUNK):
    """The gated delta rule over ``T`` tokens in chunks (never token by
    token): ``q``, ``k``, ``g`` [T, H, dk], ``v`` [T, H, dv], ``b``
    [T, H], ``s0`` [H, dk, dv], float32; ``g <= 0`` is the log decay.

        S_t = Diag(e^g_t) S_{t-1} + k_t u_t^T,   o_t = S_t^T q_t,
        u_t = b_t (v_t - S_{t-1}^T (e^g_t . k_t))

    Inside a chunk, with G the running sum of g: u solves the unit lower
    triangular system (I + Diag(b) A) U = Diag(b)(V - (K e^G) S_0), where
    A_ts = sum_d k_td k_sd e^(G_td - G_sd) for s < t, then
    O = (Q e^G) S_0 + A^q U (A^q with q for the row's k, s <= t) and one
    state pass S_C = Diag(e^G_C) S_0 + (K e^(G_C - G))^T U. Every exponent
    is <= 0: the triangular matrices are built in sub-blocks of
    ``_KDA_SUB`` rows, a diagonal sub-block elementwise over the key
    channels, an off-diagonal one as a product of two factors each
    relative to the row block's start. A token with ``g = 0, b = 0``
    (padding past a window's last real token) leaves the state as it is.
    -> (o [T, H, dv], S_T)."""
    import jax
    import jax.numpy as jnp

    t, heads, dk = q.shape
    c = math.gcd(t, chunk)
    sub = math.gcd(c, _KDA_SUB)
    m = c // sub
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def chunks(x):                    # [T, H, ...] -> [n, H, c, ...]
        return jnp.swapaxes(x.reshape((t // c, c) + x.shape[1:]), 1, 2)

    lower = jnp.tril(jnp.ones((sub, sub), bool))            # s <= r
    strict = jnp.tril(jnp.ones((sub, sub), bool), -1)
    block_of = jnp.arange(c) // sub
    before = block_of[None, :] < block_of[:, None]          # [c, c]
    eye_m = jnp.eye(m, dtype=f32)

    def step(s, xs):
        q, k, v, g, b = xs            # [H, c, dk] .. [H, c]
        G = jnp.cumsum(g, axis=1)
        # G just before each sub-block's first row
        start = jnp.concatenate(
            [jnp.zeros_like(G[:, :1]), G[:, sub - 1:c - 1:sub]], axis=1)
        Gb = G.reshape(heads, m, sub, dk)
        rel = jnp.exp(Gb - start[:, :, None, :])            # row side, <= 1
        # column side for row block i: e^(start_i - G_s), s before block i
        col = jnp.exp(jnp.minimum(
            start[:, :, None, :] - G[:, None, :, :], 0.0))  # [H, m, c, dk]
        kcol = k[:, None] * col
        kb, qb = k.reshape(heads, m, sub, dk), q.reshape(heads, m, sub, dk)
        # diagonal sub-blocks: [H, m, r, s, dk], exponent <= 0 where s <= r
        e = jnp.exp(jnp.minimum(
            Gb[:, :, :, None, :] - Gb[:, :, None, :, :], 0.0))
        ek = e * kb[:, :, None, :, :]

        def tri(rows, keep):
            diag = jnp.where(keep, (rows[:, :, :, None, :] * ek).sum(-1), 0.0)
            off = jnp.einsum("hird,hisd->hirs", rows * rel, kcol,
                             precision=hi).reshape(heads, c, c)
            full = jnp.einsum("hirs,ij->hirjs", diag, eye_m)
            return jnp.where(before, off, full.reshape(heads, c, c))

        a_k, a_q = tri(kb, strict), tri(qb, lower)
        decay = jnp.exp(G)
        rhs = b[..., None] * (v - jnp.einsum(
            "hcd,hdv->hcv", k * decay, s, precision=hi))
        system = jnp.eye(c, dtype=f32) + b[..., None] * a_k
        u = jax.lax.linalg.triangular_solve(
            system, rhs, left_side=True, lower=True, unit_diagonal=True)
        o = (jnp.einsum("hcd,hdv->hcv", q * decay, s, precision=hi)
             + jnp.einsum("hcs,hsv->hcv", a_q, u, precision=hi))
        to_end = jnp.exp(G[:, -1:, :] - G)
        s = (decay[:, -1, :, None] * s
             + jnp.einsum("hcd,hcv->hdv", k * to_end, u, precision=hi))
        return s, o

    s, o = jax.lax.scan(
        step, s0.astype(f32),
        tuple(chunks(x.astype(f32)) for x in (q, k, v, g, b)))
    return jnp.swapaxes(o, 1, 2).reshape(t, heads, -1), s


def kda_window(qkv, f, bt, conv_w, a_log, dt_bias, heads, head_dim,
               state=None, conv=None, row=None, start=None, length=None):
    """A KDA layer's mixing over ONE window ``qkv`` [T, 3*H*D] (q~ ‖ k~ ‖
    v~ before the convolution), ``f`` [T, H*D], ``bt`` [T, H]. With the
    state vars (``state`` [R, H, D, D] float32, ``conv`` [R, K-1, 3*H*D]):
    the window starts from zeros where ``start`` is 0 and from row ``row``
    otherwise, tokens at or past ``length`` are padding (``b = 0, g = 0``,
    the tail cut at the real end), and the row is rewritten. Without:
    zeros, every token real. -> (o [T, H*D] in qkv's dtype, state, conv)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t = qkv.shape[0]
    taps = conv_w.shape[0]
    s0 = jnp.zeros((heads, head_dim, head_dim), f32)
    tail = jnp.zeros((taps - 1, qkv.shape[1]), f32)
    if state is not None:
        fresh = start <= 0
        s0 = jnp.where(fresh, s0, state[row])
        tail = jnp.where(fresh, tail, conv[row].astype(f32))
    y, padded = short_conv(qkv, tail, conv_w)
    q, k, v, g, b = kda_inputs(y, f.astype(f32), bt.astype(f32), a_log,
                               dt_bias, heads, head_dim)
    if length is not None:
        real = (jnp.arange(t) < length)[:, None]
        g = jnp.where(real[..., None], g, 0.0)
        b = jnp.where(real, b, 0.0)
    o, s1 = kda_chunked(q, k, v, g, b, s0)
    o = o.reshape(t, heads * head_dim).astype(qkv.dtype)
    if state is None:
        return o, None, None
    # rows length .. length + K - 2 of ``padded`` are the last K-1 real ones
    new_tail = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, axis=0)
    return (o, state.at[row].set(s1),
            conv.at[row].set(new_tail.astype(conv.dtype)))


def _kda_attrs(op_):
    return int(op_.attr("num_heads")), int(op_.attr("head_dim"))


def _kda_infer(op_, block):
    f = in_var(op_, block, "F")
    set_out(op_, block, "Out", list(f.shape), in_var(op_, block, "QKV").dtype)
    for slot, out in (("State", "StateOut"), ("Conv", "ConvOut")):
        if op_.inputs.get(slot):
            v = in_var(op_, block, slot)
            set_out(op_, block, out, list(v.shape), v.dtype)


def _scalar(x):
    import jax.numpy as jnp

    return x.reshape(-1)[0].astype(jnp.int32)


@op("kda_window", infer_shape=_kda_infer)
def _kda_window(ctx, op_):
    """The gated delta-rule (KDA) mixing of a window, chunked
    (``kda_chunked``): ``QKV`` [N, T, 3*H*D], ``F`` [N, T, H*D], ``B``
    [N, T, H], ``ConvW`` [K, 3*H*D], ``ALog`` [H], ``DtBias`` [H*D]. With
    ``State``/``Conv`` (N = 1; a prefill window of the decode engine) the
    slot's ``Row`` of both is read unless ``Start`` is 0, tokens at or
    past ``Length`` change nothing, and the row is rewritten in place;
    without them every row of the batch starts from zeros (the export).
    Inference only (no grad op)."""
    import jax

    heads, head_dim = _kda_attrs(op_)
    qkv, f, bt = (ctx.in1(op_, n) for n in ("QKV", "F", "B"))
    params = (ctx.in1(op_, "ConvW"), ctx.in1(op_, "ALog"),
              ctx.in1(op_, "DtBias"), heads, head_dim)
    with jax.named_scope("kda_window"):
        if not op_.inputs.get("State"):
            out = jax.vmap(lambda a, b_, c: kda_window(a, b_, c, *params)[0])(
                qkv, f, bt)
            ctx.out(op_, "Out", out)
            return
        if qkv.shape[0] != 1:
            raise ValueError("kda_window with a state takes one window, "
                             "got a batch of %d" % qkv.shape[0])
        o, state, conv = kda_window(
            qkv[0], f[0], bt[0], *params, state=ctx.in1(op_, "State"),
            conv=ctx.in1(op_, "Conv"), row=_scalar(ctx.in1(op_, "Row")),
            start=_scalar(ctx.in1(op_, "Start")),
            length=_scalar(ctx.in1(op_, "Length")))
    ctx.out(op_, "Out", o[None])
    ctx.out(op_, "StateOut", state)
    ctx.out(op_, "ConvOut", conv)


@op("kda_step", infer_shape=_kda_infer)
def _kda_step(ctx, op_):
    """The KDA mixing of ONE token a slot (the T = 1 step): ``QKV``
    [slots, 1, 3*H*D], ``F`` [slots, 1, H*D], ``B`` [slots, 1, H]; slot i
    reads and rewrites row ``Rows[i]`` of ``State`` (kernel
    ``kernels/kda.py::kda_decode``, in place) and of ``Conv``. An idle
    slot feeds row 0, the sink. Inference only (no grad op)."""
    import jax
    import jax.numpy as jnp

    from ...kernels.kda import kda_decode

    heads, head_dim = _kda_attrs(op_)
    qkv, conv = ctx.in1(op_, "QKV"), ctx.in1(op_, "Conv")
    rows = ctx.in1(op_, "Row").reshape(-1).astype(jnp.int32)
    w = ctx.in1(op_, "ConvW").astype(jnp.float32)
    with jax.named_scope("kda_step"):
        window = jnp.concatenate([conv[rows], qkv.astype(conv.dtype)], axis=1)
        y = (w[None] * window.astype(jnp.float32)).sum(1)
        q, k, v, g, b = kda_inputs(
            y, ctx.in1(op_, "F")[:, 0].astype(jnp.float32),
            ctx.in1(op_, "B")[:, 0].astype(jnp.float32),
            ctx.in1(op_, "ALog"), ctx.in1(op_, "DtBias"), heads, head_dim)
        o, state = kda_decode(
            ctx.in1(op_, "State"), rows, q, k, v, jnp.exp(g), b,
            interpret=bool(op_.attr("interpret", False)) or None)
    ctx.out(op_, "Out", o.reshape(qkv.shape[0], 1, -1).astype(qkv.dtype))
    ctx.out(op_, "StateOut", state)
    ctx.out(op_, "ConvOut", conv.at[rows].set(window[:, 1:]))


def gqa_window(q, k, v, qpos, kv_heads, head_dim):
    """Grouped-query causal softmax attention of a window of queries, the
    blocking of ``mla_window`` on plain K/V: ``q`` [N, T, heads*D], ``k``,
    ``v`` [N, S, kv_heads*D] (query head h reads key head h // (heads /
    kv_heads)), ``qpos`` [N, T]: query i sees the keys at positions
    <= qpos[i]. No positions are encoded. -> [N, T, heads*D]."""
    import jax
    import jax.numpy as jnp

    n, t, width = q.shape
    s = k.shape[1]
    grp = width // head_dim // kv_heads
    tq, tk = math.gcd(t, _WINDOW_BLOCK), math.gcd(s, _WINDOW_BLOCK)
    f32 = jnp.float32
    scale = head_dim ** -0.5
    q = q.reshape(n, t // tq, tq, kv_heads, grp, head_dim).swapaxes(0, 1)
    qpos = qpos.reshape(n, t // tq, 1, 1, tq, 1).astype(jnp.int32)
    qpos = qpos.swapaxes(0, 1)
    k = k.reshape(n, s, kv_heads, head_dim)
    v = v.reshape(n, s, kv_heads, head_dim)

    def attend(queries):
        q, qpos = queries        # [N, tq, G, grp, D], [N, 1, 1, tq, 1]

        def chunk(i, carry):
            top, total, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(k, i * tk, tk, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, i * tk, tk, axis=1)
            sc = jnp.einsum("ntgrd,nsgd->ngrts", q, kc,
                            preferred_element_type=f32)
            seen = i * tk + jnp.arange(tk)[None, None, None, None, :] <= qpos
            sc = jnp.where(seen, sc * scale, _NEG)
            new_top = jnp.maximum(top, sc.max(-1, keepdims=True))
            p = jnp.exp(sc - new_top)
            keep = jnp.exp(top - new_top)
            pv = jnp.einsum("ngrts,nsgd->ngrtd", p.astype(q.dtype), vc,
                            preferred_element_type=f32)
            return (new_top, keep * total + p.sum(-1, keepdims=True),
                    keep * acc + pv)

        init = (jnp.full((n, kv_heads, grp, tq, 1), _NEG, f32),
                jnp.zeros((n, kv_heads, grp, tq, 1), f32),
                jnp.zeros((n, kv_heads, grp, tq, head_dim), f32))
        _top, total, acc = jax.lax.fori_loop(
            0, qpos.max() // tk + 1, chunk, init)
        return (acc / total).astype(q.dtype)

    o = jax.lax.map(attend, (q, qpos))      # [blocks, N, G, grp, tq, D]
    return o.transpose(1, 0, 4, 2, 3, 5).reshape(n, t, width)


@op("gqa_window_attention", infer_shape=same_shape_infer("Q"))
def _gqa_window_attention(ctx, op_):
    """Grouped-query softmax attention of a window (a prefill window over
    the slot's gathered K/V rows, or a whole prompt without a cache):
    ``Q`` [N, T, heads*D], ``K``/``V`` [N, S, kv_heads*D], ``QPos`` [N, T]
    (or [N, T, 1]): key j visible to query i iff j <= QPos[i]. See
    ``gqa_window``. Inference only (no grad op)."""
    import jax

    with jax.named_scope("gqa_window"):
        out = gqa_window(
            ctx.in1(op_, "Q"), ctx.in1(op_, "K"), ctx.in1(op_, "V"),
            ctx.in1(op_, "QPos"), int(op_.attr("num_kv_heads")),
            int(op_.attr("head_dim")))
    ctx.out(op_, "Out", out)
