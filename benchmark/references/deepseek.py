"""Plain reference of the ``deepseek_v3`` decoder as
``kakaocorp/kanana-2-30b-a3b-instruct-2601`` configures it (``config.json``;
``modeling_deepseek_v3.py`` of ``transformers`` for the equations): float32
``jax.numpy`` at the highest matmul precision, no kernel, no cache, no
absorbed attention, importing nothing of ``paddle_tpu``.

    h = x + MLA(RMSNorm(x));  y = h + FFN_l(RMSNorm(h));  RMSNorm; head

MLA with ``q_lora_rank: null``, always UP-PROJECTED: every head's key and
value come from the normed latent through ``wkvb``, one rotated rope key
is shared by all heads, rotary dims are regrouped from pairs to halves
(``rope_interleave``). FFN_0 is dense; later layers route every token
over all experts (float32 sigmoid scores, top k of score + bias, gates
renormalised and scaled) and add the shared experts as one MLP. No token
is dropped.

Weights keep the values bfloat16 holds (drawn float32, rounded once) and
are stored bfloat16: 3.79 B parameters in float32 do not fit one chip
beside activations. Dense matrices are upcast where they are used. The
expert stacks stay bfloat16: a float32 activation is split into three
bfloat16 pieces (x = hi + mid + lo, exact to 2^-24) and each piece goes
through the grouped product with float32 accumulation, which is the
float32 product of x with the (bfloat16-valued) weights whatever
precision the grouped product's own kernel takes.

``served_gaps(..., dtype="fp8")`` is the control one precision below the
configuration's bfloat16: every matmul (projections, scores, values,
experts, head) with operands rounded to e4m3, scaled per tensor; the
router stays float32, as the published gate is.
"""

import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from . import common

F32, BF16 = jnp.float32, jnp.bfloat16
_NEG = -1e30


# -- shapes and seeded weights ----------------------------------------------

def shapes(cfg):
    """{leaf: (shape, kind)}: "w" N(0, 0.02) and "g" 1 + N(0, 0.02), both
    rounded to bfloat16; "f" N(0, 0.02) kept float32 (the router's
    ``e_score_correction_bias``)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    lat, e, mi = (cfg["kv_lora_rank"], cfg["n_routed_experts"],
                  cfg["moe_intermediate_size"])
    out = {"embed": ((cfg["vocab_size"], h), "w"), "norm": ((h,), "g"),
           "head": ((h, cfg["vocab_size"]), "w")}
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d/" % i
        out.update({
            p + "ln1": ((h,), "g"), p + "ln2": ((h,), "g"),
            p + "attn/wq": ((h, heads * (nope + rope)), "w"),
            p + "attn/wkva": ((h, lat + rope), "w"),
            p + "attn/kv_norm": ((lat,), "g"),
            p + "attn/wkvb": ((lat, heads * (nope + vd)), "w"),
            p + "attn/wo": ((heads * vd, h), "w")})
        if i < cfg["first_k_dense_replace"]:
            widths = {"ffn": cfg["intermediate_size"]}
        else:
            widths = {"shared": cfg["n_shared_experts"] * mi}
            out.update({
                p + "moe/wg": ((h, e), "w"), p + "moe/bias": ((e,), "f"),
                p + "moe/w1": ((e, h, mi), "w"),
                p + "moe/w3": ((e, h, mi), "w"),
                p + "moe/w2": ((e, mi, h), "w")})
        for name, width in widths.items():
            out.update({p + name + "/w1": ((h, width), "w"),
                        p + name + "/w3": ((h, width), "w"),
                        p + name + "/w2": ((width, h), "w")})
    return out


_ALIVE = {}


def init_params(seed, cfg):
    """{leaf: array} in one jitted call on the device. Asked again for a
    seed whose arrays are all still alive (a served scope holds them),
    it hands those out: two sets of 7.6 GB do not fit one chip."""
    spec = shapes(cfg)
    names = sorted(spec)
    key = (int(seed), tuple((n, spec[n]) for n in names))
    held = {n: ref() for n, ref in _ALIVE.get(key, {}).items()}
    if held and all(v is not None for v in held.values()):
        return held
    _ALIVE.clear()

    def make(key):
        out = {}
        for n, k in zip(names, jax.random.split(key, len(names))):
            shape, kind = spec[n]
            x = 0.02 * jax.random.normal(k, shape, F32)
            out[n] = x if kind == "f" else (
                (1.0 + x if kind == "g" else x).astype(BF16))
        return out

    out = jax.jit(make)(common.seed_key(seed))
    _ALIVE[key] = {n: weakref.ref(v) for n, v in out.items()}
    return out


# -- pieces -------------------------------------------------------------------

def _mm(kind):
    """x @ w for a float32 x and a weight leaf, in the control's precision
    or float32 at the highest."""
    mm = common.MM[kind]
    return lambda x, w: mm(x, w.astype(F32))


def rms_norm(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(F32))


def rope(x, pos, theta, interleave):
    """Rotate the last axis of ``x`` [..., S, d] at positions ``pos`` [S]:
    pairs (x0, y0, x1, y1, ...) regrouped to halves first where the
    configuration says ``rope_interleave``, then rotated by halves."""
    d = x.shape[-1]
    if interleave:
        x = x.reshape(x.shape[:-1] + (d // 2, 2))
        x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (d,))
    inv = theta ** (-jnp.arange(d // 2, dtype=F32) / (d // 2))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _query_block(s):
    for b in (256, 128):
        if s % b == 0:
            return b
    return s


def mla(x, p, cfg, mm):
    """Up-projected latent attention on ONE row ``x`` [S, H], causal."""
    s = x.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rope_d, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    lat = cfg["kv_lora_rank"]
    pos = jnp.arange(s)
    rot = functools.partial(rope, pos=pos, theta=cfg["rope_theta"],
                            interleave=cfg["rope_interleave"])
    q = mm(x, p["wq"]).reshape(s, heads, nope + rope_d).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], rot(q[..., nope:])], -1)
    kva = mm(x, p["wkva"])
    c = rms_norm(kva[:, :lat], p["kv_norm"], cfg["rms_norm_eps"])
    k_rope = rot(kva[:, lat:])
    kv = mm(c, p["wkvb"]).reshape(s, heads, nope + vd).transpose(1, 0, 2)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[None], (heads, s, rope_d))],
        -1)
    v = kv[..., nope:]
    qb = _query_block(s)

    def block(at):
        qi = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=1)
        sc = mm(qi, jnp.swapaxes(k, -1, -2)) * (nope + rope_d) ** -0.5
        seen = pos[None, None, :] <= (at + jnp.arange(qb))[None, :, None]
        w = jax.nn.softmax(jnp.where(seen, sc, _NEG), axis=-1)
        return mm(w, v)                                    # [heads, qb, vd]

    o = jax.lax.map(block, jnp.arange(0, s, qb))           # [nb, heads, qb, vd]
    o = o.transpose(0, 2, 1, 3).reshape(s, heads * vd)
    return mm(o, p["wo"])


def gated_mlp(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["w1"])) * mm(x, p["w3"]), p["w2"])


def route(x, p, cfg):
    """-> (experts [T, k], gates [T, k]) for tokens ``x`` [T, H]; float32
    at the highest precision in the control too. ``n_group`` =
    ``topk_group`` = 1: the group limit is vacuous and not built."""
    s = jax.nn.sigmoid(common.mm_highest(x, p["wg"].astype(F32)))
    _, experts = jax.lax.top_k(s + p["bias"][None, :],
                               cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, experts, axis=1)
    gates = (cfg["routed_scaling_factor"] * chosen
             / (chosen.sum(-1, keepdims=True) + 1e-20))
    return experts, gates


def _pieces(x):
    """x = hi + mid + lo in bfloat16 pieces (exact to 2^-24 of x)."""
    hi = x.astype(BF16)
    r = x - hi.astype(F32)
    mid = r.astype(BF16)
    return hi, mid, (r - mid.astype(F32)).astype(BF16)


def _grouped_mm(kind):
    """xs [M, K] float32 (rows sorted by group) times a bfloat16 stack
    [G, K, N] by ``sizes``."""
    def rdot(a, w, sizes):
        return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=F32)

    def highest(xs, w, sizes):
        return sum(rdot(piece, w, sizes) for piece in _pieces(xs))

    def fp8(xs, w, sizes, top=448.0):
        # e4m3 values are exact in bfloat16; the scales multiply back after
        sx = jnp.maximum(jnp.max(jnp.abs(xs)), 1e-30) / top
        wf = w.astype(F32)
        sw = jnp.maximum(jnp.max(jnp.abs(wf)), 1e-30) / top
        q = lambda t, s: (t / s).astype(jnp.float8_e4m3fn).astype(BF16)  # noqa: E731
        return rdot(q(xs, sx), q(wf, sw), sizes) * (sx * sw)

    return {"highest": highest, "fp8": fp8}[kind]


def experts_grouped(x, experts, gates, p, kind="highest"):
    """sum over a token's chosen experts of gate * E_e(x), assignments
    grouped by expert (``argsort`` + ``jax.lax.ragged_dot``)."""
    t, k = experts.shape
    n = p["w1"].shape[0]
    gmm = _grouped_mm(kind)
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=n).astype(jnp.int32)
    xs = x[order // k]
    h = jax.nn.silu(gmm(xs, p["w1"], sizes)) * gmm(xs, p["w3"], sizes)
    y = gmm(h, p["w2"], sizes) * gates.reshape(-1)[order][:, None]
    return y[jnp.argsort(order)].reshape(t, k, -1).sum(1)


def experts_naive(x, experts, gates, p):
    """The same sum, every expert on every token and a mask (tests tie
    the grouping to this)."""
    n = p["w1"].shape[0]
    weight = (jax.nn.one_hot(experts, n, dtype=F32)
              * gates[..., None]).sum(1)                         # [T, E]
    w1, w3, w2 = (p[k].astype(F32) for k in ("w1", "w3", "w2"))
    mmh = functools.partial(jnp.einsum, precision=common.HIGHEST)
    h = (jax.nn.silu(mmh("th,ehi->eti", x, w1)) * mmh("th,ehi->eti", x, w3))
    return mmh("eti,eih,te->th", h, w2, weight)


def moe(x, p, cfg, mm, kind, token_block=8704):
    """Routed + shared experts on tokens ``x`` [T, H], ``token_block``
    tokens at a time (the sorted copies of 35 k tokens x 6 assignments
    would not fit beside the weights)."""
    t = x.shape[0]
    tb = token_block if t % token_block == 0 else t

    def block(xb):
        experts, gates = route(xb, p["moe"], cfg)
        return experts_grouped(xb, experts, gates, p["moe"], kind)

    routed = jax.lax.map(block, x.reshape(t // tb, tb, -1)).reshape(t, -1)
    return routed + gated_mlp(x, p["shared"], mm)


@functools.partial(jax.jit, static_argnames=("cfg", "dense", "kind"))
def _layer(x, p, cfg, dense, kind):
    """One block on rows ``x`` [N, S, H]."""
    cfg = dict(cfg)
    mm = _mm(kind)
    eps = cfg["rms_norm_eps"]
    n, s, h = x.shape
    x = x + jax.lax.map(
        lambda row: mla(rms_norm(row, p["ln1"], eps), p["attn"], cfg, mm), x)
    y = rms_norm(x, p["ln2"], eps).reshape(n * s, h)
    if dense:
        ff = gated_mlp(y, p["ffn"], mm)
    else:
        ff = moe(y, p, cfg, mm, kind)
    return x + ff.reshape(n, s, h)


@jax.jit
def _embed(embed, ids):
    return embed[ids].astype(F32)


def hidden(cfg, params, ids, kind="highest"):
    """[N, S] ids -> final-normed hidden [N, S, H] float32: one full
    causal forward, a layer a jitted call (so that only that layer's
    weights are upcast at a time)."""
    p = common.nest(params)
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, bool))))
    x = _embed(p["embed"], ids)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, p["l%d" % i], cfg=key,
                   dense=i < cfg["first_k_dense_replace"], kind=kind)
    return _final_norm(x, p["norm"], cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, eps):
    return rms_norm(x, w, eps)


def logits(cfg, params, ids, kind="highest"):
    """[N, S, vocab] next-token logits (tests; small shapes only)."""
    return _mm(kind)(hidden(cfg, params, jnp.asarray(ids, jnp.int32), kind),
                     params["head"])


def _position_block(n, s):
    """Positions a head block takes: its [N, block, vocab] float32 logits
    stay near a gigabyte at the real vocabulary."""
    for b in (256, 128):
        if s % b == 0 and n * b <= 2048:
            return b
    return s


@functools.partial(jax.jit, static_argnames=("low",))
def _gaps(head, h_best, h_low, ids, low):
    """Per position, the best reference logit less the reference logit of
    the picked token: the next id of the row, or (``low``) the token the
    control's own logits put first."""
    n, s, hd = h_best.shape
    pb = _position_block(n, s)
    nxt = jnp.roll(ids, -1, axis=1)

    def block(at):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, at, pb, axis=1)  # noqa: E731
        best = _mm("highest")(cut(h_best), head)
        if low:
            picked = jnp.argmax(_mm(low)(cut(h_low), head), -1)
        else:
            picked = cut(nxt)
        got = jnp.take_along_axis(best, picked[..., None], -1)[..., 0]
        return jnp.max(best, -1) - got                      # [N, pb]

    out = jax.lax.map(block, jnp.arange(0, s, pb))          # [nb, N, pb]
    return out.transpose(1, 0, 2).reshape(n, s)


def served_gaps(cfg, params, ids, dtype="highest"):
    """[N, S] gaps over padded rows of prompt + served tokens: at each
    position, how far the reference's logit of the NEXT token of the row
    lies below its best logit there (``dtype="fp8"``, the control: of the
    token an fp8 forward pass puts first there). One full forward pass,
    no cache; the head and the gap in blocks of positions."""
    ids = jnp.asarray(np.asarray(ids), jnp.int32)
    h_best = hidden(cfg, params, ids)
    low = None if dtype == "highest" else dtype
    h_low = hidden(cfg, params, ids, low) if low else h_best
    return _gaps(params["head"], h_best, h_low, ids, low)
