"""Plain reference of the language model of
``meituan-longcat/LongCat-Flash-Omni`` (``config.json``; the LongCat-Flash
technical report and the family's published modelling code for the
equations): float32 ``jax.numpy`` at the highest matmul precision, no
kernel, no cache, no absorbed attention, no batching, importing nothing
of ``paddle_tpu``. The audio and vision encoders and the codec decoder of
the "Omni" release are not built: the traffic is token ids.

Hidden 6144, 64 heads, RMSNorm eps 1e-5, no biases. Double layer ``l``,
input ``x``:

    a1 = x  + MLA[l,0](RMS(x))             u1 = RMS(a1)
    s  = MoE[l](u1)                                     # the shortcut branch
    b1 = a1 + FFN[l,0](u1)                              # SwiGLU, width 12288
    a2 = b1 + MLA[l,1](RMS(b1))
    y  = a2 + FFN[l,1](RMS(a2)) + s

``MoE(u)``: ``p = softmax(u Wr)`` in float32 over 768 outputs (512 routed
+ 256 zero); ``S`` = the 12 largest of ``p + bias`` (the bias chooses and
does not weigh); ``MoE(u) = 6 sum_{e in S, e < 512} p_e E_e(u) + 6
(sum_{e in S, e >= 512} p_e) u``, with ``E_e(u) = (silu(u W1_e) * (u
W3_e)) W2_e`` of width 2048. No renormalisation of the 12, no shared
expert. ``MLA(x)``: ``cq = RMS(x Wqa)`` (1536); ``q = 2.0 (cq Wqb)``, 64
heads of 128 + 64; ``[c | kr] = x Wkva`` (512 + 64); ``c = 3.464
RMS(c)``; rotary (interleaved pairs, theta 1e7) on the 64 of every query
head and on the one ``kr``; head ``h`` has key ``[c Wkb_h | kr]`` and
value ``c Wvb_h`` (128 each); causal softmax of ``q.k / sqrt(192)``;
``Wo`` 8192 -> 6144. Final RMSNorm, untied head, float32 logits. The two
scales are ``sqrt(6144 / 1536)`` and ``sqrt(6144 / 512)``
(``mla_scale_q_lora`` / ``mla_scale_kv_lora``).

Departures from the published description, each forced by the cut the
configuration file states: only the experts HELD here are computed
(global numbers ``expert_offset ..``; what the others would add is left
out, as on one chip of the deployment), the identity experts are computed
in full (every chip computes them for the tokens that live on it), and
the vocabulary is the configuration's slice. Readings the catalog's row
leaves open are listed under ``assumed`` in the configuration file.

Weights keep the values bfloat16 holds (drawn float32, rounded once) and
are stored bfloat16; dense matrices are upcast where they are used, a
part of a double layer (an attention, a feed-forward, the branch) a
jitted call, the expert stacks stay bfloat16 and a float32
activation goes through them in three bfloat16 pieces
(``references/deepseek.py`` explains). The router's bias is float32 and
of the scores' size (``shapes``).

``served_gaps(..., dtype="fp8")`` is the control one precision below the
configuration's bfloat16: every matmul with a weight, the attention's
scores and values, the experts and the head with operands rounded to
e4m3, scaled per tensor; the router stays float32 and the identity
experts multiply nothing.
"""

import functools
import math
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from . import deepseek as _ds
from . import solar_open2 as _so2

F32, BF16 = jnp.float32, jnp.bfloat16
_NEG = -1e30
rms_norm, gated_mlp, _mm = _ds.rms_norm, _ds.gated_mlp, _ds._mm


def sizes(cfg):
    """The sizes the equations read, from a configuration dict (the
    benchmark's file, or a test's toy)."""
    h = cfg["hidden_size"]
    return dict(
        h=h, heads=cfg["num_attention_heads"], ffn=cfg["ffn_hidden_size"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vd=cfg["v_head_dim"], lat=cfg["kv_lora_rank"],
        qrank=cfg["q_lora_rank"],
        qscale=math.sqrt(h / cfg["q_lora_rank"])
        if cfg["mla_scale_q_lora"] else 1.0,
        kvscale=math.sqrt(h / cfg["kv_lora_rank"])
        if cfg["mla_scale_kv_lora"] else 1.0,
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        held=cfg["n_routed_experts"],
        experts=cfg.get("published", {}).get(
            "n_routed_experts", cfg["n_routed_experts"]),
        zeros=cfg["zero_expert_num"], offset=cfg.get("expert_offset", 0),
        mi=cfg["expert_ffn_hidden_size"], topk=cfg["moe_topk"],
        scaling=float(cfg["routed_scaling_factor"]))


# -- shapes and seeded weights ----------------------------------------------

def shapes(cfg):
    """{leaf: (shape, kind)}: "w" N(0, 0.02) and "g" 1 + N(0, 0.02), both
    rounded to bfloat16; "b" the router's correction bias, float32,
    N(0, 0.02) in units of the mean score (1 / the router's outputs): a
    softmax score is ~1/768 where a sigmoid score is ~1/2, and a bias of
    0.02 beside it would choose the same 12 outputs for every token."""
    z = sizes(cfg)
    h, v, heads = z["h"], cfg["vocab_size"], z["heads"]
    out = {"embed": ((v, h), "w"), "norm": ((h,), "g"),
           "head": ((h, v), "w")}
    for i in range(cfg["num_layers"]):
        p = "l%d/" % i
        for j in (0, 1):
            a, f = "%satt%d/" % (p, j), "%sffn%d/" % (p, j)
            out.update({
                "%sln_att%d" % (p, j): ((h,), "g"),
                "%sln_ffn%d" % (p, j): ((h,), "g"),
                a + "wqa": ((h, z["qrank"]), "w"),
                a + "q_norm": ((z["qrank"],), "g"),
                a + "wqb": ((z["qrank"], heads * (z["nope"] + z["rope"])),
                            "w"),
                a + "wkva": ((h, z["lat"] + z["rope"]), "w"),
                a + "kv_norm": ((z["lat"],), "g"),
                a + "wkvb": ((z["lat"], heads * (z["nope"] + z["vd"])), "w"),
                a + "wo": ((heads * z["vd"], h), "w"),
                f + "w1": ((h, z["ffn"]), "w"), f + "w3": ((h, z["ffn"]), "w"),
                f + "w2": ((z["ffn"], h), "w")})
        outputs = z["experts"] + z["zeros"]
        out.update({
            p + "moe/wg": ((h, outputs), "w"),
            p + "moe/bias": ((outputs,), "b"),
            p + "moe/w1": ((z["held"], h, z["mi"]), "w"),
            p + "moe/w3": ((z["held"], h, z["mi"]), "w"),
            p + "moe/w2": ((z["held"], z["mi"], h), "w")})
    return out


def _draw(key, shape, kind):
    x = 0.02 * jax.random.normal(key, shape, F32)
    if kind == "b":
        return x / shape[0]
    return (1.0 + x if kind == "g" else x).astype(BF16)


_ALIVE = {}


def init_params(seed, cfg):
    """{leaf: array} in one jitted call on the device. Asked again for a
    seed whose arrays are all still alive (a served scope holds them), it
    hands those out: two sets of 10.3 GB do not fit one chip."""
    spec = shapes(cfg)
    names = sorted(spec)
    key = (int(seed), tuple((n, spec[n]) for n in names))
    held = {n: ref() for n, ref in _ALIVE.get(key, {}).items()}
    if held and all(v is not None for v in held.values()):
        return held
    _ALIVE.clear()

    def make(key):
        return {n: _draw(k, *spec[n])
                for n, k in zip(names, jax.random.split(key, len(names)))}

    out = jax.jit(make)(common.seed_key(seed))
    _ALIVE[key] = {n: weakref.ref(v) for n, v in out.items()}
    return out


# -- latent attention on ONE row x [S, H] -------------------------------------

def mla(x, p, z, mm):
    """Up-projected latent attention with the query LoRA and the two
    LoRA scales, causal."""
    s = x.shape[0]
    heads, nope, rope_d, vd, lat = (z["heads"], z["nope"], z["rope"],
                                    z["vd"], z["lat"])
    pos = jnp.arange(s)
    rot = functools.partial(_ds.rope, pos=pos, theta=z["theta"],
                            interleave=True)
    cq = rms_norm(mm(x, p["wqa"]), p["q_norm"], z["eps"])
    q = z["qscale"] * mm(cq, p["wqb"])
    q = q.reshape(s, heads, nope + rope_d).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], rot(q[..., nope:])], -1)
    kva = mm(x, p["wkva"])
    c = z["kvscale"] * rms_norm(kva[:, :lat], p["kv_norm"], z["eps"])
    k_rope = rot(kva[:, lat:])
    kv = mm(c, p["wkvb"]).reshape(s, heads, nope + vd).transpose(1, 0, 2)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[None], (heads, s, rope_d))],
        -1)
    v = kv[..., nope:]
    qb = _ds._query_block(s)

    def block(at):
        qi = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=1)
        sc = mm(qi, jnp.swapaxes(k, -1, -2)) * (nope + rope_d) ** -0.5
        seen = pos[None, None, :] <= (at + jnp.arange(qb))[None, :, None]
        w = jax.nn.softmax(jnp.where(seen, sc, _NEG), axis=-1)
        return mm(w, v)                                    # [heads, qb, vd]

    o = jax.lax.map(block, jnp.arange(0, s, qb))          # [nb, heads, qb, vd]
    o = o.transpose(0, 2, 1, 3).reshape(s, heads * vd)
    return mm(o, p["wo"])


# -- the expert branch --------------------------------------------------------

def route(x, p, z):
    """-> (experts [T, k] router outputs, gates [T, k]): softmax over all
    ``experts + zeros`` outputs, float32 at the highest precision in the
    control too; the top k of score + bias, the chosen scores times the
    scaling, not renormalised."""
    s = jax.nn.softmax(common.mm_highest(x, p["wg"].astype(F32)), axis=-1)
    _, experts = jax.lax.top_k(s + p["bias"][None, :], z["topk"])
    return experts, z["scaling"] * jnp.take_along_axis(s, experts, axis=1)


def identity_experts(x, experts, gates, z):
    """What the identity experts give: router outputs ``experts`` and
    above are ``E(x) = x``."""
    zero = experts >= z["experts"]
    return jnp.where(zero, gates, 0.0).sum(-1, keepdims=True) * x


# the held share, grouped and naive, is ``references/solar_open2.py``'s:
# an assignment to any number outside ``offset .. offset + held - 1`` (an
# expert held elsewhere, an identity expert) sorts past every group with
# weight 0 there, and matches no column of the naive form's mask
experts_held, experts_naive = _so2.experts_held, _so2.experts_naive


def _token_blocks(fn, x, block):
    """``fn`` over ``x`` [T, ...] in blocks of at most ``block`` tokens
    (a divisor of T), so that a block's temporaries fit."""
    t = x.shape[0]
    tb = math.gcd(t, block)
    return jax.lax.map(fn, x.reshape((t // tb, tb) + x.shape[1:])).reshape(
        (t,) + x.shape[1:])


def moe(x, p, z, kind, token_block=1024):
    """The shortcut branch on tokens ``x`` [T, H]: held share + identity
    experts (the sorted copies of a block's 12 assignments a token are
    the largest temporaries of a layer)."""
    def block(xb):
        experts, gates = route(xb, p, z)
        return (experts_held(xb, experts, gates, p, z, kind)
                + identity_experts(xb, experts, gates, z))

    return _token_blocks(block, x, token_block)


# -- the model ----------------------------------------------------------------

def _freeze(cfg):
    return tuple(sorted(sizes(cfg).items()))


_part = functools.partial(jax.jit, static_argnames=("z", "kind"))


@_part
def _attend(x, ln, p, z, kind):
    """x + MLA(RMS(x)) on rows ``x`` [N, S, H], a row at a time."""
    z = dict(z)
    mm = _mm(kind)
    return x + jax.lax.map(
        lambda row: mla(rms_norm(row, ln, z["eps"]), p, z, mm), x)


@_part
def _branch(x, ln, p, z, kind):
    """MoE(RMS(x)): the shortcut branch, in blocks of tokens."""
    z = dict(z)
    n, s, h = x.shape
    u = rms_norm(x, ln, z["eps"]).reshape(n * s, h)
    return moe(u, p, z, kind).reshape(n, s, h)


@_part
def _feed_forward(x, ln, p, z, kind):
    """x + FFN(RMS(x)), in blocks of tokens."""
    z = dict(z)
    mm = _mm(kind)
    n, s, h = x.shape
    u = rms_norm(x, ln, z["eps"]).reshape(n * s, h)
    return x + _token_blocks(lambda ub: gated_mlp(ub, p, mm), u,
                             2048).reshape(n, s, h)


def _layer(x, p, z, kind):
    """One double layer on rows ``x`` [N, S, H], a part a jitted call (so
    that only that part's weights are upcast at a time)."""
    a1 = _attend(x, p["ln_att0"], p["att0"], z=z, kind=kind)
    shortcut = _branch(a1, p["ln_ffn0"], p["moe"], z=z, kind=kind)
    b1 = _feed_forward(a1, p["ln_ffn0"], p["ffn0"], z=z, kind=kind)
    a2 = _attend(b1, p["ln_att1"], p["att1"], z=z, kind=kind)
    return _feed_forward(a2, p["ln_ffn1"], p["ffn1"], z=z,
                         kind=kind) + shortcut


def hidden(cfg, params, ids, kind="highest"):
    """[N, S] ids -> final-normed hidden [N, S, H] float32: one full
    causal forward, a part of a double layer a jitted call."""
    p = common.nest(params)
    z = _freeze(cfg)
    x = _ds._embed(p["embed"], ids)
    for i in range(cfg["num_layers"]):
        x = _layer(x, p["l%d" % i], z=z, kind=kind)
    return _ds._final_norm(x, p["norm"], cfg["rms_norm_eps"])


def logits(cfg, params, ids, kind="highest"):
    """[N, S, vocab] next-token logits (tests; small shapes only)."""
    return _mm(kind)(hidden(cfg, params, jnp.asarray(ids, jnp.int32), kind),
                     params["head"])


WIDTH_STEP = 1024    # a row is padded to a multiple of this: four widths


def served_gaps(cfg, params, ids, dtype="highest"):
    """[N, S] gaps over padded rows of prompt + served tokens: at each
    position, how far the reference's logit of the NEXT token of the row
    lies below its best logit there (``dtype="fp8"``, the control: of the
    token an fp8 forward pass puts first there). One full forward pass a
    row, no cache, a row at a time at its own length (10.3 GB of weights
    leave room for little more at 6144 x 5120 float32 a tensor, and a
    window's rows are 1280 to 5120 long: every row at the width of the
    longest was 40 % of the 104 s this took, which a run's time limit
    has no room for). A row ends at its last id that is not 0, the
    caller's padding, and is padded again to a multiple of
    ``WIDTH_STEP``: a position sees nothing after it, so its gap is what
    the full width gives, and a row that really ends in id 0 loses a gap
    only where that zero lies past the multiple. The gaps past a row's
    end, and of a row of padding alone, are 0. The head and the gap in
    blocks of positions."""
    ids = np.asarray(ids)
    low = None if dtype == "highest" else dtype
    out = np.zeros(ids.shape, np.float32)
    for r, row in enumerate(ids):
        used = np.flatnonzero(row)
        if not used.size:
            continue
        width = -(-(int(used[-1]) + 1) // WIDTH_STEP) * WIDTH_STEP
        block = np.zeros((1, width), np.int32)
        keep = min(width, row.size)
        block[0, :keep] = row[:keep]
        block = jnp.asarray(block)
        h_best = hidden(cfg, params, block)
        h_low = hidden(cfg, params, block, low) if low else h_best
        out[r, :keep] = np.asarray(_ds._gaps(
            params["head"], h_best, h_low, block, low))[0, :keep]
    return out
