"""Plain reference of the ``solar_open2`` decoder as
``upstage/Solar-Open2-250B`` configures it (``config.json``; the Kimi
Linear paper, arXiv:2510.26692, and the ``fla`` modelling code for the
delta-rule layer's equations): float32 ``jax.numpy`` at the highest
matmul precision, no kernel, no cache, no chunking, importing nothing of
``paddle_tpu``.

    h = x + Mix_l(RMSNorm(x));  y = h + MoE(RMSNorm(h));  RMSNorm; head

``Mix_l`` is, for ``l`` in ``gqa_layers``, grouped-query causal softmax
attention WITHOUT positions (``use_rope: false``) whose output passes an
elementwise sigmoid gate from its own projection before ``wo``
(``use_gqa_gate``); for every other layer Kimi Delta Attention, computed
by the TOKEN-BY-TOKEN recurrence (a ``lax.scan`` over time):

    q', k', v' = SiLU(conv4(x Wq)), SiLU(conv4(x Wk)), SiLU(conv4(x Wv))
    q = L2norm(q') d^-1/2,  k = L2norm(k'),  v = v'          per head
    a = exp(-exp(A_log) softplus(x Wf_down Wf_up + dt_bias))  per key channel
    b = 2 sigmoid(x Wb)                                       per head
    S~ = Diag(a) S;  S = S~ + b k (v - S~^T k)^T;  o = S^T q
    out = (RMSNorm_d(o) * sigmoid(x Wg_down Wg_up)) Wo

Every layer routes every token over all ``published.n_routed_experts``
(float32 sigmoid scores, top k of score + bias, gates renormalised and
scaled) and adds the shared expert. No token is dropped.

Departures from the published description, each forced by the cut the
configuration file states: only the experts HELD here are computed
(global numbers ``expert_offset ..``; what the others would add is left
out, as on one chip of the deployment), and the vocabulary is the
configuration's slice. ``L2norm`` adds 1e-6 under the root, as ``fla``'s
does. Readings the catalog's row leaves open are listed under ``assumed``
in the configuration file.

Weights keep the values bfloat16 holds (drawn float32, rounded once) and
are stored bfloat16; dense matrices are upcast where they are used, the
expert stacks stay bfloat16 and a float32 activation goes through them in
three bfloat16 pieces (``references/deepseek.py`` explains). ``A_log``,
``dt_bias`` and the router's bias are float32.

``served_gaps(..., dtype="fp8")`` is the control one precision below the
configuration's bfloat16: every matmul with a weight, the attention's
scores and values, the experts and the head with operands rounded to
e4m3, scaled per tensor; the router and the recurrence stay float32.
"""

import functools
import math
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from . import deepseek as _ds

F32, BF16 = jnp.float32, jnp.bfloat16
_NEG = -1e30
rms_norm, gated_mlp, _mm = _ds.rms_norm, _ds.gated_mlp, _ds._mm


def sizes(cfg):
    """The sizes the equations read, from a configuration dict (the
    benchmark's file, or a test's toy)."""
    kda = cfg["linear_attn_config"]
    return dict(
        h=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kvh=cfg["num_key_value_heads"], d=cfg["head_dim"],
        kh=kda["num_heads"], kd=kda["head_dim"],
        taps=kda["short_conv_kernel_size"],
        held=cfg["n_routed_experts"],
        experts=cfg.get("published", {}).get(
            "n_routed_experts", cfg["n_routed_experts"]),
        offset=cfg.get("expert_offset", 0),
        mi=cfg["moe_intermediate_size"], topk=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        gqa=tuple(i for i in cfg["gqa_layers"]
                  if i < cfg["num_hidden_layers"]))


# -- shapes and seeded weights ----------------------------------------------

def shapes(cfg):
    """{leaf: (shape, kind)}: "w" N(0, 0.02) and "g" 1 + N(0, 0.02), both
    rounded to bfloat16; "f" N(0, 0.02) float32 (the router's correction
    bias); "alog" log U(1, 16) and "dt" softplus^-1 of a log-uniform
    step in [1e-3, 1e-1], float32 (``fla``'s initial values)."""
    z = sizes(cfg)
    h, v = z["h"], cfg["vocab_size"]
    out = {"embed": ((v, h), "w"), "norm": ((h,), "g"),
           "head": ((h, v), "w")}
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d/" % i
        out.update({p + "ln1": ((h,), "g"), p + "ln2": ((h,), "g")})
        if i in z["gqa"]:
            q, kv = z["heads"] * z["d"], z["kvh"] * z["d"]
            out.update({
                p + "attn/wq": ((h, q), "w"), p + "attn/wk": ((h, kv), "w"),
                p + "attn/wv": ((h, kv), "w"),
                p + "attn/wgate": ((h, q), "w"),
                p + "attn/wo": ((q, h), "w")})
        else:
            hd, d = z["kh"] * z["kd"], z["kd"]
            for n in "qkv":
                out[p + "kda/w" + n] = ((h, hd), "w")
                out[p + "kda/conv_" + n] = ((z["taps"], hd), "w")
            out.update({
                p + "kda/f_down": ((h, d), "w"),
                p + "kda/f_up": ((d, hd), "w"),
                p + "kda/wb": ((h, z["kh"]), "w"),
                p + "kda/a_log": ((z["kh"],), "alog"),
                p + "kda/dt_bias": ((hd,), "dt"),
                p + "kda/g_down": ((h, d), "w"),
                p + "kda/g_up": ((d, hd), "w"),
                p + "kda/o_norm": ((d,), "g"),
                p + "kda/wo": ((hd, h), "w")})
        out.update({
            p + "moe/wg": ((h, z["experts"]), "w"),
            p + "moe/bias": ((z["experts"],), "f"),
            p + "moe/w1": ((z["held"], h, z["mi"]), "w"),
            p + "moe/w3": ((z["held"], h, z["mi"]), "w"),
            p + "moe/w2": ((z["held"], z["mi"], h), "w"),
            p + "shared/w1": ((h, z["shared"]), "w"),
            p + "shared/w3": ((h, z["shared"]), "w"),
            p + "shared/w2": ((z["shared"], h), "w")})
    return out


def _draw(key, shape, kind):
    if kind == "alog":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if kind == "dt":
        dt = jnp.exp(jax.random.uniform(
            key, shape, F32, np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
    x = 0.02 * jax.random.normal(key, shape, F32)
    return x if kind == "f" else (1.0 + x if kind == "g" else x).astype(BF16)


_ALIVE = {}


def init_params(seed, cfg):
    """{leaf: array} in one jitted call on the device. Asked again for a
    seed whose arrays are all still alive (a served scope holds them), it
    hands those out: two sets of 7.8 GB do not fit one chip."""
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


# -- the mixing layers, each on ONE row x [S, H] ------------------------------

def _query_block(s):
    for b in (256, 128):
        if s % b == 0:
            return b
    return s


def gqa(x, p, z, mm):
    """Gated grouped-query causal softmax attention, no positions."""
    s = x.shape[0]
    heads, kvh, d = z["heads"], z["kvh"], z["d"]
    grp = heads // kvh
    q = mm(x, p["wq"]).reshape(s, kvh, grp, d).transpose(1, 2, 0, 3)
    k = mm(x, p["wk"]).reshape(s, kvh, d).transpose(1, 0, 2)
    v = mm(x, p["wv"]).reshape(s, kvh, d).transpose(1, 0, 2)
    pos = jnp.arange(s)
    qb = _query_block(s)

    def block(at):
        qi = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=2)
        sc = mm(qi, jnp.swapaxes(k, -1, -2)[:, None]) * d ** -0.5
        seen = pos[None, None, None, :] <= (
            at + jnp.arange(qb))[None, None, :, None]
        w = jax.nn.softmax(jnp.where(seen, sc, _NEG), axis=-1)
        return mm(w, v[:, None])                    # [kvh, grp, qb, d]

    o = jax.lax.map(block, jnp.arange(0, s, qb))    # [nb, kvh, grp, qb, d]
    o = o.transpose(0, 3, 1, 2, 4).reshape(s, heads * d)
    return mm(o * jax.nn.sigmoid(mm(x, p["wgate"])), p["wo"])


def conv4(x, w):
    """Depthwise causal convolution over time, zeros before the row:
    y_t = sum_j w_j x_(t - K + 1 + j)."""
    taps = w.shape[0]
    s = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1,) + x.shape[1:], F32), x])
    return sum(w[j].astype(F32)[None] * padded[j:j + s] for j in range(taps))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, a, b, state):
    """The recurrence, token by token: ``q``, ``k``, ``a`` [S, H, dk],
    ``v`` [S, H, dv], ``b`` [S, H], ``state`` [H, dk, dv].
    -> (o [S, H, dv], the state after the last token)."""
    def step(s, x):
        q, k, v, a, b = x
        s = a[..., None] * s
        u = b[..., None] * (v - jnp.sum(k[..., None] * s, -2))
        s = s + k[..., None] * u[..., None, :]
        return s, jnp.sum(q[..., None] * s, -2)

    state, o = jax.lax.scan(step, state, (q, k, v, a, b))
    return o, state


def kda_inputs(x, p, z, mm):
    """-> q, k, a [S, H, dk], v [S, H, dv], b [S, H] of one row."""
    s = x.shape[0]
    heads, d = z["kh"], z["kd"]

    def branch(n):
        y = conv4(mm(x, p["w" + n]), p["conv_" + n])
        return jax.nn.silu(y).reshape(s, heads, d)

    f = mm(mm(x, p["f_down"]), p["f_up"]) + p["dt_bias"]
    g = -jnp.exp(p["a_log"])[None, :, None] * jax.nn.softplus(
        f.reshape(s, heads, d))
    return (l2norm(branch("q")) * d ** -0.5, l2norm(branch("k")),
            branch("v"), jnp.exp(g), 2.0 * jax.nn.sigmoid(mm(x, p["wb"])))


def kda(x, p, z, mm, eps):
    s = x.shape[0]
    heads, d = z["kh"], z["kd"]
    o, _state = delta_rule(*kda_inputs(x, p, z, mm),
                           jnp.zeros((heads, d, d), F32))
    gate = jax.nn.sigmoid(mm(mm(x, p["g_down"]), p["g_up"]))
    o = rms_norm(o, p["o_norm"], eps).reshape(s, heads * d)
    return mm(o * gate, p["wo"])


# -- the expert layer ---------------------------------------------------------

def route(x, p, z, scaling):
    """-> (experts [T, k] global numbers, gates [T, k]); float32 at the
    highest precision in the control too."""
    s = jax.nn.sigmoid(common.mm_highest(x, p["wg"].astype(F32)))
    _, experts = jax.lax.top_k(s + p["bias"][None, :], z["topk"])
    chosen = jnp.take_along_axis(s, experts, axis=1)
    return experts, scaling * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)


def experts_held(x, experts, gates, p, z, kind="highest"):
    """The part of sum_e gate_e E_e(x) that the experts held here give:
    assignments grouped by expert (``argsort`` + ``jax.lax.ragged_dot``),
    one to an expert held elsewhere sorted past every group with weight
    0."""
    t, k = experts.shape
    n = p["w1"].shape[0]
    gmm = _ds._grouped_mm(kind)
    local = experts - z["offset"]
    held = (local >= 0) & (local < n)
    flat = jnp.where(held, local, n).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes_ = jnp.bincount(flat, length=n + 1)[:n].astype(jnp.int32)
    xs = x[order // k]
    h = jax.nn.silu(gmm(xs, p["w1"], sizes_)) * gmm(xs, p["w3"], sizes_)
    weight = jnp.where(held, gates, 0.0).reshape(-1)[order][:, None]
    y = jnp.where(weight != 0.0, gmm(h, p["w2"], sizes_) * weight, 0.0)
    return y[jnp.argsort(order)].reshape(t, k, -1).sum(1)


def experts_naive(x, experts, gates, p, z):
    """The same sum, every held expert on every token and a mask (tests
    tie the grouping and the share to this)."""
    n = p["w1"].shape[0]
    weight = (jax.nn.one_hot(experts - z["offset"], n, dtype=F32)
              * gates[..., None]).sum(1)                         # [T, E]
    w1, w3, w2 = (p[k].astype(F32) for k in ("w1", "w3", "w2"))
    mmh = functools.partial(jnp.einsum, precision=common.HIGHEST)
    h = (jax.nn.silu(mmh("th,ehi->eti", x, w1)) * mmh("th,ehi->eti", x, w3))
    return mmh("eti,eih,te->th", h, w2, weight)


def moe(x, p, z, scaling, mm, kind, token_block=512):
    """Routed (held share) + shared experts on rows ``x`` [N, S, H],
    ``token_block`` tokens at a time (the sorted copies of 8 rows x 8
    assignments would not fit beside the weights)."""
    n, s, h = x.shape
    tb = math.gcd(n * s, token_block)

    def block(xb):
        experts, gates = route(xb, p["moe"], z, scaling)
        return experts_held(xb, experts, gates, p["moe"], z, kind)

    routed = jax.lax.map(block, x.reshape(n * s // tb, tb, h))
    return routed.reshape(n, s, h) + gated_mlp(x, p["shared"], mm)


# -- the model ----------------------------------------------------------------

def _freeze(cfg):
    return tuple(sorted((k, v if not isinstance(v, list) else tuple(v))
                        for k, v in sizes(cfg).items()))


def _rows_at_once(n, is_gqa):
    """Rows a mixing layer takes together: the recurrence's scan is one
    small step a token, so two rows share its steps where they fit."""
    return 2 if not is_gqa and n % 2 == 0 else 1


@functools.partial(jax.jit, static_argnames=("z", "is_gqa", "kind", "eps",
                                             "scaling"))
def _layer(x, p, z, is_gqa, kind, eps, scaling):
    """One block on rows ``x`` [N, S, H]; the mixing layer a row at a
    time."""
    z = dict(z)
    mm = _mm(kind)
    if is_gqa:
        mix = lambda r: gqa(rms_norm(r, p["ln1"], eps), p["attn"], z, mm)  # noqa: E731
    else:
        mix = lambda r: kda(rms_norm(r, p["ln1"], eps), p["kda"], z, mm,  # noqa: E731
                            eps)
    x = x + jax.lax.map(mix, x, batch_size=_rows_at_once(x.shape[0], is_gqa))
    return x + moe(rms_norm(x, p["ln2"], eps), p, z, scaling, mm, kind)


def hidden(cfg, params, ids, kind="highest"):
    """[N, S] ids -> final-normed hidden [N, S, H] float32: one full
    causal forward, a layer a jitted call."""
    p = common.nest(params)
    z = _freeze(cfg)
    gqa_layers = dict(z)["gqa"]
    eps = cfg["rms_norm_eps"]
    x = _ds._embed(p["embed"], ids)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, p["l%d" % i], z=z, is_gqa=i in gqa_layers, kind=kind,
                   eps=eps, scaling=float(cfg["routed_scaling_factor"]))
    return _ds._final_norm(x, p["norm"], eps)


def logits(cfg, params, ids, kind="highest"):
    """[N, S, vocab] next-token logits (tests; small shapes only)."""
    return _mm(kind)(hidden(cfg, params, jnp.asarray(ids, jnp.int32), kind),
                     params["head"])


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
    return _ds._gaps(params["head"], h_best, h_low, ids, low)
