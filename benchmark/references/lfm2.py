"""Plain reference of the ``lfm2_moe`` decoder as ``LiquidAI/LFM2-8B-A1B``
configures it (``config.json``; the family's public modelling code,
``transformers`` ``lfm2_moe``, is the published description): float32
``jax.numpy`` at the highest matmul precision, no kernel, no ragged
product, importing nothing of ``paddle_tpu``. Forward pass, loss and
gradients, for training.

    h = h + Op_l(RMSNorm(h));  h = h + FFN_l(RMSNorm(h));  RMSNorm; E^T

``Op_l`` of a ``conv`` layer: ``B, C, x = split3(u W_in)``, ``z_t =
sum_j w_j * (B * x)_(t-2+j)`` (depthwise, causal, zeros before the
sequence), ``(C * z) W_out``. Of a ``full_attention`` layer: ``q =
RMSNorm(u Wq)``, ``k = RMSNorm(u Wk)`` per head with learned gains, rotary
positions on the whole head in the half-split form, causal softmax of
``q k^T / sqrt(d)``, each key head shared by heads / kv_heads query heads,
``concat(heads) Wo``. ``FFN_l`` of the first ``num_dense_layers`` layers:
``(silu(v W1) * (v W3)) W2``; of the others: ``s = sigmoid(v Wg)``, the k
experts the top k of ``s + b``, gates the chosen ``s`` over their sum +
1e-6, times ``routed_scaling_factor``, ``sum_e gate_e E_e(v)``: a loop over
the held experts, each over every token with the gate zero where the token
did not choose it. No token is dropped. The loss is the mean next-token
cross entropy.

Departures from the published description, each forced by the cut the
configuration file states or listed under its ``assumed``: only the
experts HELD here are computed (global numbers ``expert_offset ..``; what
the others would add is left out, as on one chip of the deployment, and
the partial sum goes on to the next layer); the vocabulary is the
configuration's slice; the embedding is tied to the head; the expert bias
``b`` is seeded (N(0, 0.02)) so that the choice depends on it, takes no
gradient and is updated by nobody; weights are seeded float32, not the
published checkpoint.

Under a gradient rows go one at a time (``lax.map``), and a row, each of
its blocks, each block of queries and each held expert's pass are
recomputed in the backward pass (``jax.checkpoint``: the same arithmetic
again), so that the float32 activations of a 4096-token row (~1.5 GB) fit
beside the 8.1 GB that ``common.train_reference`` keeps (weights, their
start, Adam's moments) and a gradient. ``precision="fp8"``
is the control one precision below the configuration's bfloat16: every
matmul with a weight, the attention's scores and values and the experts
with operands rounded to e4m3 and cotangents to e5m2, scaled per tensor;
the router, the norms, the rotation and the convolution stay float32.
"""

import functools
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import common

F32 = jnp.float32
_NEG = -1e30


def sizes(cfg):
    """The sizes the equations read, from a configuration dict (the
    benchmark's file, or a test's toy)."""
    heads = cfg["num_attention_heads"]
    layers = cfg["num_hidden_layers"]
    # a cut in depth keeps ``layers_kept`` of the published layers
    kept = cfg.get("layers_kept", range(layers))
    return dict(
        h=cfg["hidden_size"], heads=heads, kvh=cfg["num_key_value_heads"],
        d=cfg["hidden_size"] // heads, taps=cfg["conv_L_cache"],
        ffn=cfg["intermediate_size"], mi=cfg["moe_intermediate_size"],
        held=cfg["num_experts"],
        experts=cfg.get("published", {}).get("num_experts",
                                             cfg["num_experts"]),
        offset=cfg.get("expert_offset", 0), topk=cfg["num_experts_per_tok"],
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]), eps=cfg["norm_eps"],
        theta=float(cfg["rope_theta"]), layers=layers,
        dense=sum(i < cfg["num_dense_layers"] for i in kept),
        kinds=tuple(cfg["layer_types"][i] for i in kept))


def shapes(cfg):
    """{leaf: (shape, kind)}: "w" N(0, 0.02), "g" 1 + N(0, 0.02), float32.
    The router's bias ``moe/bias`` is a "w" too: a buffer, in no
    gradient."""
    z = sizes(cfg)
    h, d = z["h"], z["d"]
    out = {"embed": ((cfg["vocab_size"], h), "w"), "norm": ((h,), "g")}
    for i, kind in enumerate(z["kinds"]):
        p = "l%d/" % i
        out.update({p + "op_norm": ((h,), "g"), p + "ffn_norm": ((h,), "g")})
        if kind == "full_attention":
            q, kv = z["heads"] * d, z["kvh"] * d
            out.update({
                p + "attn/wq": ((h, q), "w"), p + "attn/wk": ((h, kv), "w"),
                p + "attn/wv": ((h, kv), "w"), p + "attn/wo": ((q, h), "w"),
                p + "attn/q_norm": ((d,), "g"),
                p + "attn/k_norm": ((d,), "g")})
        else:
            out.update({
                p + "conv/w_in": ((h, 3 * h), "w"),
                p + "conv/taps": ((z["taps"], h), "w"),
                p + "conv/w_out": ((h, h), "w")})
        if i < z["dense"]:
            out.update({
                p + "mlp/w1": ((h, z["ffn"]), "w"),
                p + "mlp/w3": ((h, z["ffn"]), "w"),
                p + "mlp/w2": ((z["ffn"], h), "w")})
        else:
            out.update({
                p + "moe/wg": ((h, z["experts"]), "w"),
                p + "moe/bias": ((z["experts"],), "w"),
                p + "moe/w1": ((z["held"], h, z["mi"]), "w"),
                p + "moe/w3": ((z["held"], h, z["mi"]), "w"),
                p + "moe/w2": ((z["held"], z["mi"], h), "w")})
    return out


def is_buffer(leaf):
    """The leaves no optimizer touches: the router's bias."""
    return leaf.endswith("moe/bias")


def init_params(seed, cfg):
    return common.init_from_shapes(seed, shapes(cfg))


# -- the layers, each on ONE row x [S, H] -------------------------------------

def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """Rotate the last axis of ``x`` [..., S, d] at positions 0 .. S-1,
    the half-split form: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)."""
    s, d = x.shape[-2:]
    inv = theta ** (-jnp.arange(d // 2, dtype=F32) / (d // 2))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _query_block(s):
    for b in (256, 128):
        if s % b == 0:
            return b
    return s


def attention(x, p, z, mm):
    """Grouped-query causal softmax attention with QK-norm and rotary
    positions, blocks of queries one after another."""
    s = x.shape[0]
    heads, kvh, d = z["heads"], z["kvh"], z["d"]
    grp = heads // kvh
    q = rms_norm(mm(x, p["wq"]).reshape(s, heads, d), p["q_norm"], z["eps"])
    k = rms_norm(mm(x, p["wk"]).reshape(s, kvh, d), p["k_norm"], z["eps"])
    q = rope(q.transpose(1, 0, 2), z["theta"])          # [heads, S, d]
    k = rope(k.transpose(1, 0, 2), z["theta"])          # [kvh, S, d]
    v = mm(x, p["wv"]).reshape(s, kvh, d).transpose(1, 0, 2)
    # query head h reads key head h // grp
    kt = jnp.swapaxes(jnp.repeat(k, grp, axis=0), -1, -2)
    v = jnp.repeat(v, grp, axis=0)
    pos = jnp.arange(s)
    qb = _query_block(s)

    @jax.checkpoint
    def block(at):
        qi = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=1)
        sc = mm(qi, kt) * d ** -0.5
        seen = pos[None, None, :] <= (at + jnp.arange(qb))[None, :, None]
        w = jax.nn.softmax(jnp.where(seen, sc, _NEG), axis=-1)
        return mm(w, v)                             # [heads, qb, d]

    o = jax.lax.map(block, jnp.arange(0, s, qb))    # [nb, heads, qb, d]
    o = o.transpose(0, 2, 1, 3).reshape(s, heads * d)
    return mm(o, p["wo"])


def gated_conv(x, p, z, mm):
    """(C * conv(B * x)) W_out; the convolution is float32 elementwise in
    the control too."""
    s, h = x.shape
    b, c, xx = jnp.split(mm(x, p["w_in"]), 3, axis=-1)
    bx = jnp.concatenate([jnp.zeros((z["taps"] - 1, h), F32), b * xx])
    conv = sum(p["taps"][j][None] * bx[j:j + s] for j in range(z["taps"]))
    return mm(c * conv, p["w_out"])


def gated_mlp(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["w1"])) * mm(x, p["w3"]), p["w2"])


def route(x, p, z):
    """-> (experts [T, k] global numbers, gates [T, k]); float32 at the
    highest precision in the control too. The bias chooses, takes no
    gradient and does not weigh."""
    s = jax.nn.sigmoid(common.mm_highest(x, p["wg"]))
    bias = jax.lax.stop_gradient(p["bias"])
    _, experts = jax.lax.top_k(s + bias[None, :], z["topk"])
    chosen = jnp.take_along_axis(s, experts, axis=1)
    if z["norm_topk"]:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    return experts, z["scaling"] * chosen


def experts_held(x, p, z, mm):
    """What the held experts give: one after another, each over EVERY
    token, weighed by the gate the token gave it (zero where it chose
    another)."""
    experts, gates = route(x, p, z)

    @jax.checkpoint
    def one(acc, e):
        gate = jnp.where(experts == e + z["offset"], gates, 0.0).sum(-1)
        w = {n: p[n][e] for n in ("w1", "w3", "w2")}
        return acc + gate[:, None] * gated_mlp(x, w, mm), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(z["held"]))
    return out


def held_counts(x, p, z):
    """int32 [held]: the assignments each held expert received."""
    local = route(x, p, z)[0] - z["offset"]
    return jnp.stack([(local == e).sum() for e in range(z["held"])])


def ffn_input(x, p, z, kind, mm):
    """-> (h = x + Op(RMSNorm(x)), RMSNorm(h)) of one row [S, H]."""
    u = rms_norm(x, p["op_norm"], z["eps"])
    if kind == "full_attention":
        x = x + attention(u, p["attn"], z, mm)
    else:
        x = x + gated_conv(u, p["conv"], z, mm)
    return x, rms_norm(x, p["ffn_norm"], z["eps"])


@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4, 5))
def block(x, p, z, kind, dense, mm):
    """One block on one row [S, H]; recomputed under a gradient."""
    z = dict(z)
    x, v = ffn_input(x, p, z, kind, mm)
    if dense:
        return x + gated_mlp(v, p["mlp"], mm)
    return x + experts_held(v, p["moe"], z, mm)


def _frozen(z):
    return tuple(sorted(z.items()))


def hidden(flat, ids, z, mm=common.mm_highest):
    """[S] ids of one row -> the final normed hidden rows [S, H]."""
    p = common.nest(flat)
    x = p["embed"][ids]
    for i, kind in enumerate(z["kinds"]):
        x = block(x, p["l%d" % i], _frozen(z), kind, i < z["dense"], mm)
    return rms_norm(x, p["norm"], z["eps"])


@functools.lru_cache(maxsize=None)
def _loss_fn(frozen, precision):
    z = dict(frozen)
    mm = common.MM[precision]

    @jax.checkpoint
    def row_loss(flat, ids):
        x = hidden(flat, ids, z, mm)
        logits = mm(x[:-1], flat["embed"].T)
        return jnp.mean(common.softmax_xent(logits, ids[1:]))

    def loss(flat, batch):
        return jnp.mean(jax.lax.map(
            functools.partial(row_loss, flat), batch["ids"]))

    return loss


def loss_fn(cfg, precision="highest"):
    """loss(flat params, {"ids": [rows, S]}): mean next-token cross
    entropy, positions t predicting token t + 1."""
    return _loss_fn(_frozen(sizes(cfg)), precision)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _first_counts(flat, ids, frozen):
    z, p = dict(frozen), common.nest(flat)

    def row(ids):
        x, counts = p["embed"][ids], []
        for i, kind in enumerate(z["kinds"]):
            layer = p["l%d" % i]
            if i >= z["dense"]:
                v = ffn_input(x, layer, z, kind, common.mm_highest)[1]
                counts.append(held_counts(v, layer["moe"], z))
            x = block(x, layer, frozen, kind, i < z["dense"],
                      common.mm_highest)
        return jnp.stack(counts)

    return jax.lax.map(row, ids).sum(0)


def first_counts(cfg, params, batch):
    """int32 [expert layers, held]: the assignments each held expert
    receives in the forward pass of ``batch`` at ``params``."""
    return _first_counts(params, jnp.asarray(batch["ids"], jnp.int32),
                         _frozen(sizes(cfg)))


# {key of a batch: the counts the PROGRAM fetched with that batch's step},
# told by the side that drives the program (``families/lfm2.py``)
PROGRAM_COUNTS = {}


def batch_key(ids):
    ids = np.ascontiguousarray(np.asarray(ids).reshape(-1), np.int64)
    return hashlib.sha1(ids.tobytes()).hexdigest()


def note_choice(cfg, params, batch):
    """An earlier line of the run: at the first step, the assignments each
    held expert received in the float32 reference beside the program's
    (bfloat16 rows into a float32 router). A top-k choice can flip where
    two scores nearly tie; half the summed difference of the counts is
    the least number of held assignments that differ."""
    program = PROGRAM_COUNTS.pop(batch_key(batch["ids"]), None)
    if program is None:
        return
    mine = np.asarray(first_counts(cfg, params, batch))
    program = np.asarray(program)
    differ = 0.5 * np.abs(mine - program).sum()
    print(json.dumps({
        "note": "moe_choice_step1", "reference_counts": mine.tolist(),
        "program_counts": program.tolist(),
        "held_assignments": int(mine.sum()),
        "assignments_differ_at_least": differ,
        "share_differ_at_least": differ / max(int(mine.sum()), 1)}),
        flush=True)


def train(cfg, params, batches, lr, precision="highest", rows_per_block=8):
    """Adam steps over ``batches`` -> (losses, gnorm, dnorm) of the
    TRAINED leaves; the router's bias is in the parameters, gets a zero
    gradient (Adam then leaves it as it is) and is in neither norm. A
    batch's rows go through one call of the gradient, one at a time inside
    it (a second gradient-sized accumulator does not fit)."""
    if precision == "highest":
        note_choice(cfg, params, batches[0])
    losses, gnorm, dnorm = common.train_reference(
        loss_fn(cfg, precision), params,
        [{"ids": jnp.asarray(b["ids"], jnp.int32)} for b in batches],
        lr, rows_per_block)
    trained = [k for k in gnorm if not is_buffer(k)]
    return (losses, {k: gnorm[k] for k in trained},
            {k: dnorm[k] for k in trained})
