"""Plain float32 ``jax.numpy`` pieces the family references share.

Nothing here imports ``paddle_tpu``. Every matmul goes through one ``mm``
callable so that a control can put a lower precision in its place; the
default is float32 at the highest matmul precision (on a TPU a float32
matmul otherwise runs as one bf16 pass).
"""

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
ADAM = dict(beta1=0.9, beta2=0.999, eps=1e-8)


def mm_highest(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _fp8(x, dtype=jnp.float8_e4m3fn, top=448.0):
    """Per-tensor scaled fp8 round trip (amax -> the format's largest)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _mm_fp8(a, b):
    return jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)


def _mm_fp8_fwd(a, b):
    qa, qb = _fp8(a), _fp8(b)
    return jnp.matmul(qa, qb, precision=HIGHEST), (qa, qb)


def _mm_fp8_bwd(res, g):
    qa, qb = res
    g = _fp8(g, jnp.float8_e5m2, 57344.0)
    ga = jnp.matmul(g, jnp.swapaxes(qb, -1, -2), precision=HIGHEST)
    gb = jnp.matmul(jnp.swapaxes(qa, -1, -2), g, precision=HIGHEST)
    # reduce broadcast batch dims of b (weights are 2-D)
    while gb.ndim > qb.ndim:
        gb = gb.sum(0)
    return ga, gb


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm_fp8(a, b):
    """The control below bf16, as fp8 training is usually done: operands
    rounded to e4m3, cotangents to e5m2, each scaled per tensor; float32
    accumulation and float32 everything else."""
    return _mm_fp8(a, b)


def mm_bf16(a, b):
    """The control below float32: bf16 operands, bf16 result."""
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


MM = {"highest": mm_highest, "fp8": mm_fp8, "bf16": mm_bf16}


def layer_norm(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def dense(x, p, mm):
    return mm(x, p["w"]) + p["b"].astype(x.dtype)


def attention(x, p, heads, mm, causal):
    """Multi-head self attention on [N, S, H]; every key attendable (the
    cells feed no padding)."""
    n, s, h = x.shape
    d = h // heads

    def split(t):
        return t.reshape(n, s, heads, d).transpose(0, 2, 1, 3)

    q, k, v = (split(dense(x, p[name], mm)) for name in ("q", "k", "v"))
    scores = mm(q, jnp.swapaxes(k, -1, -2)) * (1.0 / math.sqrt(d))
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, jnp.asarray(-1e4, scores.dtype))
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    ctx = mm(w, v).transpose(0, 2, 1, 3).reshape(n, s, h)
    return dense(ctx, p["o"], mm)


def post_ln_block(x, p, heads, mm, causal):
    """The repo's transformer block: residual, then LayerNorm (post-LN).
    Under a gradient the block is recomputed in the backward pass
    (``jax.checkpoint``: the same arithmetic twice), so that a float32
    reference of a deep model fits beside its state."""
    return _block(x, p, heads, mm, causal)


@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4))
def _block(x, p, heads, mm, causal):
    x = layer_norm(x + attention(x, p["attn"], heads, mm, causal),
                   p["ln1"]["g"], p["ln1"]["b"])
    ff = dense(gelu(dense(x, p["fc0"], mm)), p["fc1"], mm)
    return layer_norm(x + ff, p["ln2"]["g"], p["ln2"]["b"])


def softmax_xent(logits, labels):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return lse - picked


# -- seeded weights ---------------------------------------------------------

def _leaf(key, shape, kind):
    """Kind "g" (a LayerNorm gain) is drawn around 1, "w" and "b" around 0."""
    noise = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return 1.0 + noise if kind == "g" else noise


def block_shapes(hidden, ffn):
    out = {}
    for name in ("q", "k", "v", "o"):
        out["attn/%s/w" % name] = ((hidden, hidden), "w")
        out["attn/%s/b" % name] = ((hidden,), "b")
    for ln in ("ln1", "ln2"):
        out["%s/g" % ln] = ((hidden,), "g")
        out["%s/b" % ln] = ((hidden,), "b")
    out["fc0/w"], out["fc0/b"] = ((hidden, ffn), "w"), ((ffn,), "b")
    out["fc1/w"], out["fc1/b"] = ((ffn, hidden), "w"), ((hidden,), "b")
    return out


def seed_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def mesh_of(config):
    """The devices a configuration's deployment spans, as a 1-D mesh
    ("data"), or None on one chip. The reference of a model whose state
    does not fit one chip is laid over the same chips: the same plain
    code, its arrays split along their first divisible axis."""
    spec = config.get("mesh")
    if not spec:
        return None
    n = 1
    for size in spec["mesh_axes"].values():
        n *= int(size)
    return jax.sharding.Mesh(jax.devices()[:n], ("data",))


def _split(mesh, shape):
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.devices.size
    for axis, size in enumerate(shape):
        if size % n == 0 and size >= n:
            return NamedSharding(mesh, P(*([None] * axis + ["data"])))
    return NamedSharding(mesh, P())


def rows_over(mesh, batch):
    """A block of rows split over the mesh's chips (or left as it is)."""
    if mesh is None:
        return batch
    return {k: jax.device_put(v, _split(mesh, v.shape))
            for k, v in batch.items()}


def init_from_shapes(seed, shapes, mesh=None):
    """{path: array} for {path: (shape, kind)}: one jitted call on the
    device, float32, laid over ``mesh`` where one is given. Matrices and embeddings N(0, 0.02) (GPT-2's and
    BERT's initializer range); biases N(0, 0.02) and LayerNorm gains
    1 + N(0, 0.02) rather than 0 and 1, so that no term of the forward
    pass is multiplied by nothing."""
    names = sorted(shapes)

    def make(key):
        keys = jax.random.split(key, len(names))
        return {n: _leaf(k, *shapes[n]) for n, k in zip(names, keys)}

    out = None
    if mesh is not None:
        out = {n: _split(mesh, shapes[n][0]) for n in names}
    return jax.jit(make, out_shardings=out)(seed_key(seed))


def nest(flat):
    """{"a/b/c": x} -> {"a": {"b": {"c": x}}}"""
    out = {}
    for path, val in flat.items():
        node = out
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = val
    return out


# -- the optimizer the programs are built with ------------------------------

def adam_step(params, grads, m, v, t, lr):
    """Step ``t`` (from 1) of Adam as ``fluid.optimizer.Adam`` defines it:
    the bias correction folded into the rate, epsilon added outside the
    root. -> (params, m, v)"""
    b1, b2, eps = ADAM["beta1"], ADAM["beta2"], ADAM["eps"]
    lr_t = lr * math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    new_p, new_m, new_v = {}, {}, {}
    for k in params:
        new_m[k] = b1 * m[k] + (1.0 - b1) * grads[k]
        new_v[k] = b2 * v[k] + (1.0 - b2) * jnp.square(grads[k])
        new_p[k] = params[k] - lr_t * new_m[k] / (jnp.sqrt(new_v[k]) + eps)
    return new_p, new_m, new_v


_GRAD_FNS = {}
_adam_jit = jax.jit(adam_step, static_argnames=("t", "lr"),
                    donate_argnums=(0, 2, 3))


@jax.jit
def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def train_reference(loss_fn, params, batches, lr, rows_per_block,
                    mesh=None):
    """Three (len(batches)) Adam steps of ``loss_fn(params, batch_rows)``
    (a mean over rows), gradients accumulated over blocks of rows so the
    float32 activations fit beside the state.

    -> (losses, {leaf: norm of the first gradient},
        {leaf: norm of the parameters' change after the last step})"""
    if loss_fn not in _GRAD_FNS:  # one trace per loss function a process
        _GRAD_FNS[loss_fn] = jax.jit(jax.value_and_grad(loss_fn))
    grad_fn, norm, step = _GRAD_FNS[loss_fn], _leaf_norms, _adam_jit
    start = {k: v + 0 for k, v in params.items()}
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    v = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, gnorm = [], None
    for batch in batches:
        rows = len(next(iter(batch.values())))
        blocks = [slice(i, i + rows_per_block)
                  for i in range(0, rows, rows_per_block)]
        total, acc = 0.0, None
        for blk in blocks:
            part = rows_over(mesh, {k: v[blk] for k, v in batch.items()})
            lv, g = grad_fn(params, part)
            w = len(next(iter(part.values()))) / rows
            total += float(lv) * w
            g = {k: v * w for k, v in g.items()}
            acc = g if acc is None else {k: acc[k] + g[k] for k in g}
        losses.append(total)
        if gnorm is None:
            gnorm = {k: float(v) for k, v in norm(acc).items()}
        params, m, v = step(params, acc, m, v, t=len(losses), lr=lr)
    delta = norm({k: params[k] - start[k] for k in params})
    return losses, gnorm, {k: float(v) for k, v in delta.items()}
