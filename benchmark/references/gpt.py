"""Plain reference of the repo's GPT (``models/gpt.py``): float32
``jax.numpy``, no kernel, no cache, no batching.

It follows GPT-2 (Radford et al. 2019; ``openai-community/gpt2``) except
where the repo's model does, and says where: the block is post-LN
(residual, then LayerNorm; GPT-2 normalises first and has a final
LayerNorm, the repo's model has none), the LM head is a separate matrix
with a bias (GPT-2 ties it to the token embedding), and GELU is the exact
erf form (GPT-2 uses the tanh approximation).
"""

import functools

import jax
import jax.numpy as jnp

from . import common


def shapes(cfg):
    h, ffn = cfg["n_embd"], cfg["n_inner"]
    out = {"wte": ((cfg["vocab_size"], h), "w"),
           "wpe": ((cfg["n_positions"], h), "w"),
           "head/w": ((h, cfg["vocab_size"]), "w"),
           "head/b": ((cfg["vocab_size"],), "b")}
    for i in range(cfg["n_layer"]):
        for path, spec in common.block_shapes(h, ffn).items():
            out["h%d/%s" % (i, path)] = spec
    return out


def init_params(seed, cfg):
    return common.init_from_shapes(seed, shapes(cfg), common.mesh_of(cfg))


def logits(flat, ids, heads, layers, mm=common.mm_highest):
    """[N, S] int ids -> [N, S, vocab] next-token logits."""
    p = common.nest(flat)
    s = ids.shape[1]
    x = p["wte"][ids] + p["wpe"][jnp.arange(s)][None]
    for i in range(layers):
        x = common.post_ln_block(x, p["h%d" % i], heads, mm, causal=True)
    return common.dense(x, p["head"], mm)


@functools.lru_cache(maxsize=None)
def loss_fn(heads, layers, precision="highest"):
    """loss(flat params, {"ids": [rows, S]}) : mean next-token cross
    entropy, positions t predicting token t + 1."""
    mm = common.MM[precision]

    def loss(flat, batch):
        ids = batch["ids"]
        lg = logits(flat, ids, heads, layers, mm)
        return jnp.mean(common.softmax_xent(lg[:, :-1], ids[:, 1:]))

    return loss


def train(cfg, params, batches, lr, precision="highest", rows_per_block=None):
    mesh = common.mesh_of(cfg)
    if rows_per_block is None:   # two rows a chip at a time
        rows_per_block = 2 * (mesh.devices.size if mesh is not None else 1)
    return common.train_reference(
        loss_fn(cfg["n_head"], cfg["n_layer"], precision), params,
        [{"ids": jnp.asarray(b["ids"], jnp.int32)} for b in batches],
        lr, rows_per_block, common.mesh_of(cfg))


@functools.partial(jax.jit, static_argnames=("heads", "layers", "dtype"))
def _served_gaps(flat, ids, heads, layers, dtype):
    best = logits(flat, ids, heads, layers, common.mm_highest)
    if dtype == "bf16":   # the control: which token bf16 puts first
        low = {k: v.astype(jnp.bfloat16) for k, v in flat.items()}
        picked = jnp.argmax(
            logits(low, ids, heads, layers, common.mm_bf16), -1)
    else:                 # the served token: the next id of the row
        picked = jnp.roll(ids, -1, axis=1)
    at = jnp.take_along_axis(best, picked[..., None], -1)[..., 0]
    return jnp.max(best, -1) - at


def served_gaps(cfg, params, ids, dtype="highest"):
    """[N, S] gaps over padded rows of prompt + served tokens: at each
    position, how far the reference's logit of the NEXT token of the row
    lies below its best logit there (``dtype="bf16"``, the control: of
    the token a bf16 forward pass puts first there). One full forward
    pass, no cache."""
    return _served_gaps(params, jnp.asarray(ids, jnp.int32),
                        heads=cfg["n_head"], layers=cfg["n_layer"],
                        dtype=dtype)
