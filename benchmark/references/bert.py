"""Plain reference of the repo's BERT classifier (``models/bert.py``):
float32 ``jax.numpy``, dense attention, no kernel.

It follows BERT (Devlin et al. 2018; ``google-research/bert``): summed
word, position and segment embeddings under a LayerNorm, post-LN encoder
layers with exact GELU, a tanh pooler over the first token. Departure,
the repo's: a 2-class head on the pooled output where SQuAD fine-tuning
has a span head.
"""

import functools

import jax.numpy as jnp

from . import common


def shapes(cfg):
    h, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    out = {"word": ((cfg["vocab_size"], h), "w"),
           "pos": ((cfg["max_position_embeddings"], h), "w"),
           "sent": ((cfg["type_vocab_size"], h), "w"),
           "emb_ln/g": ((h,), "g"), "emb_ln/b": ((h,), "b"),
           "pooler/w": ((h, h), "w"), "pooler/b": ((h,), "b"),
           "cls/w": ((h, cfg["num_classes"]), "w"),
           "cls/b": ((cfg["num_classes"],), "b")}
    for i in range(cfg["num_hidden_layers"]):
        for path, spec in common.block_shapes(h, ffn).items():
            out["l%d/%s" % (i, path)] = spec
    return out


def init_params(seed, cfg):
    return common.init_from_shapes(seed, shapes(cfg), common.mesh_of(cfg))


def class_logits(flat, src, sent, heads, layers, mm=common.mm_highest):
    p = common.nest(flat)
    s = src.shape[1]
    x = p["word"][src] + p["pos"][jnp.arange(s)][None] + p["sent"][sent]
    x = common.layer_norm(x, p["emb_ln"]["g"], p["emb_ln"]["b"])
    for i in range(layers):
        x = common.post_ln_block(x, p["l%d" % i], heads, mm, causal=False)
    pooled = jnp.tanh(common.dense(x[:, 0], p["pooler"], mm))
    return common.dense(pooled, p["cls"], mm)


@functools.lru_cache(maxsize=None)
def loss_fn(heads, layers, precision="highest"):
    mm = common.MM[precision]

    def loss(flat, batch):
        lg = class_logits(flat, batch["src_ids"], batch["sent_ids"],
                          heads, layers, mm)
        return jnp.mean(common.softmax_xent(lg, batch["label"]))

    return loss


def train(cfg, params, batches, lr, precision="highest", rows_per_block=8):
    keys = ("src_ids", "sent_ids", "label")
    return common.train_reference(
        loss_fn(cfg["num_attention_heads"], cfg["num_hidden_layers"],
                precision), params,
        [{k: jnp.asarray(b[k], jnp.int32) for k in keys} for b in batches],
        lr, rows_per_block, common.mesh_of(cfg))
