"""BERT configurations onto the repo's entry points
(``models/bert.py::build_bert_classifier``)."""

import numpy as np

from benchmark.families import common

TOY = dict(vocab_size=1024, hidden_size=64, num_hidden_layers=2,
           num_attention_heads=4, intermediate_size=128,
           max_position_embeddings=64)


def toy(config):
    return dict(config, **TOY)


def leaf_to_var(config):
    out = {"word": "word_embedding", "pos": "pos_embedding",
           "sent": "sent_embedding", "emb_ln/g": "emb_ln.w_0",
           "emb_ln/b": "emb_ln.b_0", "pooler/w": "pooler.w_0",
           "pooler/b": "pooler.b_0", "cls/w": "cls.w_0", "cls/b": "cls.b_0"}
    for i in range(config["num_hidden_layers"]):
        for leaf, var in common.block_vars("layer_%d" % i).items():
            out["l%d/%s" % (i, leaf)] = var
    return out


def model_config(config, rehearse):
    from paddle_tpu.models import bert

    cfg = bert.BertConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        hidden_dropout=config["dropout"],
        attention_dropout=config["dropout"],
        use_flash_attention=config["use_flash_attention"])
    cfg.flash_interpret = rehearse
    return cfg


def build_train(config, traffic, place, rehearse):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    cfg = model_config(config, rehearse)
    with fluid.unique_name.guard():
        main, startup, _feeds, loss, _acc = bert.build_bert_classifier(
            cfg, traffic["seq_len"], num_classes=config["num_classes"],
            learning_rate=config["train"]["learning_rate"], use_amp=True)
    return common.TrainStep(main, startup, loss, place, leaf_to_var(config),
                            mesh=config.get("mesh"))


def feed(batch):
    """The generator's fields as the program's feed."""
    n, s = batch["src_ids"].shape
    return {
        "src_ids": batch["src_ids"].reshape(n, s, 1).astype("int64"),
        "sent_ids": batch["sent_ids"].reshape(n, s, 1).astype("int64"),
        "pos_ids": np.tile(np.arange(s)[None, :, None], (n, 1, 1))
        .astype("int64"),
        "input_mask": np.ones((n, s, 1), "float32"),
        "label": batch["label"].reshape(n, 1).astype("int64"),
    }
