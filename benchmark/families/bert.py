"""bert: ``paddle_tpu.models.nlp.bert.BertForPretraining`` under
``bert_pretrain_loss`` (masked-LM + next-sentence)."""
import numpy as np

from benchmark.families import _recipe
from benchmark.reference import bert as reference


def valid_tokens(pool):
    """Non-padding positions of each row: the attention mask's sum."""
    return np.asarray(pool[2]).sum(axis=1).astype(np.int64)


def name_map(cfg):
    """program's structured parameter name -> reference name."""
    def dense(prog, ref):
        return {prog + ".weight": ref + ".w", prog + ".bias": ref + ".b"}

    def norm(prog, ref):
        return {prog + ".weight": ref + ".g", prog + ".bias": ref + ".b"}

    e = "bert.embeddings."
    out = {e + "word.weight": "embeddings.word",
           e + "position.weight": "embeddings.position",
           e + "token_type.weight": "embeddings.token_type",
           "mlm_bias": "mlm.bias",
           **norm(e + "norm", "embeddings.ln"),
           **dense("bert.pooler", "pooler"),
           **dense("transform", "mlm.transform"),
           **norm("transform_norm", "mlm.ln"), **dense("nsp", "nsp")}
    for i in range(cfg["num_hidden_layers"]):
        b, l = f"bert.encoder.layers.{i}.", f"layer.{i}."
        for prog, ref in (("q_proj", "q"), ("k_proj", "k"), ("v_proj", "v"),
                          ("out_proj", "o")):
            out.update(dense(b + "self_attn." + prog, l + "attn." + ref))
        out.update({**norm(b + "norm1", l + "attn_ln"),
                    **dense(b + "linear1", l + "ffn.in"),
                    **dense(b + "linear2", l + "ffn.out"),
                    **norm(b + "norm2", l + "ffn_ln")})
    return out


def build(cfg, weights, mesh_axes):
    """(model, step) with the seeded weights in place; the configuration's
    ``program`` group goes to the program's own config as it stands."""
    from paddle_tpu.models.nlp.bert import (BertConfig, BertForPretraining,
                                            bert_pretrain_loss)

    if cfg["hidden_act"] != "gelu" or cfg["layer_norm_eps"] != 1e-5 or \
            cfg["hidden_dropout_prob"] != cfg["attention_probs_dropout_prob"]:
        raise ValueError("the program's BERT is erf-GELU, eps 1e-5, one "
                         "dropout rate")
    model = BertForPretraining(BertConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        intermediate=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab=cfg["type_vocab_size"],
        dropout=cfg["hidden_dropout_prob"],
        initializer_range=cfg["initializer_range"],
        **cfg.get("program", {})))
    model.bfloat16()
    _recipe.load_weights(model, weights, name_map(cfg))
    return model, _recipe.train_step(model, bert_pretrain_loss, cfg["recipe"],
                                     mesh_axes)


def flops_per_position(cfg, length):
    return _recipe.palm_flops_per_position(
        _recipe.n_params(reference.param_specs(cfg)),
        cfg["num_hidden_layers"], cfg["hidden_size"], length)


step_flops = _recipe.token_rows_step_flops(flops_per_position)
