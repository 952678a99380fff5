"""BERT (Devlin et al. 2018) pre-training as published: word + position +
segment embeddings under a layer norm, post-norm encoder blocks with erf-GELU,
a padding mask on the keys, the masked-LM head (dense, GELU, layer norm,
decoder tied to the word embedding plus a bias) and the next-sentence head
over the tanh-pooled first token. Loss: masked-LM cross-entropy averaged over
the masked positions plus next-sentence cross-entropy averaged over the
batch. float32 ``jax.numpy``; imports nothing of the program under test.

Initialisation: normal(0, 0.02) for every matrix and embedding (the release
truncates it at two standard deviations; assumed not to matter here), biases
0, norms 1/0.
"""
import jax.numpy as jnp
import numpy as np

from . import _common as c

IGNORE = -100


def param_specs(cfg):
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg["initializer_range"]

    def dense(name, n_in, n_out):
        return [(name + ".w", (n_in, n_out), ("normal", std)),
                (name + ".b", (n_out,), ("zeros",))]

    def norm(name):
        return [(name + ".g", (d,), ("ones",)), (name + ".b", (d,), ("zeros",))]

    specs = [("embeddings.word", (cfg["vocab_size"], d), ("normal", std)),
             ("embeddings.position", (cfg["max_position_embeddings"], d),
              ("normal", std)),
             ("embeddings.token_type", (cfg["type_vocab_size"], d),
              ("normal", std))] + norm("embeddings.ln")
    for i in range(cfg["num_hidden_layers"]):
        l = f"layer.{i}."
        for part in ("q", "k", "v", "o"):
            specs += dense(l + "attn." + part, d, d)
        specs += norm(l + "attn_ln") + dense(l + "ffn.in", d, ff) + \
            dense(l + "ffn.out", ff, d) + norm(l + "ffn_ln")
    return specs + dense("pooler", d, d) + dense("mlm.transform", d, d) + \
        norm("mlm.ln") + [("mlm.bias", (cfg["vocab_size"],), ("zeros",))] + \
        dense("nsp", d, 2)


def heads(cfg, p, ids, token_type, attn_mask, mm):
    """(masked-LM logits at every position, next-sentence logits)."""
    n_heads, eps = cfg["num_attention_heads"], cfg["layer_norm_eps"]
    length = ids.shape[1]

    def dense(name, x):
        return mm(x, p[name + ".w"]) + p[name + ".b"]

    def norm(name, x):
        return c.layer_norm(x, p[name + ".g"], p[name + ".b"], eps)

    x = p["embeddings.word"][ids] + \
        p["embeddings.position"][jnp.arange(length)] + \
        p["embeddings.token_type"][token_type]
    x = norm("embeddings.ln", x)
    keys = (attn_mask != 0)[:, None, None, :]
    for i in range(cfg["num_hidden_layers"]):
        l = f"layer.{i}."
        q, k, v = (c.split_heads(dense(l + "attn." + t, x), n_heads)
                   for t in "qkv")
        a = c.merge_heads(c.attention(q, k, v, keys, mm))
        x = norm(l + "attn_ln", x + dense(l + "attn.o", a))
        f = dense(l + "ffn.out", c.gelu_erf(dense(l + "ffn.in", x)))
        x = norm(l + "ffn_ln", x + f)
    pooled = jnp.tanh(dense("pooler", x[:, 0]))
    t = norm("mlm.ln", c.gelu_erf(dense("mlm.transform", x)))
    return mm(t, p["embeddings.word"].T) + p["mlm.bias"], dense("nsp", pooled)


def denominators(batch):
    ids, _, _, mlm_labels, _ = batch
    return {"mlm": float(max(np.sum(np.asarray(mlm_labels) != IGNORE), 1)),
            "nsp": float(ids.shape[0])}


def loss_part(cfg):
    def part(p, rows, denoms, mm):
        ids, token_type, attn_mask, mlm_labels, nsp_labels = rows
        mlm, nsp = heads(cfg, p, ids, token_type, attn_mask, mm)
        return c.ce_sum(mlm, mlm_labels, IGNORE) / denoms["mlm"] + \
            c.ce_sum(nsp, nsp_labels) / denoms["nsp"]
    return part
