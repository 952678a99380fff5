"""GPT-2 (Radford et al. 2019) as published: learned positions, pre-norm
blocks, tanh-GELU, causal attention, the output head tied to the token
embedding, next-token cross-entropy averaged over all positions. float32
``jax.numpy`` throughout; imports nothing of the program under test.

Initialisation as in the released code: normal(0, 0.02), the two residual
projections of each block scaled by 1/sqrt(2 * n_layer), biases 0, norms 1/0.
"""
import math

import jax
import jax.numpy as jnp

from . import _common as c


def param_specs(cfg):
    d, n = cfg["n_embd"], cfg["n_layer"]
    std = cfg["initializer_range"]
    res = std / math.sqrt(2 * n)
    specs = [("wte", (cfg["vocab_size"], d), ("normal", std)),
             ("wpe", (cfg["n_positions"], d), ("normal", std))]
    for i in range(n):
        h = f"h.{i}."
        specs += [
            (h + "ln_1.g", (d,), ("ones",)), (h + "ln_1.b", (d,), ("zeros",)),
            (h + "attn.c_attn.w", (d, 3 * d), ("normal", std)),
            (h + "attn.c_attn.b", (3 * d,), ("zeros",)),
            (h + "attn.c_proj.w", (d, d), ("normal", res)),
            (h + "attn.c_proj.b", (d,), ("zeros",)),
            (h + "ln_2.g", (d,), ("ones",)), (h + "ln_2.b", (d,), ("zeros",)),
            (h + "mlp.c_fc.w", (d, 4 * d), ("normal", std)),
            (h + "mlp.c_fc.b", (4 * d,), ("zeros",)),
            (h + "mlp.c_proj.w", (4 * d, d), ("normal", res)),
            (h + "mlp.c_proj.b", (d,), ("zeros",)),
        ]
    return specs + [("ln_f.g", (d,), ("ones",)), ("ln_f.b", (d,), ("zeros",))]


def block(cfg, mm):
    """``run(p, x, causal)``: one pre-norm block over its own leaves ``p``."""
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]

    def run(p, x, causal):
        a = c.layer_norm(x, p["ln_1.g"], p["ln_1.b"], eps)
        qkv = mm(a, p["attn.c_attn.w"]) + p["attn.c_attn.b"]
        q, k, v = (c.split_heads(t, heads) for t in jnp.split(qkv, 3, -1))
        a = c.merge_heads(c.attention(q, k, v, causal, mm))
        x = x + mm(a, p["attn.c_proj.w"]) + p["attn.c_proj.b"]
        f = c.layer_norm(x, p["ln_2.g"], p["ln_2.b"], eps)
        f = c.gelu_tanh(mm(f, p["mlp.c_fc.w"]) + p["mlp.c_fc.b"])
        return x + mm(f, p["mlp.c_proj.w"]) + p["mlp.c_proj.b"]
    return run


def logits(cfg, p, ids, mm):
    """Block by block under ``jax.checkpoint``: the backward pass computes a
    block's inside again from its input (the same operations, so the same
    numbers) and holds the activations of one block, not of all."""
    length = ids.shape[1]
    x = p["wte"][ids] + p["wpe"][jnp.arange(length)]
    causal = jnp.tril(jnp.ones((length, length), bool))
    run = jax.checkpoint(block(cfg, mm))
    for i in range(cfg["n_layer"]):
        h = f"h.{i}."
        x = run({k[len(h):]: w for k, w in p.items() if k.startswith(h)},
                x, causal)
    x = c.layer_norm(x, p["ln_f.g"], p["ln_f.b"], cfg["layer_norm_epsilon"])
    return mm(x, p["wte"].T)


def denominators(batch):
    ids, _ = batch
    return {"lm": float(ids.shape[0] * ids.shape[1])}


def loss_part(cfg):
    def part(p, rows, denoms, mm):
        ids, labels = rows
        return c.ce_sum(logits(cfg, p, ids, mm), labels) / denoms["lm"]
    return part
