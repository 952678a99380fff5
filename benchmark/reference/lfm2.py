"""LFM2-MoE (config ``model_type: lfm2_moe``, Hugging Face's ``Lfm2Moe*``) as
its config's keys describe it, in float32 ``jax.numpy``; imports nothing of
the program under test. Every projection and every attention product goes
through the ``mm`` it is handed. Family ``lfm2``.

What is the same as in the other families' references is imported: the RMS
norm, the rotate-half rotation and ``E(h) = (silu(h W_1) * (h W_3)) W_2`` from
``reference/xing4.py`` (``rms``, ``rotate``, ``swiglu``), and causal softmax
attention a group of heads at a time from ``reference/joyai.py``
(``causal_attention``). What differs is here (``u`` the RMS-normed block
input, C = ``hidden_size``):

- **Block**: ``h = x + Op(RMS_op(x))``, ``y = h + FFN(RMS_ffn(h))``; no bias
  anywhere (``conv_bias`` false).
- **``layer_types[l] == "conv"``**: ``[B, C, X] = split3(u W_in)`` (C -> 3 C,
  in this order); ``z = B * X``; ``c_t = sum_{j < K} w_j z_{t - (K - 1) + j}``,
  K = ``conv_L_cache``, depthwise and causal (zeros before the row's first
  token, the last tap on the token itself), **no activation**; ``Op = (C * c)
  W_out``. The convolution runs across the documents packed into a row.
- **``"full_attention"``**: ``q = u W_q`` in ``num_attention_heads`` heads of
  ``hidden_size / num_attention_heads``, ``k, v`` in ``num_key_value_heads``
  (query head j reads key/value head ``j // group``); ``q <- RMS(q) g_q``,
  ``k <- RMS(k) g_k`` over a head's channels at ``norm_eps``, one weight of a
  head's width for all query heads and one for all key heads; rotary over
  the whole head at ``rope_theta``, rotate-half; causal ``softmax(q k^T
  d^-1/2) v``; ``Op = att W_o``.
- **FFN**: layers before ``num_dense_layers`` a SwiGLU at
  ``intermediate_size``. The others ``sum_{e chosen and held} g_e E_e(u)`` at
  ``moe_intermediate_size``: ``s = sigmoid(u W_r)`` over all
  ``num_experts_published`` experts; the top ``num_experts_per_tok`` of ``s +
  b`` (``use_expert_bias``; ``b`` is a buffer of zeros that no step updates
  and no leaf here); ``g = s[choice] / (sum + 1e-20)`` (``norm_topk_prob``)
  times ``routed_scaling_factor``; the experts held here are
  ``first_routed_expert .. + num_experts``, a ``lax.scan`` over their stacked
  leaves as ``reference/joyai.py``'s, and what the others would add is left
  out, as on one chip of an expert-parallel group before its exchange.
- **Head**: ``logits = RMS_final(h) E^T``, ``E`` the embedding
  (``tie_word_embeddings``): no leaf ``head``, and the gradient of ``embed``
  is the sum of its two uses.

Departures from the source, each the configuration file's (``changed``,
``assumed``): the layers kept, the experts held, the vocabulary's slice; the
normaliser's 1e-20 where Hugging Face adds 1e-6; the expert bias zero.
"""
import math

import numpy as np

import jax
import jax.numpy as jnp

from . import _common as c
from .joyai import causal_attention
from .xing4 import _under, rms, rotate, swiglu

CONV = "conv"


# ---- parameters -------------------------------------------------------------
def is_conv(cfg, i):
    return cfg["layer_types"][i] == CONV


def is_dense(cfg, i):
    return i < cfg["num_dense_layers"]


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _experts_of(cfg):
    """(held, published, first held)."""
    held = cfg["num_experts"]
    return held, cfg.get("num_experts_published", held), \
        cfg.get("first_routed_expert", 0)


def _layer_specs(cfg, i):
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    out = std / math.sqrt(2 * cfg["num_hidden_layers"])   # as GPT-2's
    normal, ones = ("normal", std), ("ones",)
    specs = [("op_norm", (d,), ones)]
    if is_conv(cfg, i):
        specs += [("conv.in_proj", (d, 3 * d), normal),
                  ("conv.taps", (cfg["conv_L_cache"], d),
                   ("normal", cfg["conv_initializer_range"])),
                  ("conv.out_proj", (d, d), ("normal", out))]
    else:
        hq, hkv, dh = cfg["num_attention_heads"], \
            cfg["num_key_value_heads"], head_dim(cfg)
        specs += [("attn.q", (d, hq * dh), normal),
                  ("attn.k", (d, hkv * dh), normal),
                  ("attn.v", (d, hkv * dh), normal),
                  ("attn.q_norm", (dh,), ones), ("attn.k_norm", (dh,), ones),
                  ("attn.o", (hq * dh, d), ("normal", out))]
    specs.append(("ffn_norm", (d,), ones))
    if is_dense(cfg, i):
        w = cfg["intermediate_size"]
        specs += [("mlp.gate", (d, w), normal), ("mlp.up", (d, w), normal),
                  ("mlp.down", (w, d), ("normal", out))]
    else:
        w = cfg["moe_intermediate_size"]
        held, published, _ = _experts_of(cfg)
        specs += [("mlp.router", (d, published), normal),
                  ("mlp.experts.gate", (held, d, w), normal),
                  ("mlp.experts.up", (held, d, w), normal),
                  ("mlp.experts.down", (held, w, d), ("normal", out))]
    return [(f"layers.{i}.{k}", shape, init) for k, shape, init in specs]


def param_specs(cfg):
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    if not cfg["tie_word_embeddings"] or cfg["conv_bias"]:
        raise ValueError("this family's head is its embedding and its "
                         "convolution has no bias")
    specs = [("embed", (cfg["vocab_size"], d), ("normal", std))]
    for i in range(cfg["num_hidden_layers"]):
        specs += _layer_specs(cfg, i)
    return specs + [("norm", (d,), ("ones",))]


# ---- the two operators --------------------------------------------------------
def gated_conv(b, cc, x, taps):
    """``C * conv(B * X)`` over (B, L, C) with taps (K, C): depthwise,
    causal, the last tap on the token itself; no bias, no activation."""
    k, length = taps.shape[0], x.shape[1]
    z = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))
    return cc * sum(z[:, j:j + length] * taps[j] for j in range(k))


def conv_operator(cfg, p, x, mm):
    d = cfg["hidden_size"]
    bcx = mm(x, p["conv.in_proj"])
    y = gated_conv(bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:],
                   p["conv.taps"])
    return mm(y, p["conv.out_proj"])


def rope_tables(cfg, length):
    """(cos, sin), each (length, head_dim), rotate-half layout."""
    dim = head_dim(cfg)
    freq = float(cfg["rope_theta"]) ** (
        -np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = np.arange(length, dtype=np.float64)[:, None] * freq[None, :]
    angle = np.concatenate([angle, angle], axis=1)
    return jnp.asarray(np.cos(angle), jnp.float32), \
        jnp.asarray(np.sin(angle), jnp.float32)


def attention_operator(cfg, p, x, mm):
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    b, l, _ = x.shape
    eps = cfg["norm_eps"]
    cos, sin = rope_tables(cfg, l)

    def heads(t, n):
        return t.reshape(b, l, n, dh).transpose(0, 2, 1, 3)

    q = rotate(rms(heads(mm(x, p["attn.q"]), hq), eps, p["attn.q_norm"]),
               cos, sin)
    k = rotate(rms(heads(mm(x, p["attn.k"]), hkv), eps, p["attn.k_norm"]),
               cos, sin)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(heads(mm(x, p["attn.v"]), hkv), hq // hkv, axis=1)
    o = causal_attention(q, k, v, dh ** -0.5, mm)
    return mm(o.transpose(0, 2, 1, 3).reshape(b, l, hq * dh), p["attn.o"])


# ---- experts ----------------------------------------------------------------
def gate_weights(cfg, scores):
    """(..., E) weight of every expert for every token, zero where it was
    not chosen: the top-k scores (the expert bias is zero) over their sum,
    scaled."""
    _, choice = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, choice, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(choice, scores.shape[-1], dtype=w.dtype) *
                   w[..., None], axis=-2)


def routed_part(cfg, p, x, mm):
    """What the experts held here add: ``sum_j g[:, first + j] E_j(x)``."""
    held, _, first = _experts_of(cfg)
    w = gate_weights(cfg, jax.nn.sigmoid(mm(x, p["mlp.router"])))
    w = jnp.moveaxis(w[..., first:first + held], -1, 0)     # (held, ..., T)

    @jax.checkpoint
    def one(y, expert):
        gate, up, down, weight = expert
        return y + weight[..., None] * swiglu(x, gate, up, down, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["mlp.experts.gate"], p["mlp.experts.up"], p["mlp.experts.down"], w))
    return y


# ---- the model --------------------------------------------------------------
def block(cfg, mm, i):
    eps = cfg["norm_eps"]
    operator = conv_operator if is_conv(cfg, i) else attention_operator

    def run(p, x):
        x = x + operator(cfg, p, rms(x, eps, p["op_norm"]), mm)
        h = rms(x, eps, p["ffn_norm"])
        if is_dense(cfg, i):
            return x + swiglu(h, p["mlp.gate"], p["mlp.up"], p["mlp.down"], mm)
        return x + routed_part(cfg, p, h, mm)
    return run


def hidden(cfg, p, ids, mm):
    x = p["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(block(cfg, mm, i))(_under(p, f"layers.{i}."), x)
    return x


def logits_of(cfg, p, h, mm):
    return mm(rms(h, cfg["norm_eps"], p["norm"]), p["embed"].T)


def denominators(batch):
    ids, _ = batch
    return {"lm": float(ids.shape[0] * ids.shape[1])}


def loss_part(cfg):
    def part(p, rows, denoms, mm):
        ids, labels = rows
        logits = logits_of(cfg, p, hidden(cfg, p, ids, mm), mm)
        return c.ce_sum(logits, labels) / denoms["lm"]
    return part
