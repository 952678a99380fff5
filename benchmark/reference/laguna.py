"""Laguna (config ``model_type: laguna``) as its config's keys describe it, in
float32 ``jax.numpy``; imports nothing of the program under test. Every
projection and every attention product goes through the ``mm`` it is handed.
Family ``laguna``.

What is the same as in the other families' references is imported: the RMS
norm, the rotate-half rotation and ``E(h) = (silu(h W_gate) * (h W_up))
W_down`` from ``reference/xing4.py`` (``rms``, ``rotate``, ``swiglu``), and
causal softmax attention a group of heads at a time from
``reference/joyai.py`` (``causal_attention``). What differs is here (``x`` the
RMS-normed block input, ``head_dim`` d, layer l with ``H_l =
num_attention_heads_per_layer[l]`` heads):

- **Residual**: plain pre-norm, ``h = h + Attn_l(RMS_w(h))``, ``h = h +
  MLP_l(RMS_w(h))``; ``logits = RMS_w(h) W_head``; no bias anywhere
  (``attention_bias`` false), untied embedding and head.
- **Attention**: ``q = W_q x`` in ``H_l`` heads, ``k, v = W_k x, W_v x`` in
  ``num_key_value_heads``, key/value head j read by the query heads ``j g ..
  j g + g - 1``, ``g = H_l / num_key_value_heads``; rotary on q and k; ``a =
  softmax(q k^T d^-1/2 + M_l) v``; ``gating: per-head``: ``g = sigmoid(W_g
  x)`` in R^{H_l}, one logit a head from the same normed input; output ``W_o
  concat_h(g_h a_h)``.
- **Mask**: ``layer_types[l]`` ``full_attention``: causal. ``sliding_
  attention``: query i sees key j where ``0 <= i - j < sliding_window``.
  Computed a block of ``QUERY_BLOCK`` queries at a time against the
  ``sliding_window - 1`` keys before the block and the block's own, each
  block under a ``checkpoint``, so that an 8,192-token row's scores are 150 MB
  at a time and not 19 GB.
- **Rotary** (``rope_parameters[layer type]``): the first ``head_dim *
  partial_rotary_factor`` dims of a head turn, rotate-half among themselves
  (dim i against dim i + half of them), the rest pass. ``rope_type``
  ``default``: ``rope_theta ** (-2i / dims)``. ``yarn``: Hugging Face's
  ``_compute_yarn_parameters``: the dims that turn more than ``beta_fast``
  times over ``original_max_position_embeddings`` keep their frequency, those
  under ``beta_slow`` turns are divided by ``factor``, a linear ramp between;
  cos and sin times the stated ``attention_factor``.
- **MLP**: ``mlp_layer_types[l]`` ``dense``: SwiGLU at ``intermediate_size``.
  ``sparse``: ``y = E_shared(x) + sum_{e chosen and held} w_e E_e(x)`` at
  ``moe_intermediate_size`` (the shared one at ``shared_expert_
  intermediate_size``); ``s = softmax(x W_r)`` over all
  ``num_experts_published`` experts, ``w = s[choice] / (sum + 1e-20) *
  moe_routed_scaling_factor`` over the top ``num_experts_per_tok``
  (``norm_topk_prob``); the experts held here are ``first_routed_expert .. +
  num_experts``, a ``lax.scan`` over their stacked leaves as
  ``reference/joyai.py``'s, and what the others would add is left out, as on
  one chip of an expert-parallel group before its exchange.
"""
import math

import numpy as np

import jax
import jax.numpy as jnp

from . import _common as c
from .joyai import causal_attention
from .xing4 import _under, rms, rotate, swiglu

QUERY_BLOCK = 512    # queries of a windowed layer computed at a time
SLIDING = "sliding_attention"


# ---- parameters -------------------------------------------------------------
def _experts_of(cfg):
    """(held, published, first held)."""
    held = cfg["num_experts"]
    return held, cfg.get("num_experts_published", held), \
        cfg.get("first_routed_expert", 0)


def _layer_specs(cfg, i):
    d, dh, std = cfg["hidden_size"], cfg["head_dim"], cfg["initializer_range"]
    hq, hkv = cfg["num_attention_heads_per_layer"][i], \
        cfg["num_key_value_heads"]
    out = std / math.sqrt(2 * cfg["num_hidden_layers"])   # as GPT-2's
    normal, ones = ("normal", std), ("ones",)
    specs = [("input_norm", (d,), ones),
             ("attn.q", (d, hq * dh), normal),
             ("attn.k", (d, hkv * dh), normal),
             ("attn.v", (d, hkv * dh), normal),
             ("attn.gate", (d, hq), normal),
             ("attn.o", (hq * dh, d), ("normal", out)),
             ("post_attn_norm", (d,), ones)]
    if cfg["mlp_layer_types"][i] == "dense":
        w = cfg["intermediate_size"]
        specs += [("mlp.gate", (d, w), normal), ("mlp.up", (d, w), normal),
                  ("mlp.down", (w, d), ("normal", out))]
    else:
        w, s = cfg["moe_intermediate_size"], \
            cfg["shared_expert_intermediate_size"]
        held, published, _ = _experts_of(cfg)
        specs += [("mlp.router", (d, published), normal),
                  ("mlp.experts.gate", (held, d, w), normal),
                  ("mlp.experts.up", (held, d, w), normal),
                  ("mlp.experts.down", (held, w, d), ("normal", out)),
                  ("mlp.shared.gate", (d, s), normal),
                  ("mlp.shared.up", (d, s), normal),
                  ("mlp.shared.down", (s, d), ("normal", out))]
    return [(f"layers.{i}.{k}", shape, init) for k, shape, init in specs]


def param_specs(cfg):
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    specs = [("embed", (cfg["vocab_size"], d), ("normal", std))]
    for i in range(cfg["num_hidden_layers"]):
        specs += _layer_specs(cfg, i)
    return specs + [("norm", (d,), ("ones",)),
                    ("head", (d, cfg["vocab_size"]), ("normal", std))]


# ---- rotary -------------------------------------------------------------------
def rope_tables(cfg, kind, length):
    """(cos, sin), each (length, dims rotated), rotate-half layout, for the
    layer type ``kind``."""
    r = cfg["rope_parameters"][kind]
    dim = int(cfg["head_dim"] * r["partial_rotary_factor"])
    base = float(r["rope_theta"])
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = 1.0
    if r["rope_type"] == "yarn":
        def dim_of(turns):   # the dim that turns this often over the old length
            return dim * math.log(r["original_max_position_embeddings"] /
                                  (turns * 2 * math.pi)) / (2 * math.log(base))

        lo = max(math.floor(dim_of(r["beta_fast"])), 0)
        hi = min(math.ceil(dim_of(r["beta_slow"])), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - lo) /
                       (hi - lo if hi > lo else 0.001), 0.0, 1.0)
        freq = freq / r["factor"] * ramp + freq * (1.0 - ramp)
        scale = r["attention_factor"]
    elif r["rope_type"] != "default":
        raise ValueError(f"rope_type {r['rope_type']!r}")
    angle = np.arange(length, dtype=np.float64)[:, None] * freq[None, :]
    angle = np.concatenate([angle, angle], axis=1)
    return jnp.asarray(np.cos(angle) * scale, jnp.float32), \
        jnp.asarray(np.sin(angle) * scale, jnp.float32)


def turn(x, cos, sin):
    """Rotary over the first ``cos.shape[-1]`` dims of a head."""
    dim = cos.shape[-1]
    return jnp.concatenate([rotate(x[..., :dim], cos, sin), x[..., dim:]], -1)


# ---- attention ------------------------------------------------------------------
def window_attention(q, k, v, window, scale, mm):
    """softmax(q k^T scale) v over (B, H, L, d) where query i sees the keys
    ``0 <= i - j < window``, a block of queries at a time."""
    b, h, l, d = q.shape
    block = QUERY_BLOCK if l % QUERY_BLOCK == 0 else l
    back = window - 1                     # keys before a block's first query
    span = back + block
    # key c of a block's span lies back - c before the block's first query
    ahead = jnp.arange(block)[:, None] + back - jnp.arange(span)[None, :]
    inside = (ahead >= 0) & (ahead < window)
    kp = jnp.pad(k, ((0, 0), (0, 0), (back, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (back, 0), (0, 0)))

    @jax.checkpoint
    def one(n):
        start = n * block
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        kb = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=2)
        # the padding before the row's first key is no key
        real = start - back + jnp.arange(span) >= 0
        s = mm(qb, jnp.swapaxes(kb, -1, -2)) * scale
        s = jnp.where(inside & real[None, :], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vb)

    out = jax.lax.map(one, jnp.arange(l // block))      # (blocks, B, H, ., d)
    return jnp.moveaxis(out, 0, 2).reshape(b, h, l, v.shape[-1])


def attention(cfg, i, p, x, mm):
    dh, kind = cfg["head_dim"], cfg["layer_types"][i]
    hq, hkv = cfg["num_attention_heads_per_layer"][i], \
        cfg["num_key_value_heads"]
    b, l, _ = x.shape
    cos, sin = rope_tables(cfg, kind, l)

    def heads(t, n):
        return t.reshape(b, l, n, dh).transpose(0, 2, 1, 3)

    q = turn(heads(mm(x, p["attn.q"]), hq), cos, sin)
    k = turn(heads(mm(x, p["attn.k"]), hkv), cos, sin)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(heads(mm(x, p["attn.v"]), hkv), hq // hkv, axis=1)
    if kind == SLIDING and cfg["sliding_window"] < l:
        o = window_attention(q, k, v, cfg["sliding_window"], dh ** -0.5, mm)
    else:
        o = causal_attention(q, k, v, dh ** -0.5, mm)
    if cfg["gating"] != "per-head":
        raise ValueError("this family's output gate is one logit a head")
    o = o * jax.nn.sigmoid(mm(x, p["attn.gate"])).transpose(0, 2, 1)[..., None]
    return mm(o.transpose(0, 2, 1, 3).reshape(b, l, hq * dh), p["attn.o"])


# ---- experts ----------------------------------------------------------------------
def gate_weights(cfg, scores):
    """(..., E) weight of every expert for every token, zero where it was
    not chosen: the top-k scores over their sum, scaled."""
    _, choice = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, choice, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["moe_routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(choice, scores.shape[-1], dtype=w.dtype) *
                   w[..., None], axis=-2)


def routed_part(cfg, p, x, mm):
    """What the experts held here add: ``sum_j w[:, first + j] E_j(x)``."""
    held, _, first = _experts_of(cfg)
    if cfg["moe_router_logit_softcapping"]:
        raise ValueError("the router's logits are not capped in this family")
    w = gate_weights(cfg, jax.nn.softmax(mm(x, p["mlp.router"]), axis=-1))
    w = jnp.moveaxis(w[..., first:first + held], -1, 0)     # (held, ..., T)

    @jax.checkpoint
    def one(y, expert):
        gate, up, down, weight = expert
        return y + weight[..., None] * swiglu(x, gate, up, down, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["mlp.experts.gate"], p["mlp.experts.up"], p["mlp.experts.down"], w))
    return y


def shared_part(p, x, mm):
    return swiglu(x, p["mlp.shared.gate"], p["mlp.shared.up"],
                  p["mlp.shared.down"], mm)


# ---- the model ------------------------------------------------------------------
def block(cfg, mm, i):
    eps = cfg["rms_norm_eps"]

    def run(p, x):
        x = x + attention(cfg, i, p, rms(x, eps, p["input_norm"]), mm)
        h = rms(x, eps, p["post_attn_norm"])
        if cfg["mlp_layer_types"][i] == "dense":
            return x + swiglu(h, p["mlp.gate"], p["mlp.up"], p["mlp.down"], mm)
        return x + shared_part(p, h, mm) + routed_part(cfg, p, h, mm)
    return run


def hidden(cfg, p, ids, mm):
    x = p["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(block(cfg, mm, i))(_under(p, f"layers.{i}."), x)
    return x


def logits_of(cfg, p, h, mm):
    return mm(rms(h, cfg["rms_norm_eps"], p["norm"]), p["head"])


def denominators(batch):
    ids, _ = batch
    return {"lm": float(ids.shape[0] * ids.shape[1])}


def loss_part(cfg):
    def part(p, rows, denoms, mm):
        ids, labels = rows
        logits = logits_of(cfg, p, hidden(cfg, p, ids, mm), mm)
        return c.ce_sum(logits, labels) / denoms["lm"]
    return part
