"""Solar-Open2 (config ``model_type: solar_open2``) as its config's keys
describe it, in float32 ``jax.numpy``; imports nothing of the program under
test. Every projection goes through the ``mm`` it is handed. Family
``solar2``.

What is the same as in the other families' references is imported: the RMS
norm from ``reference/xing4.py`` (``rms``), and from ``reference/joyai.py``
causal softmax attention a group of heads at a time (``causal_attention``)
and the expert layer (``experts``: ``s = sigmoid(h W_g)`` over all published
experts, ``w = s[choice] / (sum + 1e-20) * routed_scaling_factor`` over the
top ``num_experts_per_tok`` of the scores plus a zero bias, the held experts
``E(h) = (silu(h W_gate) * (h W_up)) W_down`` as a scan over their stacked
leaves, plus the shared expert). What differs is here:

- **Residual**: plain pre-norm, ``x = x + Attn_i(RMS_w(x))``, ``x = x +
  MoE(RMS_w(x))``; every layer has routed experts (``first_k_dense_replace``
  0); ``logits = RMS_w(x) W_head``. Layer i is softmax attention where i is in
  ``gqa_layers``, else linear attention.
- **Linear attention** (Kimi Linear's KDA, arXiv:2510.26692, as
  ``fla.layers.kda`` states it; a head h of the ``linear_attn_config.
  num_heads`` held, d = its ``head_dim``): ``q', k', v' = SiLU(conv(W_q x)),
  SiLU(conv(W_k x)), SiLU(conv(W_v x))``, the convolution depthwise, causal,
  ``short_conv_kernel_size`` wide, no bias (tap K-1 on the token itself);
  ``q_t = unit(q'_t) d^-1/2``, ``k_t = unit(k'_t)``, ``unit(x) = x /
  sqrt(sum x^2 + 1e-6)``; ``g_t = -exp(A_log_h) softplus(W_f2 W_f1 x_t +
  dt_bias)`` in R^d, ``alpha_t = exp(g_t)``; ``beta_t = 2 sigmoid(w_b x_t)``
  (``kda_allow_neg_eigval``; without it no 2);

      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T

  in R^{d x d}, ``S_0 = 0`` at the start of a row; ``o_t = S_t^T q_t``; output
  ``W_o concat_h[RMS_w(o_t) * sigmoid(W_g2 W_g1 x_t + b_g)]`` (the norm over a
  head's d, one weight for all heads). ``kda_use_full_proj`` false: ``W_f1``,
  ``W_g1`` are hidden x ``kda_gate_rank``, held whole; everything with a
  head axis is the held heads' slice. **The rule runs token by token** (a
  ``lax.scan`` over positions, the line above as it stands), in segments of
  ``SEGMENT`` positions each under a ``checkpoint`` of its own, so that the
  backward pass keeps a state a segment and not a state a token (2 GB a layer
  at 4,096 tokens).
- **Softmax attention**: ``q = W_q x`` in the ``num_attention_heads`` held,
  ``k, v = W_k x, W_v x`` in the ``num_key_value_heads`` they read (query head
  j of the held reads key/value head ``j // (held / kv held)``), no rotary
  embedding (``use_rope`` false), causal softmax at ``head_dim^-1/2``, output
  ``W_o (att * sigmoid(W_gate x))`` (``use_gqa_gate``).

Departures from a whole model, each the configuration file's (``changed``,
``assumed``): heads ``first_head ..`` and experts ``first_routed_expert ..``
only, the vocabulary's slice; what the others would add is left out, as on
one chip of a tensor- and expert-parallel group before its exchange.
"""
import math

import jax
import jax.numpy as jnp

from . import _common as c
from .joyai import causal_attention, experts
from .xing4 import _under, rms

SEGMENT = 64     # positions of the recurrence under one checkpoint


# ---- parameters -------------------------------------------------------------
def is_softmax(cfg, i):
    return i in cfg["gqa_layers"]


def _layer_specs(cfg, i):
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    out = std / math.sqrt(2 * cfg["num_hidden_layers"])   # as GPT-2's
    normal, ones, zeros = ("normal", std), ("ones",), ("zeros",)
    specs = [("input_norm", (d,), ones)]
    if is_softmax(cfg, i):
        dh = cfg["head_dim"]
        hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        specs += [("attn.q", (d, hq * dh), normal),
                  ("attn.k", (d, hkv * dh), normal),
                  ("attn.v", (d, hkv * dh), normal),
                  ("attn.gate", (d, hq * dh), normal),
                  ("attn.o", (hq * dh, d), ("normal", out))]
    else:
        lin = cfg["linear_attn_config"]
        h, dh, taps = lin["num_heads"], lin["head_dim"], \
            lin["short_conv_kernel_size"]
        rank = cfg["kda_gate_rank"]
        conv = ("normal", cfg["conv_initializer_range"])
        specs += [("attn.q", (d, h * dh), normal),
                  ("attn.k", (d, h * dh), normal),
                  ("attn.v", (d, h * dh), normal),
                  ("attn.q_conv", (taps, h * dh), conv),
                  ("attn.k_conv", (taps, h * dh), conv),
                  ("attn.v_conv", (taps, h * dh), conv),
                  ("attn.f_a", (d, rank), normal),
                  ("attn.f_b", (rank, h * dh), normal),
                  ("attn.A_log", (h,), zeros),
                  ("attn.dt_bias", (h * dh,), zeros),
                  ("attn.beta", (d, h), normal),
                  ("attn.g_a", (d, rank), normal),
                  ("attn.g_b", (rank, h * dh), normal),
                  ("attn.g_bias", (h * dh,), zeros),
                  ("attn.o_norm", (dh,), ones),
                  ("attn.o", (h * dh, d), ("normal", out))]
    w, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    published = cfg.get("n_routed_experts_published", held)
    specs += [("post_attn_norm", (d,), ones),
              ("mlp.router", (d, published), normal),
              ("mlp.experts.gate", (held, d, w), normal),
              ("mlp.experts.up", (held, d, w), normal),
              ("mlp.experts.down", (held, w, d), ("normal", out))]
    if cfg["n_shared_experts"]:
        s = cfg["n_shared_experts"] * w
        specs += [("mlp.shared.gate", (d, s), normal),
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


# ---- linear attention ---------------------------------------------------------
def short_conv(x, w):
    """Causal depthwise convolution a channel of (B, L, C) with taps (K, C),
    the last tap on the token itself, then SiLU."""
    taps, length = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + length] * w[j]
                           for j in range(taps)))


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """``o_t = S_t^T q_t`` of the gated delta rule, token by token from ``S_0
    = 0``. q, k, g: (B, L, H, d); v: (B, L, H, dv); beta: (B, L, H)."""
    batch, length, heads, d = q.shape

    def token(s, x):
        qt, kt, vt, gt, bt = x                      # (B, H, d) .. (B, H)
        s = s * jnp.exp(gt)[..., None]              # Diag(alpha) S
        seen = jnp.sum(s * kt[..., None], axis=-2)  # S^T k
        s = s + (bt[..., None] * kt)[..., None] * (vt - seen)[..., None, :]
        return s, jnp.sum(s * qt[..., None], axis=-2)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs)

    pad = -length % SEGMENT

    def segments(x):    # (B, L, ...) -> (L / SEGMENT, SEGMENT, B, ...)
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((-1, SEGMENT) + x.shape[1:])

    _, o = jax.lax.scan(
        segment, jnp.zeros((batch, heads, d, v.shape[-1]), jnp.float32),
        tuple(segments(x) for x in (q, k, v, g, beta)))
    o = o.reshape((-1,) + o.shape[2:])[:length]     # (L, B, H, dv)
    return jnp.moveaxis(o, 0, 1)


def linear_attention(cfg, p, x, mm):
    lin = cfg["linear_attn_config"]
    h, dh = lin["num_heads"], lin["head_dim"]
    b, l, _ = x.shape

    def heads(t):
        return t.reshape(b, l, h, dh)

    q = heads(short_conv(mm(x, p["attn.q"]), p["attn.q_conv"]))
    k = heads(short_conv(mm(x, p["attn.k"]), p["attn.k_conv"]))
    v = heads(short_conv(mm(x, p["attn.v"]), p["attn.v_conv"]))
    g = -jnp.exp(p["attn.A_log"])[:, None] * heads(jax.nn.softplus(
        mm(mm(x, p["attn.f_a"]), p["attn.f_b"]) + p["attn.dt_bias"]))
    beta = jax.nn.sigmoid(mm(x, p["attn.beta"]))
    if cfg["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    o = delta_rule(unit(q) * dh ** -0.5, unit(k), v, g, beta)
    gate = heads(mm(mm(x, p["attn.g_a"]), p["attn.g_b"]) + p["attn.g_bias"])
    o = rms(o, cfg["rms_norm_eps"], p["attn.o_norm"]) * jax.nn.sigmoid(gate)
    return mm(o.reshape(b, l, h * dh), p["attn.o"])


# ---- softmax attention --------------------------------------------------------
def softmax_attention(cfg, p, x, mm):
    dh = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b, l, _ = x.shape

    def heads(t, n):
        return t.reshape(b, l, n, dh).transpose(0, 2, 1, 3)

    q = heads(mm(x, p["attn.q"]), hq)
    k = jnp.repeat(heads(mm(x, p["attn.k"]), hkv), hq // hkv, axis=1)
    v = jnp.repeat(heads(mm(x, p["attn.v"]), hkv), hq // hkv, axis=1)
    if cfg["use_rope"]:
        raise ValueError("this family's softmax layers carry no positions")
    o = causal_attention(q, k, v, dh ** -0.5, mm)
    o = o.transpose(0, 2, 1, 3).reshape(b, l, hq * dh)
    if cfg["use_gqa_gate"]:
        o = o * jax.nn.sigmoid(mm(x, p["attn.gate"]))
    return mm(o, p["attn.o"])


# ---- the model ----------------------------------------------------------------
def block(cfg, mm, softmax):
    eps = cfg["rms_norm_eps"]
    attend = softmax_attention if softmax else linear_attention

    def run(p, x):
        x = x + attend(cfg, p, rms(x, eps, p["input_norm"]), mm)
        return x + experts(cfg, p, rms(x, eps, p["post_attn_norm"]), mm)
    return run


def hidden(cfg, p, ids, mm):
    x = p["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        run = jax.checkpoint(block(cfg, mm, is_softmax(cfg, i)))
        x = run(_under(p, f"layers.{i}."), x)
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
