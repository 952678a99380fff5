"""Granite 4.0-H (config ``model_type: granitemoehybrid``, no experts:
``num_local_experts`` 0) as its config's keys describe it, in float32
``jax.numpy``; imports nothing of the program under test. Every projection
and every attention product goes through the ``mm`` it is handed. Family
``granite4h``.

What is the same as in the other families' references is imported: the RMS
norm and ``E(h) = (silu(h W_gate) * (h W_up)) W_down`` from
``reference/xing4.py`` (``rms``, ``swiglu``), and causal softmax attention a
group of heads at a time from ``reference/joyai.py`` (``causal_attention``:
one head's 8,192 x 8,192 scores at a time, made again in the backward pass).
What differs is here (``x`` the residual state, ``r = residual_multiplier``):

- **Model**: ``x_0 = embedding_multiplier E[ids]``; a block ``h = x + r
  Mixer(RMS_w(x))``, ``x' = h + r MLP(RMS_w(h))`` with the SwiGLU at
  ``shared_intermediate_size``; ``logits = (RMS_w(x_L) E^T) /
  logits_scaling``, ``E`` the one tied matrix (``tie_word_embeddings``): no
  leaf ``head``, and the gradient of ``embed`` is the sum of its two uses.
- **Mixer, ``layer_types[i] == "mamba"``** (Mamba-2, arXiv:2405.21060, as
  Hugging Face's ``GraniteMoeHybridMambaLayer`` states it; H =
  ``mamba_n_heads`` of P = ``mamba_d_head``, N = ``mamba_d_state``,
  ``mamba_n_groups`` 1, ``d_inner = mamba_expand x hidden_size = H P``):
  ``[z_t, u_t, d_t] = W_in x_t`` (``d_inner``, ``d_inner + 2 N``, H; no bias);
  ``[x'_t, B_t, C_t] = SiLU(conv(u)_t + b_conv)``, the convolution depthwise,
  causal, ``mamba_d_conv`` wide (tap K-1 on the token itself); ``Delta_t,h =
  softplus(d_t,h + dt_bias_h)``, ``a_t,h = exp(-exp(A_log_h) Delta_t,h)``;

      S_t,h = a_t,h S_t-1,h + Delta_t,h x'_t,h B_t^T     in R^{P x N}
      y_t,h = S_t,h C_t + D_h x'_t,h

  ``S = 0`` at the start of a row and carried across the documents packed
  into it; ``out_t = W_out (w * RMS(y_t * SiLU(z_t)))``, the RMS over all
  ``d_inner`` channels. **The recurrence runs token by token** (a
  ``lax.scan`` over positions, the two lines above as they stand), in
  segments of ``SEGMENT`` positions each under a ``checkpoint`` of its own,
  so that the backward pass keeps a state a segment and not a state a token
  (17 GB a layer at 8,192 tokens).
- **Mixer, ``"attention"``**: ``q, k, v = W_q x, W_k x, W_v x`` in
  ``num_attention_heads`` / ``num_key_value_heads`` heads of ``hidden_size /
  num_attention_heads`` (query head j reads key/value head ``j // group``),
  no position embedding (``position_embedding_type: nope``), causal
  ``softmax(q k^T attention_multiplier) v``, ``W_o``; no bias, no gate.

Departures from a whole model, each the configuration file's (``changed``,
``assumed``): the first ``num_hidden_layers`` layers and the vocabulary's
slice only.
"""
import math

import jax
import jax.numpy as jnp

from . import _common as c
from .joyai import causal_attention
from .xing4 import _under, rms, swiglu

SEGMENT = 64     # positions of the recurrence under one checkpoint


# ---- parameters -------------------------------------------------------------
def is_mamba(cfg, i):
    return cfg["layer_types"][i] == "mamba"


def mamba_sizes(cfg):
    """(heads, a head's width, the state's columns, d_inner, the channels the
    convolution runs over)."""
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    if inner != h * p or cfg["mamba_n_groups"] != 1:
        raise ValueError("this family's state-space layers have mamba_expand "
                         "x hidden_size = heads x head channels and one "
                         "group of B and C")
    return h, p, n, inner, inner + 2 * n


def _layer_specs(cfg, i):
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    out = std / math.sqrt(2 * cfg["num_hidden_layers"])   # as GPT-2's
    normal, ones, zeros = ("normal", std), ("ones",), ("zeros",)
    specs = [("input_norm", (d,), ones)]
    if is_mamba(cfg, i):
        h, _, _, inner, conv = mamba_sizes(cfg)
        specs += [("mixer.in_proj", (d, inner + conv + h), normal),
                  ("mixer.conv", (cfg["mamba_d_conv"], conv),
                   ("normal", cfg["conv_initializer_range"])),
                  ("mixer.conv_bias", (conv,), zeros),
                  ("mixer.dt_bias", (h,), zeros),
                  ("mixer.A_log", (h,), zeros),
                  ("mixer.D", (h,), ones),
                  ("mixer.norm", (inner,), ones),
                  ("mixer.out_proj", (inner, d), ("normal", out))]
    else:
        hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        dh = d // hq
        specs += [("attn.q", (d, hq * dh), normal),
                  ("attn.k", (d, hkv * dh), normal),
                  ("attn.v", (d, hkv * dh), normal),
                  ("attn.o", (hq * dh, d), ("normal", out))]
    w = cfg["shared_intermediate_size"]
    specs += [("post_attn_norm", (d,), ones),
              ("mlp.gate", (d, w), normal), ("mlp.up", (d, w), normal),
              ("mlp.down", (w, d), ("normal", out))]
    return [(f"layers.{i}.{k}", shape, init) for k, shape, init in specs]


def param_specs(cfg):
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    if not cfg["tie_word_embeddings"]:
        raise ValueError("this family's head is its embedding")
    specs = [("embed", (cfg["vocab_size"], d), ("normal", std))]
    for i in range(cfg["num_hidden_layers"]):
        specs += _layer_specs(cfg, i)
    return specs + [("norm", (d,), ("ones",))]


# ---- the state-space mixer --------------------------------------------------
def biased_conv(x, w, bias):
    """Causal depthwise convolution a channel of (B, L, C) with taps (K, C),
    the last tap on the token itself, plus a bias a channel, then SiLU."""
    taps, length = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + length] * w[j]
                           for j in range(taps)) + bias)


def recurrence(x, dt, rate, b, cc):
    """``y_t = S_t C_t`` of the state-space recurrence, token by token from
    ``S = 0``. x: (B, L, H, P); dt: (B, L, H); rate = -exp(A_log): (H,); b,
    cc: (B, L, N)."""
    batch, length, heads, width = x.shape

    def token(s, inputs):
        xt, dtt, bt, ct = inputs            # (B, H, P), (B, H), (B, N) twice
        s = s * jnp.exp(rate * dtt)[..., None, None] + \
            (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        return s, jnp.sum(s * ct[:, None, None, :], axis=-1)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs)

    pad = -length % SEGMENT

    def segments(t):    # (B, L, ...) -> (L / SEGMENT, SEGMENT, B, ...)
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((-1, SEGMENT) + t.shape[1:])

    _, y = jax.lax.scan(
        segment, jnp.zeros((batch, heads, width, b.shape[-1]), jnp.float32),
        tuple(segments(t) for t in (x, dt, b, cc)))
    y = y.reshape((-1,) + y.shape[2:])[:length]     # (L, B, H, P)
    return jnp.moveaxis(y, 0, 1)


def mamba_mixer(cfg, p, x, mm):
    h, width, n, inner, conv = mamba_sizes(cfg)
    batch, length, _ = x.shape
    if not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"]:
        raise ValueError("this family's convolution has a bias and its "
                         "projections none")
    zud = mm(x, p["mixer.in_proj"])
    z, u, raw = zud[..., :inner], zud[..., inner:inner + conv], \
        zud[..., inner + conv:]
    xbc = biased_conv(u, p["mixer.conv"], p["mixer.conv_bias"])
    xs = xbc[..., :inner].reshape(batch, length, h, width)
    b, cc = xbc[..., inner:inner + n], xbc[..., inner + n:]
    dt = jax.nn.softplus(raw + p["mixer.dt_bias"])
    y = recurrence(xs, dt, -jnp.exp(p["mixer.A_log"]), b, cc)
    y = (y + p["mixer.D"][:, None] * xs).reshape(batch, length, inner)
    y = rms(y * jax.nn.silu(z), cfg["rms_norm_eps"], p["mixer.norm"])
    return mm(y, p["mixer.out_proj"])


# ---- softmax attention ------------------------------------------------------
def softmax_attention(cfg, p, x, mm):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b, l, d = x.shape
    dh = d // hq
    if cfg["position_embedding_type"] != "nope" or cfg["attention_bias"]:
        raise ValueError("this family's attention layers carry no positions "
                         "and no bias")

    def heads(t, n):
        return t.reshape(b, l, n, dh).transpose(0, 2, 1, 3)

    q = heads(mm(x, p["attn.q"]), hq)
    k = jnp.repeat(heads(mm(x, p["attn.k"]), hkv), hq // hkv, axis=1)
    v = jnp.repeat(heads(mm(x, p["attn.v"]), hkv), hq // hkv, axis=1)
    o = causal_attention(q, k, v, cfg["attention_multiplier"], mm)
    return mm(o.transpose(0, 2, 1, 3).reshape(b, l, hq * dh), p["attn.o"])


# ---- the model --------------------------------------------------------------
def block(cfg, mm, mamba):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = mamba_mixer if mamba else softmax_attention

    def run(p, x):
        x = x + r * mixer(cfg, p, rms(x, eps, p["input_norm"]), mm)
        h = rms(x, eps, p["post_attn_norm"])
        return x + r * swiglu(h, p["mlp.gate"], p["mlp.up"], p["mlp.down"],
                              mm)
    return run


def hidden(cfg, p, ids, mm):
    x = cfg["embedding_multiplier"] * p["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        run = jax.checkpoint(block(cfg, mm, is_mamba(cfg, i)))
        x = run(_under(p, f"layers.{i}."), x)
    return x


def logits_of(cfg, p, h, mm):
    return mm(rms(h, cfg["rms_norm_eps"], p["norm"]),
              p["embed"].T) / cfg["logits_scaling"]


def denominators(batch):
    ids, _ = batch
    return {"lm": float(ids.shape[0] * ids.shape[1])}


def loss_part(cfg):
    def part(p, rows, denoms, mm):
        ids, labels = rows
        logits = logits_of(cfg, p, hidden(cfg, p, ids, mm), mm)
        return c.ce_sum(logits, labels) / denoms["lm"]
    return part
