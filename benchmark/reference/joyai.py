"""JoyAI-LLM-Flash (config ``model_type: joyai_llm_flash``) as its config's
keys describe it, in float32 ``jax.numpy``; imports nothing of the program
under test. Every matrix product goes through the ``mm`` it is handed. Family
``joyai``.

Its equations are DeepSeek-V3's. What is the same as in ``reference/xing4.py``
is imported from there (``rms``, ``rotate``, ``swiglu``, ``gate_weights``:
the RMS norm, the rotate-half rotation, ``E(h) = (silu(h W_gate) * (h W_up))
W_down``, and ``w = s[choice] / (sum + 1e-20) * routed_scaling_factor`` over
the top ``num_experts_per_tok`` of the sigmoid scores plus a zero bias, zero
where an expert was not chosen). What differs is written here:

- **Residual**: plain pre-norm, one state (B, L, C): ``x = x + Attn(RMS_w(x))``,
  ``x = x + MLP(RMS_w(x))``; ``logits = RMS_w(x) W_head``.
- **Latent attention** (DeepSeek-V2 section 2.1, no absorption): ``c_q =
  RMS_w(h W_qa)`` (``q_lora_rank``), ``q = c_q W_qb`` in ``num_attention_heads``
  heads of ``[q_nope, q_rope]``; ``[c_kv (kv_lora_rank), k_rope] = h W_kva``;
  ``[k_nope, v] = RMS_w(c_kv) W_kvb`` a head; rotary on ``q_rope`` and on the
  ``k_rope`` all heads share; causal softmax of ``q k^T (nope + rope)^-0.5``
  (``rope_scaling`` is null: no YaRN factor, no temperature); ``W_o``.
  Rotary: ``rope_theta ** (-2i / d)`` over the ``qk_rope_head_dim`` dims,
  unscaled, in the rotate-half layout (dim i against dim i + d/2). The
  config's ``rope_interleave: true`` pairs dims (2i, 2i + 1) instead; one
  fixed permutation of the rope columns of ``W_qb`` and ``W_kva`` maps one
  layout onto the other and leaves every score as it is, so under seeded
  weights either is the model (the configuration's ``assumed``).
- **Experts** (DeepSeek-V3 section 2.1.2): ``s = sigmoid(h W_g)`` over all
  ``n_routed_experts_published`` experts; ``y = E_shared(h) + sum_{e chosen
  and held} w_e E_e(h)`` over the experts ``first_routed_expert .. +
  n_routed_experts`` held here (what the others would add is left out, as on
  one chip of an expert-parallel group). Layers before
  ``first_k_dense_replace`` are one E at ``intermediate_size``.
- **Multi-token prediction**, depth 1 (DeepSeek-V3 section 2.2): ``h' = W_eh
  [RMS_w(h_i); RMS_w(Emb(t_{i+1}))]``, one more expert block, the shared
  final norm and head, cross-entropy against ``t_{i+2}``; loss = main +
  ``mtp_loss_weight`` x MTP, the means over ``rows x L`` and ``rows x (L -
  1)`` positions.

So that an 8,192-token row fits beside 12 bytes a parameter and compiles in
a cell's time: blocks run under ``jax.checkpoint``; attention runs as many
heads at a time as ``SCORE_BYTES`` of float32 scores hold (one at 8,192),
each group under a ``checkpoint`` of its own; the experts held are a
``lax.scan`` over their stacked leaves (one expert's program compiled once,
not sixteen), every token through each and weighted by its gate, summed in
the experts' order.
"""
import math

import numpy as np

import jax
import jax.numpy as jnp

from . import _common as c
from .xing4 import gate_weights, rms, rotate, swiglu

SCORE_BYTES = 2 ** 28   # float32 scores of the heads computed at a time


# ---- parameters -------------------------------------------------------------
def _experts_of(cfg):
    """(held, published, first held)."""
    held = cfg["n_routed_experts"]
    return held, cfg.get("n_routed_experts_published", held), \
        cfg.get("first_routed_expert", 0)


def _layer_specs(cfg, prefix, dense):
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    heads, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    out = std / math.sqrt(2 * cfg["num_hidden_layers"])   # as GPT-2's
    normal, ones = ("normal", std), ("ones",)
    specs = [
        ("input_norm", (d,), ones),
        ("attn.q_a", (d, cfg["q_lora_rank"]), normal),
        ("attn.q_a_norm", (cfg["q_lora_rank"],), ones),
        ("attn.q_b", (cfg["q_lora_rank"], heads * (nope + rope)), normal),
        ("attn.kv_a", (d, cfg["kv_lora_rank"] + rope), normal),
        ("attn.kv_a_norm", (cfg["kv_lora_rank"],), ones),
        ("attn.kv_b", (cfg["kv_lora_rank"], heads * (nope + dv)), normal),
        ("attn.o", (heads * dv, d), ("normal", out)),
        ("post_attn_norm", (d,), ones)]
    if dense:
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
        if cfg["n_shared_experts"]:
            s = cfg["n_shared_experts"] * w
            specs += [("mlp.shared.gate", (d, s), normal),
                      ("mlp.shared.up", (d, s), normal),
                      ("mlp.shared.down", (s, d), ("normal", out))]
    return [(prefix + k, shape, init) for k, shape, init in specs]


def param_specs(cfg):
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    specs = [("embed", (cfg["vocab_size"], d), ("normal", std))]
    for i in range(cfg["num_hidden_layers"]):
        specs += _layer_specs(cfg, f"layers.{i}.",
                              i < cfg["first_k_dense_replace"])
    specs += [("norm", (d,), ("ones",)),
              ("head", (d, cfg["vocab_size"]), ("normal", std))]
    if cfg["num_nextn_predict_layers"]:
        specs += [("mtp.hnorm", (d,), ("ones",)), ("mtp.enorm", (d,), ("ones",)),
                  ("mtp.proj", (2 * d, d), ("normal", std))]
        specs += _layer_specs(cfg, "mtp.block.", False)
    return specs


# ---- building blocks ---------------------------------------------------------
def rope_tables(cfg, length):
    """(cos, sin), each (length, rope dims), rotate-half layout, unscaled."""
    if cfg["rope_scaling"] is not None:
        raise ValueError("this family's rotary embedding is unscaled")
    dim = cfg["qk_rope_head_dim"]
    freq = float(cfg["rope_theta"]) ** (-np.arange(0, dim, 2) / dim)
    angle = np.arange(length)[:, None] * freq[None, :]
    angle = np.concatenate([angle, angle], axis=1)
    return jnp.asarray(np.cos(angle), jnp.float32), \
        jnp.asarray(np.sin(angle), jnp.float32)


def causal_attention(q, k, v, scale, mm):
    """Causal softmax(q k^T scale) v over (B, H, L, d), a group of heads at
    a time, each group's scores made again in the backward pass."""
    b, h, l, _ = q.shape
    g = math.gcd(h, max(1, SCORE_BYTES // (4 * b * l * l)))
    causal = jnp.tril(jnp.ones((l, l), bool))

    @jax.checkpoint
    def some(qkv):
        qg, kg, vg = qkv
        s = mm(qg, jnp.swapaxes(kg, -1, -2)) * scale
        return mm(jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), vg)

    def groups(t):   # (B, H, L, d) -> (H / g, B, g, L, d)
        return jnp.moveaxis(t.reshape(b, h // g, g, l, t.shape[-1]), 1, 0)

    out = jax.lax.map(some, (groups(q), groups(k), groups(v)))
    return jnp.moveaxis(out, 0, 1).reshape(b, h, l, v.shape[-1])


def latent_attention(cfg, p, x, mm):
    eps, heads = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    b, l, _ = x.shape
    cos, sin = rope_tables(cfg, l)
    q = mm(rms(mm(x, p["attn.q_a"]), eps, p["attn.q_a_norm"]), p["attn.q_b"])
    q = q.reshape(b, l, heads, nope + rope).transpose(0, 2, 1, 3)
    kv_a = mm(x, p["attn.kv_a"])
    c_kv, k_r = kv_a[..., :cfg["kv_lora_rank"]], kv_a[..., cfg["kv_lora_rank"]:]
    kv = mm(rms(c_kv, eps, p["attn.kv_a_norm"]), p["attn.kv_b"])
    kv = kv.reshape(b, l, heads, nope + dv).transpose(0, 2, 1, 3)
    k_r = jnp.broadcast_to(rotate(k_r, cos, sin)[:, None], (b, heads, l, rope))
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], cos, sin)], -1)
    k = jnp.concatenate([kv[..., :nope], k_r], -1)
    o = causal_attention(q, k, kv[..., nope:], (nope + rope) ** -0.5, mm)
    return mm(o.transpose(0, 2, 1, 3).reshape(b, l, heads * dv), p["attn.o"])


def routed_part(cfg, p, x, mm):
    """What the experts held here add: ``sum_j w[:, first + j] E_j(x)``."""
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


def experts(cfg, p, x, mm):
    y = routed_part(cfg, p, x, mm)
    if cfg["n_shared_experts"]:
        y = y + swiglu(x, p["mlp.shared.gate"], p["mlp.shared.up"],
                       p["mlp.shared.down"], mm)
    return y


def block(cfg, mm, dense):
    eps = cfg["rms_norm_eps"]

    def run(p, x):
        x = x + latent_attention(cfg, p, rms(x, eps, p["input_norm"]), mm)
        h = rms(x, eps, p["post_attn_norm"])
        if dense:
            return x + swiglu(h, p["mlp.gate"], p["mlp.up"], p["mlp.down"], mm)
        return x + experts(cfg, p, h, mm)
    return run


def _under(p, prefix):
    return {k[len(prefix):]: w for k, w in p.items() if k.startswith(prefix)}


def hidden(cfg, p, ids, mm):
    x = p["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        run = jax.checkpoint(block(cfg, mm, i < cfg["first_k_dense_replace"]))
        x = run(_under(p, f"layers.{i}."), x)
    return x


def logits_of(cfg, p, h, mm):
    return mm(rms(h, cfg["rms_norm_eps"], p["norm"]), p["head"])


def mtp_logits(cfg, p, h, next_ids, mm):
    """Position i: from the main stack's ``h_i`` and the embedding of the
    token after it, the logits of the token after that."""
    eps = cfg["rms_norm_eps"]
    joined = jnp.concatenate([rms(h, eps, p["mtp.hnorm"]),
                              rms(p["embed"][next_ids], eps, p["mtp.enorm"])],
                             axis=-1)
    x = jax.checkpoint(block(cfg, mm, False))(
        _under(p, "mtp.block."), mm(joined, p["mtp.proj"]))
    return logits_of(cfg, p, x, mm)


def denominators(batch):
    ids, _ = batch
    rows, length = ids.shape
    return {"lm": float(rows * length), "mtp": float(rows * (length - 1))}


def loss_part(cfg):
    lam = cfg.get("mtp_loss_weight", 0.3)

    def part(p, rows, denoms, mm):
        ids, labels = rows
        h = hidden(cfg, p, ids, mm)
        loss = c.ce_sum(logits_of(cfg, p, h, mm), labels) / denoms["lm"]
        if not cfg["num_nextn_predict_layers"]:
            return loss
        extra = mtp_logits(cfg, p, h, labels, mm)
        # position i predicts t_{i+2} = labels[i + 1]; the last has no target
        return loss + lam * c.ce_sum(extra[:, :-1], labels[:, 1:]) / \
            denoms["mtp"]
    return part
