"""Xing4.0 (config ``model_type: xing4_0``) as its config's keys describe it,
in float32 ``jax.numpy``; imports nothing of the program under test. Every
matrix product goes through the ``mm`` it is handed. Family ``xing4``.

What the config's keys fix, and whose published equations they are:

- **Latent attention** (DeepSeek-V2 section 2.1, no absorption): ``c_q =
  RMS_w(h W_qa)`` (``q_lora_rank``), ``q = c_q W_qb`` in ``num_attention_heads``
  heads of ``[q_nope (qk_nope_head_dim), q_rope (qk_rope_head_dim)]``;
  ``[c_kv (kv_lora_rank), k_rope] = h W_kva``; ``[k_nope, v (v_head_dim)] =
  RMS_w(c_kv) W_kvb`` a head; rotary on ``q_rope`` and on ``k_rope``, which
  all heads share; causal softmax of ``q k^T s``, ``s = (nope + rope)^-0.5
  m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``; ``W_o`` on the heads'
  outputs. Rotary: ``rope_theta`` over the rope dims with YaRN frequencies
  (Peng et al. 2023: dims turning more than ``beta_fast`` times over
  ``original_max_position_embeddings`` keep their frequency, those under
  ``beta_slow`` are divided by ``factor``, a linear ramp between), cos and
  sin scaled by ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
  mscale_all_dim)``.
- **Experts** (DeepSeek-V3 section 2.1.2, ``scoring_func: sigmoid``,
  ``topk_method: noaux_tc``, ``n_group = topk_group = 1``): ``s = sigmoid(h
  W_g)`` over all published experts; the choice is the top
  ``num_experts_per_tok`` of ``s + b`` (``b`` = ``e_score_correction_bias``,
  zeros: it steers the choice, never the weight); ``w = s[choice] / (sum +
  1e-20) * routed_scaling_factor``; ``y = E_shared(h) + sum_{e chosen and
  held} w_e E_e(h)``, ``E(h) = (silu(h W_gate) * (h W_up)) W_down``. The first
  ``first_k_dense_replace`` layers are one such E at ``intermediate_size``.
- **Residual** (``hc_mult`` streams; manifold-constrained hyper-connections):
  state ``X`` (T, n, C), every stream the embedding at first. A sublayer F:
  ``x~ = RMS(vec(X))``; ``[P, Q, R] = x~ phi``; ``H_pre = sigmoid(a_pre P +
  b_pre)``, ``H_post = 2 sigmoid(a_post Q + b_post)``, ``H_res =
  SK(clip(a_res R + b_res, clamp))``, SK = exp, then ``hc_sinkhorn_iters``
  times rows / (sum + ``hc_eps``), columns / (sum + ``hc_eps``); ``h = sum_j
  H_pre[j] X_j``; ``y = F(RMS_w(h))``; ``X'_i = sum_j H_res[i, j] X_j +
  H_post[i] y``. Readout ``RMS_w(sum_j X_j) W_head``.
- **Multi-token prediction**, ``num_nextn_predict_layers`` 1 (DeepSeek-V3
  section 2.2): ``h' = W_eh [RMS_w(h_i); RMS_w(Emb(t_{i+1}))]``, one more
  expert block, the shared final norm and head, cross-entropy against
  ``t_{i+2}``; loss = main + lambda MTP.

Departures from a whole model, each the configuration file's (``changed``,
``assumed``): only the experts ``first_routed_expert .. + n_routed_experts``
of ``n_routed_experts_published`` are held (what the others would add is
left out, as on one chip of an expert-parallel group); the vocabulary is the
file's slice; the gates ``a_*`` are stored as multiples of ``hc_alpha_init``
and ``b_res`` as an offset from ``hc_res_init * I``, because the benchmark's
seeded weights are normal, ones or zeros.

So that a 4,096-token row fits beside 12 bytes a parameter: blocks run under
``jax.checkpoint``, attention runs ``ATTN_HEADS`` heads at a time under a
``checkpoint`` of its own (a head group's scores are 0.27 GB, all 32 heads'
2.1 GB a tensor), and the experts held are a plain loop, every token through
each and weighted by its gate (zero where it was not chosen).
"""
import math

import numpy as np

import jax
import jax.numpy as jnp

from . import _common as c

ATTN_HEADS = 4      # heads whose scores are held at a time


# ---- the configuration's derived sizes --------------------------------------
def _sizes(cfg):
    n = cfg["hc_mult"]
    return dict(
        d=cfg["hidden_size"], n=n, maps=2 * n + n * n,
        heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        held=cfg["n_routed_experts"],
        experts=cfg.get("n_routed_experts_published", cfg["n_routed_experts"]),
        first=cfg.get("first_routed_expert", 0))


def _layer_specs(cfg, prefix, dense):
    z = _sizes(cfg)
    d, std = z["d"], cfg["initializer_range"]
    out = std / math.sqrt(2 * cfg["num_hidden_layers"])   # as GPT-2's
    normal, ones, zeros = ("normal", std), ("ones",), ("zeros",)
    specs = []
    for hc in ("attn_hc.", "mlp_hc."):
        specs += [(hc + "phi", (z["n"] * d, z["maps"]), normal),
                  (hc + "alpha", (3,), ones), (hc + "bias", (z["maps"],), zeros)]
    specs += [
        ("input_norm", (d,), ones),
        ("attn.q_a", (d, cfg["q_lora_rank"]), normal),
        ("attn.q_a_norm", (cfg["q_lora_rank"],), ones),
        ("attn.q_b", (cfg["q_lora_rank"],
                      z["heads"] * (z["nope"] + z["rope"])), normal),
        ("attn.kv_a", (d, cfg["kv_lora_rank"] + z["rope"]), normal),
        ("attn.kv_a_norm", (cfg["kv_lora_rank"],), ones),
        ("attn.kv_b", (cfg["kv_lora_rank"],
                       z["heads"] * (z["nope"] + z["dv"])), normal),
        ("attn.o", (z["heads"] * z["dv"], d), ("normal", out)),
        ("post_attn_norm", (d,), ones)]
    if dense:
        w = cfg["intermediate_size"]
        specs += [("mlp.gate", (d, w), normal), ("mlp.up", (d, w), normal),
                  ("mlp.down", (w, d), ("normal", out))]
    else:
        w, held = cfg["moe_intermediate_size"], z["held"]
        specs += [("mlp.router", (d, z["experts"]), normal),
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
def rms(x, eps, w=None):
    y = x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y if w is None else y * w


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(cfg, length):
    """(cos, sin), each (length, rope dims), rotate-half layout."""
    dim, base, ys = cfg["qk_rope_head_dim"], cfg["rope_theta"], \
        cfg["rope_scaling"]
    plain = base ** (-np.arange(0, dim, 2) / dim)
    stretched = plain / ys["factor"]

    def dim_of(turns):   # the dim that turns this often over the old length
        return dim * math.log(ys["original_max_position_embeddings"] /
                              (turns * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(dim_of(ys["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(ys["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    freq = stretched * (1 - keep) + plain * keep
    angle = np.arange(length)[:, None] * freq[None, :]
    angle = np.concatenate([angle, angle], axis=1)
    scale = yarn_mscale(ys["factor"], ys["mscale"]) / \
        yarn_mscale(ys["factor"], ys["mscale_all_dim"])
    return jnp.asarray(np.cos(angle) * scale, jnp.float32), \
        jnp.asarray(np.sin(angle) * scale, jnp.float32)


def rotate(x, cos, sin):
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


def swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def attention_by_head_groups(q, k, v, scale, mm):
    """Causal softmax(q k^T scale) v over (B, H, L, d), ``ATTN_HEADS`` heads
    at a time, each group's scores made again in the backward pass."""
    b, h, l, _ = q.shape
    g = math.gcd(h, ATTN_HEADS)
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
    z, eps = _sizes(cfg), cfg["rms_norm_eps"]
    heads, nope, rope, dv = z["heads"], z["nope"], z["rope"], z["dv"]
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
    m = yarn_mscale(cfg["rope_scaling"]["factor"],
                    cfg["rope_scaling"]["mscale_all_dim"])
    o = attention_by_head_groups(q, k, kv[..., nope:],
                                 (nope + rope) ** -0.5 * m * m, mm)
    return mm(o.transpose(0, 2, 1, 3).reshape(b, l, heads * dv), p["attn.o"])


def gate_weights(cfg, scores):
    """(T, E) weight of every expert for every token, zero where it was not
    chosen: top-k of scores + bias (zeros), normalised, scaled."""
    k = cfg["num_experts_per_tok"]
    bias = jnp.zeros((scores.shape[-1],), scores.dtype)   # no step updates it
    _, choice = jax.lax.top_k(scores + bias, k)
    w = jnp.take_along_axis(scores, choice, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(choice, scores.shape[-1], dtype=w.dtype) *
                   w[..., None], axis=-2)


def experts(cfg, p, x, mm):
    z = _sizes(cfg)
    scores = jax.nn.sigmoid(mm(x, p["mlp.router"]))
    w = gate_weights(cfg, scores)
    y = jnp.zeros_like(x)
    for j in range(z["held"]):
        y = y + w[..., z["first"] + j, None] * swiglu(
            x, p["mlp.experts.gate"][j], p["mlp.experts.up"][j],
            p["mlp.experts.down"][j], mm)
    if cfg["n_shared_experts"]:
        y = y + swiglu(x, p["mlp.shared.gate"], p["mlp.shared.up"],
                       p["mlp.shared.down"], mm)
    return y


def sinkhorn(m, iters, eps):
    m = jnp.exp(m)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hyper_sublayer(cfg, p, hc, norm, x, fn, mm):
    """One sublayer over the streams ``x`` (B, L, n, C)."""
    n, eps = cfg["hc_mult"], cfg["rms_norm_eps"]
    flat = rms(x.reshape(x.shape[:-2] + (-1,)), eps)
    dyn = mm(flat, p[hc + "phi"])
    a = p[hc + "alpha"] * cfg["hc_alpha_init"]
    b = p[hc + "bias"]
    pre = jax.nn.sigmoid(a[0] * dyn[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * dyn[..., n:2 * n] + b[n:2 * n])
    res = (a[2] * dyn[..., 2 * n:] + b[2 * n:]).reshape(x.shape[:-2] + (n, n))
    res = res + cfg["hc_res_init"] * jnp.eye(n)
    res = sinkhorn(jnp.clip(res, cfg["mhc_h_res_clamp_min"],
                            cfg["mhc_h_res_clamp_max"]),
                   cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    h = jnp.einsum("...j,...jc->...c", pre, x)
    y = fn(rms(h, eps, p[norm]))
    return jnp.einsum("...ij,...jc->...ic", res, x) + \
        post[..., None] * y[..., None, :]


def block(cfg, mm, dense):
    def run(p, x):
        x = hyper_sublayer(cfg, p, "attn_hc.", "input_norm", x,
                           lambda h: latent_attention(cfg, p, h, mm), mm)
        if dense:
            def mlp(h):
                return swiglu(h, p["mlp.gate"], p["mlp.up"], p["mlp.down"], mm)
        else:
            def mlp(h):
                return experts(cfg, p, h, mm)
        return hyper_sublayer(cfg, p, "mlp_hc.", "post_attn_norm", x, mlp, mm)
    return run


def _under(p, prefix):
    return {k[len(prefix):]: w for k, w in p.items() if k.startswith(prefix)}


def _streams(cfg, h):
    return jnp.broadcast_to(h[..., None, :],
                            h.shape[:-1] + (cfg["hc_mult"], h.shape[-1]))


def hidden(cfg, p, ids, mm):
    x = _streams(cfg, p["embed"][ids])
    for i in range(cfg["num_hidden_layers"]):
        run = jax.checkpoint(block(cfg, mm, i < cfg["first_k_dense_replace"]))
        x = run(_under(p, f"layers.{i}."), x)
    return jnp.sum(x, axis=-2)


def logits_of(cfg, p, h, mm):
    return mm(rms(h, cfg["rms_norm_eps"], p["norm"]), p["head"])


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
        eps = cfg["rms_norm_eps"]
        joined = jnp.concatenate([rms(h, eps, p["mtp.hnorm"]),
                                  rms(p["embed"][labels], eps,
                                      p["mtp.enorm"])], axis=-1)
        x = jax.checkpoint(block(cfg, mm, False))(
            _under(p, "mtp.block."), _streams(cfg, mm(joined, p["mtp.proj"])))
        extra = logits_of(cfg, p, jnp.sum(x, axis=-2), mm)
        # position i predicts t_{i+2} = labels[i + 1]; the last has no target
        return loss + lam * c.ce_sum(extra[:, :-1], labels[:, 1:]) / \
            denoms["mtp"]
    return part
