"""solar2: ``paddle_tpu.models.nlp.hybrid_moe.HybridMoE`` under
``latent_moe_loss``: three gated-delta-rule linear-attention layers to one
gated softmax layer over grouped-query heads without positions, every layer
sigmoid-routed experts beside a shared one, as the chip that holds a share of
each layer's heads and experts.

What ``benchmark/README.md``'s table would say of this family (a
``model_config`` PR edits no file the benchmark has): ``families/solar2.py``
builds the program's model from the source's own keys. ``num_attention_heads``,
``num_key_value_heads`` and ``linear_attn_config.num_heads`` are the heads
HELD, ``*_published`` beside them what the model has, ``first_head`` where
the share starts; ``n_routed_experts`` is the experts held,
``n_routed_experts_published`` the router's width, ``first_routed_expert``
where that share starts. ``reference/solar2.py`` is its plain float32
reference. The model's buffers (``e_score_correction_bias``, ``expert_load``,
``linear_attn_stats``) are not persistable, so the seeded weights cover its
whole state dict. A program without the hybrid model (any before PR 37) is
refused as this module is imported, before any weight is made: the run exits
non-zero.

**A step's FLOPs** (``step_flops``; recompute not counted): 6 a parameter a
position's forward pass multiplies with (every matrix outside the token
embedding; of the held experts the share a position reaches in expectation,
``num_experts_per_tok / n_routed_experts_published`` each), plus the softmax
layers' attention over the held query heads as ``families/joyai.py`` counts
it (``3 x 2 x (Dqk + Dv) L`` a head a position, the whole length for a causal
model too), plus the linear layers' recurrence **as the token-by-token form
has it**: a token a head decays the state (d^2), reads it with k (2 d^2),
adds the rank-one correction (2 d^2, with beta k formed in d) and reads it
with q (2 d^2), 8 d^2 with the decay's exponentials and the small terms
rounded in; three times that with the backward pass. The chunked form the
program runs does other work (pairwise decays, a triangular solve, products
with the chunk's state), which is not what is counted.
"""
import importlib.util
import math
import weakref

from benchmark.families import _recipe
from benchmark.reference import solar2 as reference

if importlib.util.find_spec("paddle_tpu.models.nlp.hybrid_moe") is None:
    raise SystemExit("this program has no models.nlp.hybrid_moe (linear-"
                     "attention layers, grouped-query heads, a share of the "
                     "heads): family solar2 cannot run")

valid_tokens = _recipe.full_rows
_BUILT = None   # a weak reference to the model built last: readers ask it

_EXPERTS = {"mlp.routed.router": "mlp.router",
            "mlp.routed.experts_gate": "mlp.experts.gate",
            "mlp.routed.experts_up": "mlp.experts.up",
            "mlp.routed.experts_down": "mlp.experts.down",
            "mlp.shared.gate.weight": "mlp.shared.gate",
            "mlp.shared.up.weight": "mlp.shared.up",
            "mlp.shared.down.weight": "mlp.shared.down"}
_LINEAR = {f"attn.{k}.weight": f"attn.{k}"
           for k in ("q", "k", "v", "f_a", "f_b", "beta", "g_a", "g_b", "o")}
_LINEAR.update({f"attn.{k}": f"attn.{k}" for k in (
    "q_conv", "k_conv", "v_conv", "A_log", "dt_bias", "o_norm")})
_LINEAR["attn.g_b.bias"] = "attn.g_bias"
_SOFTMAX = {f"attn.{k}.weight": f"attn.{k}"
            for k in ("q", "k", "v", "gate", "o")}


def name_map(cfg):
    """program's structured parameter name -> reference name."""
    out = {"embed.weight": "embed", "final_norm.weight": "norm",
           "head.weight": "head"}
    for i in range(cfg["num_hidden_layers"]):
        names = {"attn_norm.weight": "input_norm",
                 "mlp_norm.weight": "post_attn_norm", **_EXPERTS,
                 **(_SOFTMAX if reference.is_softmax(cfg, i) else _LINEAR)}
        if not cfg["n_shared_experts"]:
            names = {k: v for k, v in names.items() if "shared" not in k}
        out.update({f"blocks.{i}.{prog}": f"layers.{i}.{ref}"
                    for prog, ref in names.items()})
    return out


def program_config(cfg):
    from paddle_tpu.models.nlp.hybrid_moe import HybridMoEConfig

    lin = cfg["linear_attn_config"]
    if cfg["use_rope"] or cfg["kda_use_full_proj"] or \
            cfg["tie_word_embeddings"] or cfg["first_k_dense_replace"] or \
            not cfg["use_gqa_gate"] or lin["num_kv_heads"] is not None:
        raise ValueError("the program's HybridMoE has softmax layers without "
                         "positions under an output gate, low-rank gate "
                         "projections, experts in every layer, an untied head")
    held = cfg["num_attention_heads"]
    heads = cfg.get("num_attention_heads_published", held)
    return HybridMoEConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], softmax_layers=cfg["gqa_layers"],
        heads=heads,
        kv_heads=cfg.get("num_key_value_heads_published",
                         cfg["num_key_value_heads"]),
        head_dim=cfg["head_dim"],
        linear_heads=lin["num_heads"] * heads // held,
        linear_head_dim=lin["head_dim"],
        conv_size=lin["short_conv_kernel_size"],
        gate_rank=cfg["kda_gate_rank"],
        neg_eigval=cfg["kda_allow_neg_eigval"], heads_held=held,
        first_head=cfg.get("first_head", 0),
        experts=cfg.get("n_routed_experts_published",
                        cfg["n_routed_experts"]),
        expert_width=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"],
        first_expert=cfg.get("first_routed_expert", 0),
        experts_held=cfg["n_routed_experts"], rms_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        conv_initializer_range=cfg["conv_initializer_range"],
        **cfg.get("program", {}))


def build(cfg, weights, mesh_axes):
    """(model, step) with the seeded weights in place."""
    from paddle_tpu.models.nlp.hybrid_moe import HybridMoE
    from paddle_tpu.models.nlp.latent_moe import latent_moe_loss

    global _BUILT
    model = HybridMoE(program_config(cfg))
    _BUILT = weakref.ref(model)
    model.bfloat16()
    _recipe.load_weights(model, weights, name_map(cfg))
    return model, _recipe.train_step(model, latent_moe_loss, cfg["recipe"],
                                     mesh_axes)


def used_params(cfg):
    """Parameters a position's forward pass multiplies with: every leaf but
    the token embedding (a lookup), of each layer's held experts the share a
    position reaches in expectation (``num_experts_per_tok`` slots spread
    evenly over the published experts reach each held one with probability
    k / E)."""
    reach = cfg["num_experts_per_tok"] / cfg.get(
        "n_routed_experts_published", cfg["n_routed_experts"])
    total = 0.0
    for name, shape, _ in reference.param_specs(cfg):
        if name != "embed":
            size = math.prod(shape)
            total += size * reach if ".mlp.experts." in name else size
    return total


def flops_per_position(cfg, length):
    """See the module's docstring."""
    softmax = len(cfg["gqa_layers"])
    lin = cfg["linear_attn_config"]
    return 6.0 * used_params(cfg) + \
        6.0 * softmax * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * \
        length + \
        3.0 * (cfg["num_hidden_layers"] - softmax) * lin["num_heads"] * \
        8.0 * lin["head_dim"] ** 2


step_flops = _recipe.token_rows_step_flops(flops_per_position)


def expert_load(steps):
    """(steps, expert layers, experts held) slots of the last ``steps`` steps
    of the model this module built last, from the program's own counter
    (``LatentMoE.expert_load_counts``); None once that model is gone."""
    model = _BUILT() if _BUILT is not None else None
    if model is None:
        return None
    c = model.cfg
    return model.expert_load_counts(steps)[
        ..., c.first_expert:c.first_expert + c.experts_held]
