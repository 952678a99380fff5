"""xing4: ``paddle_tpu.models.nlp.latent_moe.LatentMoE`` under
``latent_moe_loss``: latent attention, sigmoid-routed experts of which this
chip holds a contiguous share, a constrained multi-stream residual, optional
multi-token prediction.

What ``benchmark/README.md``'s table would say of this family (a
``model_config`` PR edits no file the benchmark has): ``families/xing4.py``
builds the program's model from the source's own keys (``n_routed_experts``
is the experts HELD, ``n_routed_experts_published`` the router's width,
``first_routed_expert`` where the share starts); ``reference/xing4.py`` is
its plain float32 reference. The model's two kinds of buffer
(``e_score_correction_bias``, ``expert_load``) are not persistable, so the
seeded weights cover its whole state dict.
"""
import math
import weakref

from benchmark.families import _recipe
from benchmark.reference import xing4 as reference

valid_tokens = _recipe.full_rows
_BUILT = None   # a weak reference to the model built last: readers ask it

_LAYER = {
    **{f"{hc}_hc.{leaf}": f"{hc}_hc.{leaf}" for hc in ("attn", "mlp")
       for leaf in ("phi", "alpha", "bias")},
    "attn_norm.weight": "input_norm", "mlp_norm.weight": "post_attn_norm",
    "attn.q_a.weight": "attn.q_a", "attn.q_norm.weight": "attn.q_a_norm",
    "attn.q_b.weight": "attn.q_b", "attn.kv_a.weight": "attn.kv_a",
    "attn.kv_norm.weight": "attn.kv_a_norm", "attn.kv_b.weight": "attn.kv_b",
    "attn.o.weight": "attn.o"}
_DENSE = {"mlp.gate.weight": "mlp.gate", "mlp.up.weight": "mlp.up",
          "mlp.down.weight": "mlp.down"}
_EXPERTS = {"mlp.routed.router": "mlp.router",
            "mlp.routed.experts_gate": "mlp.experts.gate",
            "mlp.routed.experts_up": "mlp.experts.up",
            "mlp.routed.experts_down": "mlp.experts.down"}
_SHARED = {"mlp.shared.gate.weight": "mlp.shared.gate",
           "mlp.shared.up.weight": "mlp.shared.up",
           "mlp.shared.down.weight": "mlp.shared.down"}


def _block_names(cfg, dense):
    names = dict(_LAYER)
    names.update(_DENSE if dense else _EXPERTS)
    if not dense and cfg["n_shared_experts"]:
        names.update(_SHARED)
    return names


def name_map(cfg):
    """program's structured parameter name -> reference name."""
    out = {"embed.weight": "embed", "final_norm.weight": "norm",
           "head.weight": "head"}
    for i in range(cfg["num_hidden_layers"]):
        for prog, ref in _block_names(
                cfg, i < cfg["first_k_dense_replace"]).items():
            out[f"blocks.{i}.{prog}"] = f"layers.{i}.{ref}"
    if cfg["num_nextn_predict_layers"]:
        out.update({"mtp.hnorm.weight": "mtp.hnorm",
                    "mtp.enorm.weight": "mtp.enorm",
                    "mtp.proj.weight": "mtp.proj"})
        for prog, ref in _block_names(cfg, False).items():
            out[f"mtp.block.{prog}"] = f"mtp.block.{ref}"
    return out


def program_config(cfg):
    from paddle_tpu.models.nlp.latent_moe import LatentMoEConfig

    if cfg["hidden_act"] != "silu" or cfg["scoring_func"] != "sigmoid" or \
            cfg["topk_method"] != "noaux_tc" or cfg["n_group"] != 1 or \
            cfg["topk_group"] != 1 or cfg["attention_bias"] or \
            cfg["tie_word_embeddings"] or cfg["moe_layer_freq"] != 1 or \
            cfg["rope_scaling"]["type"] != "yarn" or \
            cfg["num_nextn_predict_layers"] > 1:
        raise ValueError("the program's LatentMoE is silu, sigmoid noaux_tc "
                         "routing without groups, untied, YaRN, MTP depth <= 1")
    return LatentMoEConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        dense_width=cfg["intermediate_size"],
        heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=cfg["rope_theta"], rope_scaling=cfg["rope_scaling"],
        experts=cfg.get("n_routed_experts_published",
                        cfg["n_routed_experts"]),
        expert_width=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"],
        first_expert=cfg.get("first_routed_expert", 0),
        experts_held=cfg["n_routed_experts"], streams=cfg["hc_mult"],
        sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        hc_clamp=(cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]),
        hc_alpha_init=cfg["hc_alpha_init"], hc_res_init=cfg["hc_res_init"],
        rms_eps=cfg["rms_norm_eps"],
        mtp_layers=cfg["num_nextn_predict_layers"],
        mtp_lambda=cfg.get("mtp_loss_weight", 0.3),
        initializer_range=cfg["initializer_range"],
        **cfg.get("program", {}))


def build(cfg, weights, mesh_axes):
    """(model, step) with the seeded weights in place."""
    from paddle_tpu.models.nlp.latent_moe import LatentMoE, latent_moe_loss

    global _BUILT
    model = LatentMoE(program_config(cfg))
    _BUILT = weakref.ref(model)
    model.bfloat16()
    _recipe.load_weights(model, weights, name_map(cfg))
    return model, _recipe.train_step(model, latent_moe_loss, cfg["recipe"],
                                     mesh_axes)


def used_params(cfg):
    """Parameters a position's forward pass multiplies with: every leaf
    outside the routed experts and the token embedding (a lookup), and of each
    expert layer's held experts the share a position reaches in expectation:
    ``num_experts_per_tok`` slots spread evenly over the published experts
    reach each held one with probability k / E."""
    reach = cfg["num_experts_per_tok"] / cfg.get(
        "n_routed_experts_published", cfg["n_routed_experts"])
    total = 0.0
    for name, shape, _ in reference.param_specs(cfg):
        if name == "embed":
            continue
        size = math.prod(shape)
        total += size * reach if ".mlp.experts." in name else size
    return total


def flops_per_position(cfg, length):
    """6 per used parameter (forward and backward, recompute not counted)
    plus attention over the whole length as ``palm_flops_per_position``
    counts it, with latent attention's widths: QK^T over nope + rope, PV over
    v_head_dim, 3 x 2 x (Dqk + Dv) L a head."""
    heads = cfg["num_attention_heads"]
    widths = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + \
        cfg["v_head_dim"]
    return 6.0 * used_params(cfg) + \
        6.0 * cfg["num_hidden_layers"] * heads * widths * length


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
