"""joyai: ``paddle_tpu.models.nlp.latent_moe.LatentMoE`` with ONE residual
stream (the plain pre-norm residual) and its multi-token-prediction module,
under ``latent_moe_loss``: latent attention with an unscaled rotary
embedding, sigmoid-routed experts of which this chip holds a contiguous
share, MTP trained inside the step.

What ``benchmark/README.md``'s table would say of this family (a
``model_config`` PR edits no file the benchmark has): ``families/joyai.py``
builds the program's model from the source's own keys (``n_routed_experts``
is the experts HELD, ``n_routed_experts_published`` the router's width,
``first_routed_expert`` where the share starts; the config has no
``hc_mult``, so ``residual_mix_ms`` is not owed); ``reference/joyai.py`` is
its plain float32 reference. The model's buffers (``e_score_correction_bias``,
``expert_load``, ``loss_terms``) are not persistable, so the seeded weights
cover its whole state dict. A step's FLOPs count the MTP module: one more
block of attention, and the head a second time. A program whose
``LatentMoE(streams=1)`` still builds residual maps (any before PR 35) is
refused before the model is built: the run exits non-zero.
"""
import math
import weakref

from benchmark.families import _recipe, xing4
from benchmark.reference import joyai as reference

valid_tokens = _recipe.full_rows
_BUILT = None   # a weak reference to the model built last: readers ask it


def name_map(cfg):
    """program's structured parameter name -> reference name: the xing4
    family's (the same model class, the same reference names) without the
    residual maps' leaves, which one stream does not have."""
    return {prog: ref for prog, ref in xing4.name_map(cfg).items()
            if "_hc." not in prog}


def program_config(cfg):
    from paddle_tpu.models.nlp.latent_moe import LatentMoEConfig

    if cfg["hidden_act"] != "silu" or cfg["scoring_func"] != "sigmoid" or \
            cfg["topk_method"] != "noaux_tc" or cfg["n_group"] != 1 or \
            cfg["topk_group"] != 1 or cfg["attention_bias"] or \
            cfg["tie_word_embeddings"] or cfg["moe_layer_freq"] != 1 or \
            cfg["rope_scaling"] is not None or \
            cfg["num_nextn_predict_layers"] > 1:
        raise ValueError("the program's plain-residual LatentMoE is silu, "
                         "sigmoid noaux_tc routing without groups, untied, "
                         "an unscaled rotary embedding, MTP depth <= 1")
    return LatentMoEConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        dense_width=cfg["intermediate_size"],
        heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=cfg["rope_theta"], rope_scaling=None,
        experts=cfg.get("n_routed_experts_published",
                        cfg["n_routed_experts"]),
        expert_width=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"],
        first_expert=cfg.get("first_routed_expert", 0),
        experts_held=cfg["n_routed_experts"], streams=1,
        rms_eps=cfg["rms_norm_eps"],
        mtp_layers=cfg["num_nextn_predict_layers"],
        mtp_lambda=cfg.get("mtp_loss_weight", 0.3),
        initializer_range=cfg["initializer_range"],
        **cfg.get("program", {}))


def _require_plain_residual():
    """A program from before the plain residual builds one-stream maps at
    ``streams=1``, which the seeded weights do not cover: say so at once."""
    from paddle_tpu.models.nlp import latent_moe

    block = latent_moe.LatentMoEBlock(
        latent_moe.latent_moe_tiny(streams=1), dense=True)
    if any("_hc." in name for name, _ in block.named_parameters()):
        raise SystemExit("this program's LatentMoE(streams=1) is not the "
                         "plain pre-norm residual: family joyai cannot run")


def build(cfg, weights, mesh_axes):
    """(model, step) with the seeded weights in place."""
    from paddle_tpu.models.nlp.latent_moe import LatentMoE, latent_moe_loss

    global _BUILT
    _require_plain_residual()
    model = LatentMoE(program_config(cfg))
    _BUILT = weakref.ref(model)
    model.bfloat16()
    _recipe.load_weights(model, weights, name_map(cfg))
    return model, _recipe.train_step(model, latent_moe_loss, cfg["recipe"],
                                     mesh_axes)


def used_params(cfg):
    """Parameters a position's forward pass multiplies with: every leaf
    outside the routed experts and the token embedding (a lookup), the head
    once more where the MTP module reads its logits through it, and of each
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
        if name == "head" and cfg["num_nextn_predict_layers"]:
            size *= 2
        total += size * reach if ".mlp.experts." in name else size
    return total


def flops_per_position(cfg, length):
    """6 per used parameter (forward and backward, recompute not counted)
    plus attention over the whole length as ``palm_flops_per_position``
    counts it, with latent attention's widths (QK^T over nope + rope, PV over
    v_head_dim: 3 x 2 x (Dqk + Dv) L a head), in every layer and in the MTP
    module's block."""
    blocks = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    widths = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + \
        cfg["v_head_dim"]
    return 6.0 * used_params(cfg) + \
        6.0 * blocks * cfg["num_attention_heads"] * widths * length


step_flops = _recipe.token_rows_step_flops(flops_per_position)


def expert_load(steps):
    """(steps, expert layers, experts held) slots of the last ``steps`` steps
    of the model this module built last (the MTP block's row last), from the
    program's own counter (``LatentMoE.expert_load_counts``); None once that
    model is gone."""
    model = _BUILT() if _BUILT is not None else None
    if model is None:
        return None
    c = model.cfg
    return model.expert_load_counts(steps)[
        ..., c.first_expert:c.first_expert + c.experts_held]
