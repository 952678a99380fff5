"""laguna: ``paddle_tpu.models.nlp.laguna_moe.LagunaMoE`` under
``latent_moe_loss``: sliding-window attention layers over more query heads and
full-attention layers over fewer, both grouped-query under a gate a head, a
leading dense layer and softmax-routed experts beside a shared one, as the
chip that holds a share of each layer's experts.

What ``benchmark/README.md``'s table would say of this family (a
``model_config`` PR edits no file the benchmark has): ``families/laguna.py``
builds the program's model from the source's own keys. ``num_experts`` is the
experts HELD, ``num_experts_published`` the router's width,
``first_routed_expert`` where the share starts; the lists a layer
(``layer_types``, ``mlp_layer_types``, ``num_attention_heads_per_layer``) are
cut to the layers kept. ``reference/laguna.py`` is its plain float32
reference. The model's buffers (``e_score_correction_bias``, ``expert_load``,
``attn_stats``) are not persistable, so the seeded weights cover its whole
state dict. A program without the model (any before PR 42) is refused as this
module is imported, before any weight is made: the run exits non-zero.

**A step's FLOPs** (``step_flops``; recompute not counted): 6 a parameter a
position's forward pass multiplies with (every matrix outside the token
embedding; of the held experts the share a position reaches in expectation,
``num_experts_per_tok / num_experts_published`` each), plus attention as the
other families count it, ``3 x 2 x (Dqk + Dv) x keys`` a head a position with
the whole length for a causal layer's keys, and for a windowed layer the
window where that is shorter: no pair the band removes is counted.
"""
import importlib.util
import math
import weakref

from benchmark.families import _recipe
from benchmark.reference import laguna as reference

if importlib.util.find_spec("paddle_tpu.models.nlp.laguna_moe") is None:
    raise SystemExit("this program has no models.nlp.laguna_moe (sliding-"
                     "window layers, heads a layer, a gate a head, a softmax "
                     "router): family laguna cannot run")

valid_tokens = _recipe.full_rows
_BUILT = None   # a weak reference to the model built last: readers ask it

_ATTENTION = {f"attn.{k}.weight": f"attn.{k}"
              for k in ("q", "k", "v", "gate", "o")}
_DENSE = {f"mlp.{k}.weight": f"mlp.{k}" for k in ("gate", "up", "down")}
_EXPERTS = {"mlp.routed.router": "mlp.router",
            "mlp.routed.experts_gate": "mlp.experts.gate",
            "mlp.routed.experts_up": "mlp.experts.up",
            "mlp.routed.experts_down": "mlp.experts.down",
            "mlp.shared.gate.weight": "mlp.shared.gate",
            "mlp.shared.up.weight": "mlp.shared.up",
            "mlp.shared.down.weight": "mlp.shared.down"}


def _dense(cfg, i):
    return cfg["mlp_layer_types"][i] == "dense"


def name_map(cfg):
    """program's structured parameter name -> reference name."""
    out = {"embed.weight": "embed", "final_norm.weight": "norm",
           "head.weight": "head"}
    for i in range(cfg["num_hidden_layers"]):
        names = {"attn_norm.weight": "input_norm",
                 "mlp_norm.weight": "post_attn_norm", **_ATTENTION,
                 **(_DENSE if _dense(cfg, i) else _EXPERTS)}
        out.update({f"blocks.{i}.{prog}": f"layers.{i}.{ref}"
                    for prog, ref in names.items()})
    return out


def program_config(cfg):
    from paddle_tpu.models.nlp.laguna_moe import LagunaMoEConfig

    layers = cfg["num_hidden_layers"]
    dense = [_dense(cfg, i) for i in range(layers)]
    first_dense = sum(dense)
    if dense != [True] * first_dense + [False] * (layers - first_dense) or \
            cfg["gating"] != "per-head" or cfg["tie_word_embeddings"] or \
            cfg["attention_bias"] or cfg["moe_router_logit_softcapping"] or \
            cfg["moe_apply_router_weight_on_input"] or \
            cfg["shared_expert_intermediate_size"] % \
            cfg["moe_intermediate_size"]:
        raise ValueError("the program's LagunaMoE has its dense layers first, "
                         "a gate a head, no bias, an untied head, uncapped "
                         "router logits, weights on the experts' outputs")
    rope = {kind: dict(
        theta=float(r["rope_theta"]), partial=r["partial_rotary_factor"],
        **({"scaling": {k: r[k] for k in (
            "factor", "beta_fast", "beta_slow",
            "original_max_position_embeddings")},
            "attention_factor": r["attention_factor"]}
           if r["rope_type"] == "yarn" else {}))
        for kind, r in cfg["rope_parameters"].items()}
    return LagunaMoEConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=layers, layer_types=cfg["layer_types"],
        heads_per_layer=cfg["num_attention_heads_per_layer"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], rope=rope, first_dense=first_dense,
        dense_width=cfg["intermediate_size"],
        experts=cfg.get("num_experts_published", cfg["num_experts"]),
        expert_width=cfg["moe_intermediate_size"],
        shared_experts=cfg["shared_expert_intermediate_size"] //
        cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["moe_routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"],
        first_expert=cfg.get("first_routed_expert", 0),
        experts_held=cfg["num_experts"], rms_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        **cfg.get("program", {}))


def build(cfg, weights, mesh_axes):
    """(model, step) with the seeded weights in place."""
    from paddle_tpu.models.nlp.laguna_moe import LagunaMoE
    from paddle_tpu.models.nlp.latent_moe import latent_moe_loss

    global _BUILT
    model = LagunaMoE(program_config(cfg))
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
    reach = cfg["num_experts_per_tok"] / cfg.get("num_experts_published",
                                                 cfg["num_experts"])
    total = 0.0
    for name, shape, _ in reference.param_specs(cfg):
        if name != "embed":
            size = math.prod(shape)
            total += size * reach if ".mlp.experts." in name else size
    return total


def flops_per_position(cfg, length):
    """See the module's docstring."""
    keys = sum(heads * (min(length, cfg["sliding_window"])
                        if kind == reference.SLIDING else length)
               for kind, heads in zip(cfg["layer_types"],
                                      cfg["num_attention_heads_per_layer"]))
    return 6.0 * used_params(cfg) + 6.0 * 2 * cfg["head_dim"] * keys


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
