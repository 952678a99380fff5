"""lfm2: ``paddle_tpu.models.nlp.lfm2_moe.LFM2MoE`` under ``latent_moe_loss``:
double-gated short-convolution layers beside grouped-query attention layers
with an RMS norm on every head of q and k and a rotary embedding, a leading
dense SwiGLU and sigmoid-routed experts without a shared one, the head tied
to the embedding; the chip that holds a quarter of each layer's experts and a
quarter of the tied matrix.

What ``benchmark/README.md``'s table would say of this family (a
``model_config`` PR edits no file the benchmark has): ``families/lfm2.py``
builds the program's model from the source's own keys. ``num_experts`` is the
experts HELD, ``num_experts_published`` the router's width,
``first_routed_expert`` where the share starts; ``layer_types`` is cut to the
layers kept and ``num_dense_layers`` counts the leading dense ones among
them. ``reference/lfm2.py`` is its plain float32 reference. The model's
buffers (``e_score_correction_bias``, ``expert_load``) are not persistable,
so the seeded weights cover its whole state dict, the tied matrix once. A
program without the model (any before PR 48) is refused as this module is
imported, before any weight is made: the run exits non-zero.

**A step's FLOPs** (``step_flops``; recompute not counted): 6 a parameter a
position's forward pass multiplies with (every leaf; the tied matrix once, as
the head: its other use is a lookup; of the held experts the share a position
reaches in expectation, ``num_experts_per_tok / num_experts_published``
each), plus the attention layers over their query heads as the other families
count them, ``3 x 2 x (Dqk + Dv) L`` a head a position, the whole length for
a causal layer too. The convolution's own arithmetic (K taps and two gates a
channel) is under a thousandth of its projections' and is not counted.
"""
import importlib.util
import math
import weakref

from benchmark.families import _recipe
from benchmark.reference import lfm2 as reference

if importlib.util.find_spec("paddle_tpu.models.nlp.lfm2_moe") is None:
    raise SystemExit("this program has no models.nlp.lfm2_moe (gated short-"
                     "convolution layers, q/k norm, a tied head over routed "
                     "experts): family lfm2 cannot run")

valid_tokens = _recipe.full_rows
_BUILT = None   # a weak reference to the model built last: readers ask it

_CONV = {"op.in_proj.weight": "conv.in_proj", "op.conv": "conv.taps",
         "op.out_proj.weight": "conv.out_proj"}
_ATTENTION = {**{f"op.{k}.weight": f"attn.{k}" for k in ("q", "k", "v", "o")},
              "op.q_norm.weight": "attn.q_norm",
              "op.k_norm.weight": "attn.k_norm"}
_DENSE = {f"mlp.{k}.weight": f"mlp.{k}" for k in ("gate", "up", "down")}
_EXPERTS = {"mlp.routed.router": "mlp.router",
            "mlp.routed.experts_gate": "mlp.experts.gate",
            "mlp.routed.experts_up": "mlp.experts.up",
            "mlp.routed.experts_down": "mlp.experts.down"}


def name_map(cfg):
    """program's structured parameter name -> reference name."""
    out = {"embed.weight": "embed", "final_norm.weight": "norm"}
    for i in range(cfg["num_hidden_layers"]):
        names = {"op_norm.weight": "op_norm", "mlp_norm.weight": "ffn_norm",
                 **(_CONV if reference.is_conv(cfg, i) else _ATTENTION),
                 **(_DENSE if reference.is_dense(cfg, i) else _EXPERTS)}
        out.update({f"blocks.{i}.{prog}": f"layers.{i}.{ref}"
                    for prog, ref in names.items()})
    return out


def program_config(cfg):
    from paddle_tpu.models.nlp.lfm2_moe import LFM2MoEConfig

    if not cfg["tie_word_embeddings"] or cfg["conv_bias"] or \
            not cfg["use_expert_bias"]:
        raise ValueError("the program's LFM2MoE has a tied head, a "
                         "convolution without bias, a router with an expert "
                         "bias")
    return LFM2MoEConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], layer_types=cfg["layer_types"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=reference.head_dim(cfg), rope_theta=float(cfg["rope_theta"]),
        conv_size=cfg["conv_L_cache"], dense_layers=cfg["num_dense_layers"],
        dense_width=cfg["intermediate_size"],
        experts=cfg.get("num_experts_published", cfg["num_experts"]),
        expert_width=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"],
        first_expert=cfg.get("first_routed_expert", 0),
        experts_held=cfg["num_experts"], rms_eps=cfg["norm_eps"],
        initializer_range=cfg["initializer_range"],
        conv_initializer_range=cfg["conv_initializer_range"],
        **cfg.get("program", {}))


def build(cfg, weights, mesh_axes):
    """(model, step) with the seeded weights in place."""
    from paddle_tpu.models.nlp.latent_moe import latent_moe_loss
    from paddle_tpu.models.nlp.lfm2_moe import LFM2MoE

    global _BUILT
    model = LFM2MoE(program_config(cfg))
    _BUILT = weakref.ref(model)
    model.bfloat16()
    _recipe.load_weights(model, weights, name_map(cfg))
    return model, _recipe.train_step(model, latent_moe_loss, cfg["recipe"],
                                     mesh_axes)


def used_params(cfg):
    """Parameters a position's forward pass multiplies with: every leaf (the
    tied matrix once, as the head), of each layer's held experts the share a
    position reaches in expectation (``num_experts_per_tok`` slots spread
    evenly over the published experts reach each held one with probability
    k / E)."""
    reach = cfg["num_experts_per_tok"] / cfg.get("num_experts_published",
                                                 cfg["num_experts"])
    return sum(math.prod(shape) * (reach if ".mlp.experts." in name else 1.0)
               for name, shape, _ in reference.param_specs(cfg))


def flops_per_position(cfg, length):
    """See the module's docstring."""
    attention = sum(not reference.is_conv(cfg, i)
                    for i in range(cfg["num_hidden_layers"]))
    return 6.0 * used_params(cfg) + 6.0 * attention * \
        cfg["num_attention_heads"] * 2 * reference.head_dim(cfg) * length


step_flops = _recipe.token_rows_step_flops(flops_per_position)


def expert_load(steps):
    """(steps, expert layers, experts held) slots of the last ``steps`` steps
    of the model this module built last, from the program's own counter
    (``ExpertStack.expert_load_counts``); None once that model is gone."""
    model = _BUILT() if _BUILT is not None else None
    if model is None:
        return None
    c = model.cfg
    return model.expert_load_counts(steps)[
        ..., c.first_expert:c.first_expert + c.experts_held]
