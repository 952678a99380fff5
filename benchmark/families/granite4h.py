"""granite4h: ``paddle_tpu.models.nlp.ssm_hybrid.SSMHybrid`` under
``latent_moe_loss``: nine Mamba-2 state-space layers to one softmax layer
over grouped-query heads without positions, every layer a dense SwiGLU, the
four multipliers, the head tied to the embedding; the chip that holds one
pipeline stage's layers whole and an eighth of the tied matrix.

What ``benchmark/README.md``'s table would say of this family (a
``model_config`` PR edits no file the benchmark has): ``families/
granite4h.py`` builds the program's model from the source's own keys
(``shared_intermediate_size`` is the MLP's width; the config has no
``n_routed_experts``, so the four expert readers are not owed);
``reference/granite4h.py`` is its plain float32 reference. The model's one
buffer (``state_space_stats``) is not persistable, so the seeded weights
cover its whole state dict, the tied matrix once. A program without the
state-space model (any before PR 44) is refused as this module is imported,
before any weight is made: the run exits non-zero.

**A step's FLOPs** (``step_flops``; recompute not counted): 6 a parameter a
position's forward pass multiplies with (every leaf; the tied matrix once,
as the head: its other use is a lookup), plus the attention layer over its
query heads as ``families/joyai.py`` counts it (``3 x 2 x (Dqk + Dv) L`` a
head a position, the whole length for a causal model too), plus the
state-space layers' recurrence **as the token-by-token form has it**: a token
a head decays the state (P N), adds the outer product (2 P N) and reads it
with C (2 P N), 5 P N; three times that with the backward pass
(``benchmark/ssm_costs.py``, which the scan's roofline reads too). The
chunked form the program runs does other work, which is not what is counted.
"""
import importlib.util

from benchmark import ssm_costs
from benchmark.families import _recipe
from benchmark.reference import granite4h as reference

if importlib.util.find_spec("paddle_tpu.models.nlp.ssm_hybrid") is None:
    raise SystemExit("this program has no models.nlp.ssm_hybrid (Mamba-2 "
                     "state-space layers, a tied head, the four "
                     "multipliers): family granite4h cannot run")

valid_tokens = _recipe.full_rows

_MLP = {"mixer_norm.weight": "input_norm", "mlp_norm.weight": "post_attn_norm",
        "mlp.gate.weight": "mlp.gate", "mlp.up.weight": "mlp.up",
        "mlp.down.weight": "mlp.down"}
_MAMBA = {"mixer.in_proj.weight": "mixer.in_proj",
          "mixer.out_proj.weight": "mixer.out_proj",
          **{f"mixer.{k}": f"mixer.{k}" for k in (
              "conv", "conv_bias", "dt_bias", "A_log", "D", "norm")}}
_ATTENTION = {f"mixer.{k}.weight": f"attn.{k}" for k in ("q", "k", "v", "o")}


def name_map(cfg):
    """program's structured parameter name -> reference name."""
    out = {"embed.weight": "embed", "final_norm.weight": "norm"}
    for i in range(cfg["num_hidden_layers"]):
        names = {**_MLP, **(_MAMBA if reference.is_mamba(cfg, i)
                            else _ATTENTION)}
        out.update({f"blocks.{i}.{prog}": f"layers.{i}.{ref}"
                    for prog, ref in names.items()})
    return out


def program_config(cfg):
    from paddle_tpu.models.nlp.ssm_hybrid import SSMHybridConfig

    h, p, n, _, _ = reference.mamba_sizes(cfg)
    if cfg["hidden_act"] != "silu" or cfg["num_local_experts"] or \
            cfg["normalization_function"] != "rmsnorm" or \
            cfg["position_embedding_type"] != "nope" or \
            cfg["attention_bias"] or cfg["mamba_proj_bias"] or \
            not cfg["mamba_conv_bias"] or not cfg["tie_word_embeddings"]:
        raise ValueError("the program's SSMHybrid is silu and RMS norms, "
                         "dense in every layer, attention without positions "
                         "or bias, a biased convolution, a tied head")
    return SSMHybridConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], layer_types=cfg["layer_types"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        mlp_width=cfg["shared_intermediate_size"], ssm_heads=h,
        ssm_head_dim=p, ssm_state=n, conv_size=cfg["mamba_d_conv"],
        chunk=cfg["mamba_chunk_size"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"], rms_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        conv_initializer_range=cfg["conv_initializer_range"],
        **cfg.get("program", {}))


def build(cfg, weights, mesh_axes):
    """(model, step) with the seeded weights in place."""
    from paddle_tpu.models.nlp.latent_moe import latent_moe_loss
    from paddle_tpu.models.nlp.ssm_hybrid import SSMHybrid

    model = SSMHybrid(program_config(cfg))
    model.bfloat16()
    _recipe.load_weights(model, weights, name_map(cfg))
    return model, _recipe.train_step(model, latent_moe_loss, cfg["recipe"],
                                     mesh_axes)


def flops_per_position(cfg, length):
    """See the module's docstring."""
    heads = cfg["num_attention_heads"]
    attention = cfg["num_hidden_layers"] - ssm_costs.mamba_layers(cfg)
    return 6.0 * _recipe.n_params(reference.param_specs(cfg)) + \
        6.0 * attention * heads * 2 * (cfg["hidden_size"] // heads) * \
        length + ssm_costs.scan_flops_per_position(cfg)


step_flops = _recipe.token_rows_step_flops(flops_per_position)
