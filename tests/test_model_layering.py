"""The model layer's seam (PR 47): the decoder families stand on
``models/nlp/decoder_stack.py`` and on no other family, build what they built
before the base had a file of its own, and ``dist/moe.py`` holds one expert
layer. No model is trained here."""
import ast
import os

import pytest

from paddle_tpu import distributed as dist
from paddle_tpu.framework.jit import _collect_state
from paddle_tpu.models.nlp import decoder_stack as ds, hybrid_moe as hm, \
    laguna_moe as lg, latent_moe as lm, lfm2_moe as lf, ssm_hybrid as sh
from paddle_tpu.ops._base import OP_REGISTRY

FAMILIES = ("latent_moe", "hybrid_moe", "laguna_moe", "ssm_hybrid",
            "lfm2_moe")


@pytest.mark.parametrize("module", FAMILIES + ("decoder_stack",))
def test_a_family_imports_no_other_family(module):
    """The base and ``nn/`` are what a family depends on: a new family edits
    no sibling, and the base knows none of them."""
    path = os.path.join(os.path.dirname(ds.__file__), module + ".py")
    with open(path) as f:
        tree = ast.parse(f.read())
    seen = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            seen |= set((node.module or "").split("."))
            seen |= {a.name for a in node.names}
        elif isinstance(node, ast.Import):
            seen |= {part for a in node.names for part in a.name.split(".")}
    assert not seen & (set(FAMILIES) - {module}), module
    if module != "decoder_stack":
        assert "decoder_stack" in seen


@pytest.mark.parametrize("config,model", [
    (hm.HybridMoEConfig, hm.HybridMoE), (lg.LagunaMoEConfig, lg.LagunaMoE),
    (lf.LFM2MoEConfig, lf.LFM2MoE),
], ids=["hybrid_moe", "laguna_moe", "lfm2_moe"])
def test_a_plain_expert_family_carries_no_stand_in(config, model):
    """Neither a multi-stream residual nor an MTP module to answer for."""
    assert not hasattr(config, "streams") and not hasattr(config, "mtp_layers")
    assert issubclass(model, ds.ExpertStack)
    assert not issubclass(model, lm.LatentMoE)


def test_a_stack_without_experts_has_no_expert_base():
    assert issubclass(lm.LatentMoE, ds.ExpertStack)
    assert issubclass(sh.SSMHybrid, ds.DecoderStack) and \
        not issubclass(sh.SSMHybrid, ds.ExpertStack)
    assert not hasattr(sh.SSMHybrid(sh.ssm_hybrid_tiny()), "expert_load")


@pytest.mark.parametrize("name", ["MoEMLP", "top2_gating",
                                  "moe_dispatch_combine"])
def test_the_capacity_based_layer_is_gone(name):
    assert not hasattr(dist.moe, name) and not hasattr(dist, name)
    assert name not in dist.moe.__all__


def test_one_expert_layer_is_registered():
    assert "moe_mlp" not in OP_REGISTRY
    assert {"moe_route", "moe_plan", "moe_dispatch", "moe_experts",
            "moe_combine", "moe_held"} <= set(OP_REGISTRY)


# ---- what a model builds ---------------------------------------------------------
PRESETS = {
    "latent_moe": lambda: lm.LatentMoE(lm.latent_moe_tiny()),
    "latent_moe_plain_mtp": lambda: lm.LatentMoE(
        lm.latent_moe_tiny(streams=1, mtp_layers=1)),
    "hybrid_moe": lambda: hm.HybridMoE(hm.hybrid_moe_tiny()),
    "laguna_moe": lambda: lg.LagunaMoE(lg.laguna_moe_tiny()),
    "ssm_hybrid": lambda: sh.SSMHybrid(sh.ssm_hybrid_tiny()),
    "lfm2_moe": lambda: lf.LFM2MoE(lf.lfm2_moe_tiny()),
}

# Parameters (P) and buffers (B) in ``TrainStep``'s collection order, as the
# commit before this file built them (9722eae; ``lfm2_moe`` as PR 48 brought
# it: a tied head over experts builds no ``head.weight``): name, shape, dtype.
# The order is the order of the compiled step's arguments.
STATE = {
    "latent_moe": """
P embed.weight 256x64 float32
P blocks.0.attn_hc.phi 256x24 float32
P blocks.0.attn_hc.alpha 3 float32
P blocks.0.attn_hc.bias 24 float32
P blocks.0.attn_norm.weight 64 float32
P blocks.0.attn.q_a.weight 64x32 float32
P blocks.0.attn.q_norm.weight 32 float32
P blocks.0.attn.q_b.weight 32x48 float32
P blocks.0.attn.kv_a.weight 64x24 float32
P blocks.0.attn.kv_norm.weight 16 float32
P blocks.0.attn.kv_b.weight 16x64 float32
P blocks.0.attn.o.weight 32x64 float32
P blocks.0.mlp_hc.phi 256x24 float32
P blocks.0.mlp_hc.alpha 3 float32
P blocks.0.mlp_hc.bias 24 float32
P blocks.0.mlp_norm.weight 64 float32
P blocks.0.mlp.gate.weight 64x96 float32
P blocks.0.mlp.up.weight 64x96 float32
P blocks.0.mlp.down.weight 96x64 float32
P blocks.1.attn_hc.phi 256x24 float32
P blocks.1.attn_hc.alpha 3 float32
P blocks.1.attn_hc.bias 24 float32
P blocks.1.attn_norm.weight 64 float32
P blocks.1.attn.q_a.weight 64x32 float32
P blocks.1.attn.q_norm.weight 32 float32
P blocks.1.attn.q_b.weight 32x48 float32
P blocks.1.attn.kv_a.weight 64x24 float32
P blocks.1.attn.kv_norm.weight 16 float32
P blocks.1.attn.kv_b.weight 16x64 float32
P blocks.1.attn.o.weight 32x64 float32
P blocks.1.mlp_hc.phi 256x24 float32
P blocks.1.mlp_hc.alpha 3 float32
P blocks.1.mlp_hc.bias 24 float32
P blocks.1.mlp_norm.weight 64 float32
P blocks.1.mlp.shared.gate.weight 64x32 float32
P blocks.1.mlp.shared.up.weight 64x32 float32
P blocks.1.mlp.shared.down.weight 32x64 float32
P blocks.1.mlp.routed.router 64x8 float32
P blocks.1.mlp.routed.experts_gate 8x64x32 float32
P blocks.1.mlp.routed.experts_up 8x64x32 float32
P blocks.1.mlp.routed.experts_down 8x32x64 float32
P blocks.2.attn_hc.phi 256x24 float32
P blocks.2.attn_hc.alpha 3 float32
P blocks.2.attn_hc.bias 24 float32
P blocks.2.attn_norm.weight 64 float32
P blocks.2.attn.q_a.weight 64x32 float32
P blocks.2.attn.q_norm.weight 32 float32
P blocks.2.attn.q_b.weight 32x48 float32
P blocks.2.attn.kv_a.weight 64x24 float32
P blocks.2.attn.kv_norm.weight 16 float32
P blocks.2.attn.kv_b.weight 16x64 float32
P blocks.2.attn.o.weight 32x64 float32
P blocks.2.mlp_hc.phi 256x24 float32
P blocks.2.mlp_hc.alpha 3 float32
P blocks.2.mlp_hc.bias 24 float32
P blocks.2.mlp_norm.weight 64 float32
P blocks.2.mlp.shared.gate.weight 64x32 float32
P blocks.2.mlp.shared.up.weight 64x32 float32
P blocks.2.mlp.shared.down.weight 32x64 float32
P blocks.2.mlp.routed.router 64x8 float32
P blocks.2.mlp.routed.experts_gate 8x64x32 float32
P blocks.2.mlp.routed.experts_up 8x64x32 float32
P blocks.2.mlp.routed.experts_down 8x32x64 float32
P final_norm.weight 64 float32
P head.weight 64x256 float32
B expert_load 8x2x8 int32
B blocks.1.mlp.routed.e_score_correction_bias 8 float32
B blocks.2.mlp.routed.e_score_correction_bias 8 float32
""",
    "latent_moe_plain_mtp": """
P embed.weight 256x64 float32
P blocks.0.attn_norm.weight 64 float32
P blocks.0.attn.q_a.weight 64x32 float32
P blocks.0.attn.q_norm.weight 32 float32
P blocks.0.attn.q_b.weight 32x48 float32
P blocks.0.attn.kv_a.weight 64x24 float32
P blocks.0.attn.kv_norm.weight 16 float32
P blocks.0.attn.kv_b.weight 16x64 float32
P blocks.0.attn.o.weight 32x64 float32
P blocks.0.mlp_norm.weight 64 float32
P blocks.0.mlp.gate.weight 64x96 float32
P blocks.0.mlp.up.weight 64x96 float32
P blocks.0.mlp.down.weight 96x64 float32
P blocks.1.attn_norm.weight 64 float32
P blocks.1.attn.q_a.weight 64x32 float32
P blocks.1.attn.q_norm.weight 32 float32
P blocks.1.attn.q_b.weight 32x48 float32
P blocks.1.attn.kv_a.weight 64x24 float32
P blocks.1.attn.kv_norm.weight 16 float32
P blocks.1.attn.kv_b.weight 16x64 float32
P blocks.1.attn.o.weight 32x64 float32
P blocks.1.mlp_norm.weight 64 float32
P blocks.1.mlp.shared.gate.weight 64x32 float32
P blocks.1.mlp.shared.up.weight 64x32 float32
P blocks.1.mlp.shared.down.weight 32x64 float32
P blocks.1.mlp.routed.router 64x8 float32
P blocks.1.mlp.routed.experts_gate 8x64x32 float32
P blocks.1.mlp.routed.experts_up 8x64x32 float32
P blocks.1.mlp.routed.experts_down 8x32x64 float32
P blocks.2.attn_norm.weight 64 float32
P blocks.2.attn.q_a.weight 64x32 float32
P blocks.2.attn.q_norm.weight 32 float32
P blocks.2.attn.q_b.weight 32x48 float32
P blocks.2.attn.kv_a.weight 64x24 float32
P blocks.2.attn.kv_norm.weight 16 float32
P blocks.2.attn.kv_b.weight 16x64 float32
P blocks.2.attn.o.weight 32x64 float32
P blocks.2.mlp_norm.weight 64 float32
P blocks.2.mlp.shared.gate.weight 64x32 float32
P blocks.2.mlp.shared.up.weight 64x32 float32
P blocks.2.mlp.shared.down.weight 32x64 float32
P blocks.2.mlp.routed.router 64x8 float32
P blocks.2.mlp.routed.experts_gate 8x64x32 float32
P blocks.2.mlp.routed.experts_up 8x64x32 float32
P blocks.2.mlp.routed.experts_down 8x32x64 float32
P final_norm.weight 64 float32
P head.weight 64x256 float32
P mtp.hnorm.weight 64 float32
P mtp.enorm.weight 64 float32
P mtp.proj.weight 128x64 float32
P mtp.block.attn_norm.weight 64 float32
P mtp.block.attn.q_a.weight 64x32 float32
P mtp.block.attn.q_norm.weight 32 float32
P mtp.block.attn.q_b.weight 32x48 float32
P mtp.block.attn.kv_a.weight 64x24 float32
P mtp.block.attn.kv_norm.weight 16 float32
P mtp.block.attn.kv_b.weight 16x64 float32
P mtp.block.attn.o.weight 32x64 float32
P mtp.block.mlp_norm.weight 64 float32
P mtp.block.mlp.shared.gate.weight 64x32 float32
P mtp.block.mlp.shared.up.weight 64x32 float32
P mtp.block.mlp.shared.down.weight 32x64 float32
P mtp.block.mlp.routed.router 64x8 float32
P mtp.block.mlp.routed.experts_gate 8x64x32 float32
P mtp.block.mlp.routed.experts_up 8x64x32 float32
P mtp.block.mlp.routed.experts_down 8x32x64 float32
B expert_load 8x3x8 int32
B loss_terms 2 float32
B blocks.1.mlp.routed.e_score_correction_bias 8 float32
B blocks.2.mlp.routed.e_score_correction_bias 8 float32
B mtp.block.mlp.routed.e_score_correction_bias 8 float32
""",
    "hybrid_moe": """
P embed.weight 256x64 float32
P blocks.0.attn_norm.weight 64 float32
P blocks.0.attn.q.weight 64x64 float32
P blocks.0.attn.k.weight 64x32 float32
P blocks.0.attn.v.weight 64x32 float32
P blocks.0.attn.gate.weight 64x64 float32
P blocks.0.attn.o.weight 64x64 float32
P blocks.0.mlp_norm.weight 64 float32
P blocks.0.mlp.shared.gate.weight 64x32 float32
P blocks.0.mlp.shared.up.weight 64x32 float32
P blocks.0.mlp.shared.down.weight 32x64 float32
P blocks.0.mlp.routed.router 64x8 float32
P blocks.0.mlp.routed.experts_gate 8x64x32 float32
P blocks.0.mlp.routed.experts_up 8x64x32 float32
P blocks.0.mlp.routed.experts_down 8x32x64 float32
P blocks.1.attn_norm.weight 64 float32
P blocks.1.attn.q_conv 4x64 float32
P blocks.1.attn.k_conv 4x64 float32
P blocks.1.attn.v_conv 4x64 float32
P blocks.1.attn.A_log 4 float32
P blocks.1.attn.dt_bias 64 float32
P blocks.1.attn.o_norm 16 float32
P blocks.1.attn.q.weight 64x64 float32
P blocks.1.attn.k.weight 64x64 float32
P blocks.1.attn.v.weight 64x64 float32
P blocks.1.attn.f_a.weight 64x16 float32
P blocks.1.attn.f_b.weight 16x64 float32
P blocks.1.attn.beta.weight 64x4 float32
P blocks.1.attn.g_a.weight 64x16 float32
P blocks.1.attn.g_b.weight 16x64 float32
P blocks.1.attn.g_b.bias 64 float32
P blocks.1.attn.o.weight 64x64 float32
P blocks.1.mlp_norm.weight 64 float32
P blocks.1.mlp.shared.gate.weight 64x32 float32
P blocks.1.mlp.shared.up.weight 64x32 float32
P blocks.1.mlp.shared.down.weight 32x64 float32
P blocks.1.mlp.routed.router 64x8 float32
P blocks.1.mlp.routed.experts_gate 8x64x32 float32
P blocks.1.mlp.routed.experts_up 8x64x32 float32
P blocks.1.mlp.routed.experts_down 8x32x64 float32
P blocks.2.attn_norm.weight 64 float32
P blocks.2.attn.q_conv 4x64 float32
P blocks.2.attn.k_conv 4x64 float32
P blocks.2.attn.v_conv 4x64 float32
P blocks.2.attn.A_log 4 float32
P blocks.2.attn.dt_bias 64 float32
P blocks.2.attn.o_norm 16 float32
P blocks.2.attn.q.weight 64x64 float32
P blocks.2.attn.k.weight 64x64 float32
P blocks.2.attn.v.weight 64x64 float32
P blocks.2.attn.f_a.weight 64x16 float32
P blocks.2.attn.f_b.weight 16x64 float32
P blocks.2.attn.beta.weight 64x4 float32
P blocks.2.attn.g_a.weight 64x16 float32
P blocks.2.attn.g_b.weight 16x64 float32
P blocks.2.attn.g_b.bias 64 float32
P blocks.2.attn.o.weight 64x64 float32
P blocks.2.mlp_norm.weight 64 float32
P blocks.2.mlp.shared.gate.weight 64x32 float32
P blocks.2.mlp.shared.up.weight 64x32 float32
P blocks.2.mlp.shared.down.weight 32x64 float32
P blocks.2.mlp.routed.router 64x8 float32
P blocks.2.mlp.routed.experts_gate 8x64x32 float32
P blocks.2.mlp.routed.experts_up 8x64x32 float32
P blocks.2.mlp.routed.experts_down 8x32x64 float32
P blocks.3.attn_norm.weight 64 float32
P blocks.3.attn.q_conv 4x64 float32
P blocks.3.attn.k_conv 4x64 float32
P blocks.3.attn.v_conv 4x64 float32
P blocks.3.attn.A_log 4 float32
P blocks.3.attn.dt_bias 64 float32
P blocks.3.attn.o_norm 16 float32
P blocks.3.attn.q.weight 64x64 float32
P blocks.3.attn.k.weight 64x64 float32
P blocks.3.attn.v.weight 64x64 float32
P blocks.3.attn.f_a.weight 64x16 float32
P blocks.3.attn.f_b.weight 16x64 float32
P blocks.3.attn.beta.weight 64x4 float32
P blocks.3.attn.g_a.weight 64x16 float32
P blocks.3.attn.g_b.weight 16x64 float32
P blocks.3.attn.g_b.bias 64 float32
P blocks.3.attn.o.weight 64x64 float32
P blocks.3.mlp_norm.weight 64 float32
P blocks.3.mlp.shared.gate.weight 64x32 float32
P blocks.3.mlp.shared.up.weight 64x32 float32
P blocks.3.mlp.shared.down.weight 32x64 float32
P blocks.3.mlp.routed.router 64x8 float32
P blocks.3.mlp.routed.experts_gate 8x64x32 float32
P blocks.3.mlp.routed.experts_up 8x64x32 float32
P blocks.3.mlp.routed.experts_down 8x32x64 float32
P final_norm.weight 64 float32
P head.weight 64x256 float32
B expert_load 8x4x8 int32
B linear_attn_stats 2 float32
B blocks.0.mlp.routed.e_score_correction_bias 8 float32
B blocks.1.mlp.routed.e_score_correction_bias 8 float32
B blocks.2.mlp.routed.e_score_correction_bias 8 float32
B blocks.3.mlp.routed.e_score_correction_bias 8 float32
""",
    "laguna_moe": """
P embed.weight 256x64 float32
P blocks.0.attn_norm.weight 64 float32
P blocks.0.attn.q.weight 64x64 float32
P blocks.0.attn.k.weight 64x32 float32
P blocks.0.attn.v.weight 64x32 float32
P blocks.0.attn.gate.weight 64x4 float32
P blocks.0.attn.o.weight 64x64 float32
P blocks.0.mlp_norm.weight 64 float32
P blocks.0.mlp.gate.weight 64x96 float32
P blocks.0.mlp.up.weight 64x96 float32
P blocks.0.mlp.down.weight 96x64 float32
P blocks.1.attn_norm.weight 64 float32
P blocks.1.attn.q.weight 64x96 float32
P blocks.1.attn.k.weight 64x32 float32
P blocks.1.attn.v.weight 64x32 float32
P blocks.1.attn.gate.weight 64x6 float32
P blocks.1.attn.o.weight 96x64 float32
P blocks.1.mlp_norm.weight 64 float32
P blocks.1.mlp.shared.gate.weight 64x32 float32
P blocks.1.mlp.shared.up.weight 64x32 float32
P blocks.1.mlp.shared.down.weight 32x64 float32
P blocks.1.mlp.routed.router 64x8 float32
P blocks.1.mlp.routed.experts_gate 8x64x32 float32
P blocks.1.mlp.routed.experts_up 8x64x32 float32
P blocks.1.mlp.routed.experts_down 8x32x64 float32
P blocks.2.attn_norm.weight 64 float32
P blocks.2.attn.q.weight 64x96 float32
P blocks.2.attn.k.weight 64x32 float32
P blocks.2.attn.v.weight 64x32 float32
P blocks.2.attn.gate.weight 64x6 float32
P blocks.2.attn.o.weight 96x64 float32
P blocks.2.mlp_norm.weight 64 float32
P blocks.2.mlp.shared.gate.weight 64x32 float32
P blocks.2.mlp.shared.up.weight 64x32 float32
P blocks.2.mlp.shared.down.weight 32x64 float32
P blocks.2.mlp.routed.router 64x8 float32
P blocks.2.mlp.routed.experts_gate 8x64x32 float32
P blocks.2.mlp.routed.experts_up 8x64x32 float32
P blocks.2.mlp.routed.experts_down 8x32x64 float32
P blocks.3.attn_norm.weight 64 float32
P blocks.3.attn.q.weight 64x96 float32
P blocks.3.attn.k.weight 64x32 float32
P blocks.3.attn.v.weight 64x32 float32
P blocks.3.attn.gate.weight 64x6 float32
P blocks.3.attn.o.weight 96x64 float32
P blocks.3.mlp_norm.weight 64 float32
P blocks.3.mlp.shared.gate.weight 64x32 float32
P blocks.3.mlp.shared.up.weight 64x32 float32
P blocks.3.mlp.shared.down.weight 32x64 float32
P blocks.3.mlp.routed.router 64x8 float32
P blocks.3.mlp.routed.experts_gate 8x64x32 float32
P blocks.3.mlp.routed.experts_up 8x64x32 float32
P blocks.3.mlp.routed.experts_down 8x32x64 float32
P blocks.4.attn_norm.weight 64 float32
P blocks.4.attn.q.weight 64x64 float32
P blocks.4.attn.k.weight 64x32 float32
P blocks.4.attn.v.weight 64x32 float32
P blocks.4.attn.gate.weight 64x4 float32
P blocks.4.attn.o.weight 64x64 float32
P blocks.4.mlp_norm.weight 64 float32
P blocks.4.mlp.shared.gate.weight 64x32 float32
P blocks.4.mlp.shared.up.weight 64x32 float32
P blocks.4.mlp.shared.down.weight 32x64 float32
P blocks.4.mlp.routed.router 64x8 float32
P blocks.4.mlp.routed.experts_gate 8x64x32 float32
P blocks.4.mlp.routed.experts_up 8x64x32 float32
P blocks.4.mlp.routed.experts_down 8x32x64 float32
P final_norm.weight 64 float32
P head.weight 64x256 float32
B expert_load 8x4x8 int32
B attn_stats 2 float32
B blocks.1.mlp.routed.e_score_correction_bias 8 float32
B blocks.2.mlp.routed.e_score_correction_bias 8 float32
B blocks.3.mlp.routed.e_score_correction_bias 8 float32
B blocks.4.mlp.routed.e_score_correction_bias 8 float32
""",
    "ssm_hybrid": """
P embed.weight 256x64 float32
P blocks.0.mixer_norm.weight 64 float32
P blocks.0.mixer.conv 4x80 float32
P blocks.0.mixer.conv_bias 80 float32
P blocks.0.mixer.dt_bias 4 float32
P blocks.0.mixer.A_log 4 float32
P blocks.0.mixer.D 4 float32
P blocks.0.mixer.norm 64 float32
P blocks.0.mixer.in_proj.weight 64x148 float32
P blocks.0.mixer.out_proj.weight 64x64 float32
P blocks.0.mlp_norm.weight 64 float32
P blocks.0.mlp.gate.weight 64x96 float32
P blocks.0.mlp.up.weight 64x96 float32
P blocks.0.mlp.down.weight 96x64 float32
P blocks.1.mixer_norm.weight 64 float32
P blocks.1.mixer.q.weight 64x64 float32
P blocks.1.mixer.k.weight 64x32 float32
P blocks.1.mixer.v.weight 64x32 float32
P blocks.1.mixer.o.weight 64x64 float32
P blocks.1.mlp_norm.weight 64 float32
P blocks.1.mlp.gate.weight 64x96 float32
P blocks.1.mlp.up.weight 64x96 float32
P blocks.1.mlp.down.weight 96x64 float32
P blocks.2.mixer_norm.weight 64 float32
P blocks.2.mixer.conv 4x80 float32
P blocks.2.mixer.conv_bias 80 float32
P blocks.2.mixer.dt_bias 4 float32
P blocks.2.mixer.A_log 4 float32
P blocks.2.mixer.D 4 float32
P blocks.2.mixer.norm 64 float32
P blocks.2.mixer.in_proj.weight 64x148 float32
P blocks.2.mixer.out_proj.weight 64x64 float32
P blocks.2.mlp_norm.weight 64 float32
P blocks.2.mlp.gate.weight 64x96 float32
P blocks.2.mlp.up.weight 64x96 float32
P blocks.2.mlp.down.weight 96x64 float32
P final_norm.weight 64 float32
B state_space_stats 2 float32
""",
    "lfm2_moe": """
P embed.weight 256x64 float32
P blocks.0.op_norm.weight 64 float32
P blocks.0.op.conv 3x64 float32
P blocks.0.op.in_proj.weight 64x192 float32
P blocks.0.op.out_proj.weight 64x64 float32
P blocks.0.mlp_norm.weight 64 float32
P blocks.0.mlp.gate.weight 64x96 float32
P blocks.0.mlp.up.weight 64x96 float32
P blocks.0.mlp.down.weight 96x64 float32
P blocks.1.op_norm.weight 64 float32
P blocks.1.op.q.weight 64x64 float32
P blocks.1.op.k.weight 64x32 float32
P blocks.1.op.v.weight 64x32 float32
P blocks.1.op.o.weight 64x64 float32
P blocks.1.op.q_norm.weight 16 float32
P blocks.1.op.k_norm.weight 16 float32
P blocks.1.mlp_norm.weight 64 float32
P blocks.1.mlp.routed.router 64x8 float32
P blocks.1.mlp.routed.experts_gate 8x64x32 float32
P blocks.1.mlp.routed.experts_up 8x64x32 float32
P blocks.1.mlp.routed.experts_down 8x32x64 float32
P blocks.2.op_norm.weight 64 float32
P blocks.2.op.conv 3x64 float32
P blocks.2.op.in_proj.weight 64x192 float32
P blocks.2.op.out_proj.weight 64x64 float32
P blocks.2.mlp_norm.weight 64 float32
P blocks.2.mlp.routed.router 64x8 float32
P blocks.2.mlp.routed.experts_gate 8x64x32 float32
P blocks.2.mlp.routed.experts_up 8x64x32 float32
P blocks.2.mlp.routed.experts_down 8x32x64 float32
P blocks.3.op_norm.weight 64 float32
P blocks.3.op.conv 3x64 float32
P blocks.3.op.in_proj.weight 64x192 float32
P blocks.3.op.out_proj.weight 64x64 float32
P blocks.3.mlp_norm.weight 64 float32
P blocks.3.mlp.routed.router 64x8 float32
P blocks.3.mlp.routed.experts_gate 8x64x32 float32
P blocks.3.mlp.routed.experts_up 8x64x32 float32
P blocks.3.mlp.routed.experts_down 8x32x64 float32
P final_norm.weight 64 float32
B expert_load 8x3x8 int32
B blocks.1.mlp.routed.e_score_correction_bias 8 float32
B blocks.2.mlp.routed.e_score_correction_bias 8 float32
B blocks.3.mlp.routed.e_score_correction_bias 8 float32
""",
}


@pytest.mark.parametrize("preset", list(PRESETS))
def test_a_preset_builds_what_it_built(preset):
    model = PRESETS[preset]()
    by_id = {id(t): n for n, t in list(model.named_parameters()) +
             list(model.named_buffers())}
    params, buffers = _collect_state([model])
    got = [f"{kind} {by_id[id(t)]} "
           f"{'x'.join(str(d) for d in t.shape) or '-'} {t._data.dtype}"
           for kind, ts in (("P", params), ("B", buffers)) for t in ts]
    assert got == STATE[preset].split("\n")[1:-1]
