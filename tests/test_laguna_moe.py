"""``models.nlp.laguna_moe``: the tiny preset's layers as its lists say (kind,
heads, rotary table, window a layer; a dense layer first; a softmax router),
the one gated grouped-query sublayer it shares with ``hybrid_moe``, the model
with the windowed kernels in the interpreter against its dense path, its
program scopes on forward and backward instructions, and its gauges."""
import importlib
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import obs, optim
from paddle_tpu.models.nlp import decoder_stack as ds
from paddle_tpu.models.nlp import hybrid_moe as hm
from paddle_tpu.models.nlp import laguna_moe as lg
from paddle_tpu.models.nlp.latent_moe import latent_moe_loss
from paddle_tpu.ops import pallas as pk

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import scope_paths, scope_reduce  # noqa: E402

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def test_the_tiny_presets_layers_are_what_its_lists_say():
    pt.seed(0)
    cfg = lg.laguna_moe_tiny()
    model = lg.LagunaMoE(cfg)
    assert cfg.layer_types == (lg.FULL,) + (lg.SLIDING,) * 3 + (lg.FULL,)
    assert cfg.heads_per_layer == (4, 6, 6, 6, 4)
    shapes = {n: tuple(p.shape) for n, p in model.named_parameters()}
    for i, block in enumerate(model.blocks):
        h = cfg.heads_per_layer[i]
        assert block.attn.window == (8 if i in (1, 2, 3) else None)
        assert block.attn.rope == cfg.rope_of(cfg.layer_types[i])
        assert shapes[f"blocks.{i}.attn.q.weight"] == (64, h * 16)
        assert shapes[f"blocks.{i}.attn.k.weight"] == (64, 2 * 16)
        assert shapes[f"blocks.{i}.attn.gate.weight"] == (64, h)  # a head
        assert shapes[f"blocks.{i}.attn.o.weight"] == (h * 16, 64)
        assert block.dense is (i == 0)
    assert cfg.rope_of(lg.FULL)[0] == 8 and cfg.rope_of(lg.SLIDING)[0] == 16
    assert shapes["blocks.0.mlp.gate.weight"] == (64, 96)
    assert shapes["blocks.1.mlp.routed.router"] == (64, 8)
    assert model.blocks[1].mlp.routed.score == "softmax"
    assert tuple(model.expert_load.shape) == (8, 4, 8)   # 4 sparse layers
    logits = model(pt.to_tensor(np.zeros((2, 12), np.int32)))
    assert logits.shape == [2, 12, 256]
    with pytest.raises(ValueError, match="whole groups"):
        lg.laguna_moe_tiny(heads_per_layer=[4, 5, 6, 6, 4])
    with pytest.raises(ValueError, match="a kind"):
        lg.laguna_moe_tiny(layer_types=["full_attention"] * 4)


def test_one_gated_sublayer_serves_both_families():
    """``hybrid_moe``'s softmax layers and every layer here are one class:
    there a gate a channel, no positions, every key; here a gate a head, a
    rotary table, a window."""
    assert lg.GatedGroupedAttention is hm.GatedGroupedAttention is \
        ds.GatedGroupedAttention
    theirs = ds.GatedGroupedAttention(hm.hybrid_moe_tiny())
    assert (theirs.heads, theirs.kv_heads, theirs.head_gate, theirs.rope,
            theirs.window) == (4, 2, False, None, None)
    assert tuple(theirs.gate.weight.shape) == (64, 4 * 16)
    assert [n for n, _ in theirs.named_parameters()] == [
        f"{k}.weight" for k in ("q", "k", "v", "gate", "o")]
    ours = lg.LagunaMoE(lg.laguna_moe_tiny()).blocks[1].attn
    assert [n for n, _ in ours.named_parameters()] == [
        n for n, _ in theirs.named_parameters()]
    x = pt.to_tensor(np.random.default_rng(0).normal(
        size=(2, 12, 64)).astype(np.float32))
    y, gate = ours(x, with_gate=True)
    assert y.shape == [2, 12, 64] and gate.shape == [2, 12, 6]
    assert theirs(x).shape == [2, 12, 64]


@pytest.fixture(scope="module")
def trained():
    """Full, sliding, sliding at head_dim 64 over rows of 128 under a window
    of 32, the kernels in the interpreter: (dense logits, kernel logits,
    compiled text, losses, gauges)."""
    cfg = dict(layers=3, head_dim=64, window=32, experts_held=4,
               first_expert=2, use_recompute=True)
    ids = np.random.default_rng(0).integers(0, 256, (2, 129)).astype(np.int32)
    pt.seed(0)
    dense = lg.LagunaMoE(lg.laguna_moe_tiny(**cfg))(
        pt.to_tensor(ids[:, :-1])).numpy()
    floor, fa.MIN_STEP_SCORES = fa.MIN_STEP_SCORES, 128 * 128
    pk.set_enabled(True)
    try:
        pt.seed(0)
        model = lg.LagunaMoE(lg.laguna_moe_tiny(**cfg))
        kernels = model(pt.to_tensor(ids[:, :-1])).numpy()
    finally:
        pk.set_enabled(None)
        fa.MIN_STEP_SCORES = floor
    obs.enable_tracing()
    step = pt.TrainStep(model, optim.AdamW(
        parameters=model.parameters(), learning_rate=1e-3,
        grad_clip=optim.ClipGradByGlobalNorm(1.0)), latent_moe_loss)
    losses = [float(step(ids[:, :-1], ids[:, 1:]).numpy()) for _ in range(3)]
    snap = obs.snapshot()      # runs the model's publish_gauges
    return dense, kernels, step.compiled().as_text(), losses, snap, model


def test_the_windowed_kernels_give_the_dense_paths_logits(trained):
    dense, kernels = trained[:2]
    assert float(np.abs(dense).max()) > 0.1
    np.testing.assert_allclose(kernels, dense, atol=2e-5, rtol=1e-4)


def test_the_scopes_are_on_forward_and_backward_instructions(trained):
    text, losses = trained[2], trained[3]
    assert losses[-1] < losses[0]
    paths = set(scope_reduce._OP_NAME.findall(text))
    for scope in ("window_attn", "gqa_attn"):
        under = [p for p in paths if scope_paths.holds(p, scope)]
        ops = {name for p in under for name, _ in scope_reduce.scopes(p)}
        assert {"linear_nobias", "rotary", "sdpa", "sigmoid"} <= ops, scope
        assert any("transpose(" in p for p in under), scope
        assert any("transpose(" not in p for p in under), scope
    # the expert layer and the norms lie outside both
    assert not [p for p in paths if "moe_experts" in p and (
        scope_paths.holds(p, "window_attn") or
        scope_paths.holds(p, "gqa_attn"))]


def test_the_gauges(trained):
    snap, model = trained[4], trained[5]
    assert snap["attn.window"] == 32
    assert (snap["attn.window_layers"], snap["attn.full_layers"]) == (2, 1)
    assert snap["attn.window_pair_share"] == pytest.approx(
        (32 * 128 - 32 * 31 / 2) / (128 * 129 / 2))
    assert 0.45 < snap["attn.head_gate_mean"] < 0.55
    assert snap["moe.slots_held"] == model.expert_load_counts()[:, 2:6].sum()
    # the cell's: 512 of 8,192, and a window that reaches the row keeps all
    assert lg.window_pair_share(8192, 512) == pytest.approx(0.1211, abs=1e-4)
    assert lg.window_pair_share(256, 512) == 1.0
