"""The ops and layers the latent-attention / routed-expert / multi-stream
decoder brought, each at a small size against the plain reference
(``benchmark/reference/xing4.py``, which imports nothing of the program):
RMS norm, YaRN rotary, SwiGLU, latent attention on the dense path and through
the flash kernels in the interpreter at unequal head widths, Sinkhorn and the
residual block, the multi-token-prediction loss, the load counter."""
import importlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as pt  # noqa: E402
from benchmark.reference import _common as rc  # noqa: E402
from benchmark.reference import xing4 as ref  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.models.nlp import latent_moe as lm  # noqa: E402
from paddle_tpu.nn import functional as F  # noqa: E402
from paddle_tpu.ops import pallas as pk  # noqa: E402

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
MM = rc.matmul_of("float32")
YARN = {"type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16}


def ref_cfg(**kw):
    """The reference's configuration (the source's keys) at a small size."""
    cfg = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_hidden_layers=2, first_k_dense_replace=1,
               num_attention_heads=2, q_lora_rank=32, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
               norm_topk_prob=True, routed_scaling_factor=2.0,
               vocab_size=256, num_nextn_predict_layers=0, hc_mult=4,
               hc_sinkhorn_iters=6, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
               mhc_h_res_clamp_max=30, rms_norm_eps=1e-6, rope_theta=10000,
               rope_scaling=dict(YARN), initializer_range=0.02,
               hc_alpha_init=0.5, hc_res_init=4.0)
    cfg.update(kw)
    return cfg


def prog_cfg(cfg, **kw):
    return lm.LatentMoEConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        dense_width=cfg["intermediate_size"],
        heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=cfg["rope_theta"], rope_scaling=cfg["rope_scaling"],
        experts=cfg["n_routed_experts"],
        expert_width=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"], streams=cfg["hc_mult"],
        sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_alpha_init=cfg["hc_alpha_init"], hc_res_init=cfg["hc_res_init"],
        mtp_layers=cfg["num_nextn_predict_layers"], **kw)


def tensor(a, grad=False):
    return Tensor(jnp.asarray(a), stop_gradient=not grad, _internal=True)


def rand(seed, *shape, scale=1.0):
    return (np.random.default_rng(seed).normal(size=shape) * scale) \
        .astype(np.float32)


@pytest.fixture
def kernels_in_the_interpreter(monkeypatch):
    pk.set_enabled(True)
    # the route's floor keeps L=128 dense on the chip; here the kernels are
    # the thing under test and the interpreter is slow, so L stays 128
    monkeypatch.setattr(fa, "MIN_STEP_SCORES", 128 * 128)
    yield
    pk.set_enabled(None)


# ---- the small ops -------------------------------------------------------------
def test_rms_norm_against_the_reference():
    x, w = rand(0, 3, 5, 32), 1.0 + rand(1, 32, scale=0.1)
    got = F.rms_norm(tensor(x), tensor(w), 1e-6).numpy()
    np.testing.assert_allclose(got, ref.rms(x, 1e-6, w), rtol=2e-6, atol=2e-6)
    bare = F.rms_norm(tensor(x), None, 1e-6).numpy()
    np.testing.assert_allclose(bare, ref.rms(x, 1e-6), rtol=2e-6, atol=2e-6)
    layer = pt.nn.RMSNorm(32)
    layer.weight.set_value(w)
    np.testing.assert_allclose(layer(tensor(x)).numpy(), got)
    # statistics in float32 whatever the input's type
    low = F.rms_norm(tensor(x.astype(jnp.bfloat16)), tensor(w)).numpy()
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(low.astype(np.float32), got, atol=0.05)


@pytest.mark.parametrize("scaling", [None, YARN, dict(YARN, factor=64,
                         original_max_position_embeddings=4096)],
                         ids=["plain", "yarn4", "yarn64"])
def test_rotary_tables_and_rotation_against_the_reference(scaling):
    dim, length = 64, 48
    cos, sin = F.rotary_cos_sin(length, dim, 10000, scaling)
    if scaling:
        cfg = ref_cfg(qk_rope_head_dim=dim, rope_scaling=dict(scaling))
        want_cos, want_sin = ref.rope_tables(cfg, length)
        np.testing.assert_allclose(cos, want_cos, atol=1e-6)
        np.testing.assert_allclose(sin, want_sin, atol=1e-6)
        # fast dims keep their frequency, slow ones are divided by the factor
        plain = F.yarn_inv_freq(dim, 10000)
        got = F.yarn_inv_freq(dim, 10000, scaling)
        assert got[0] == plain[0]
        assert got[-1] == pytest.approx(plain[-1] / scaling["factor"])
    else:
        np.testing.assert_allclose(cos[:, 0], np.cos(np.arange(length)),
                                   atol=1e-5)
    x = rand(2, 2, 3, length, dim)
    got = F.rotary(tensor(x), cos, sin).numpy()
    np.testing.assert_allclose(got, ref.rotate(x, cos, sin), atol=1e-6)
    # a rotation: norms are kept (mscale / mscale_all_dim is 1 here)
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_yarn_attention_temperature():
    assert F.yarn_mscale(1) == 1.0
    assert F.yarn_mscale(64, 1) == pytest.approx(1.4159, abs=1e-4)
    cfg = lm.LatentMoEConfig(rope_scaling=dict(YARN, factor=64))
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2,
                                              rel=1e-4)


def test_swiglu_layer_against_the_reference():
    layer = pt.nn.SwiGLU(16, 24)
    x = rand(3, 5, 16)
    w = [rand(4 + i, *p.shape, scale=0.3)
         for i, p in enumerate((layer.gate.weight, layer.up.weight,
                                layer.down.weight))]
    for p, v in zip((layer.gate.weight, layer.up.weight, layer.down.weight),
                    w):
        p.set_value(v)
    np.testing.assert_allclose(layer(tensor(x)).numpy(),
                               ref.swiglu(x, *w, MM), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        F.swiglu(tensor(x), tensor(2 * x)).numpy(),
        jax.nn.silu(x) * 2 * x, rtol=1e-6)


# ---- latent attention -----------------------------------------------------------
def _attention_pair(cfg, seed, length):
    pt.seed(seed)
    layer = lm.LatentAttention(prog_cfg(cfg))
    names = {"q_a.weight": "attn.q_a", "q_norm.weight": "attn.q_a_norm",
             "q_b.weight": "attn.q_b", "kv_a.weight": "attn.kv_a",
             "kv_norm.weight": "attn.kv_a_norm", "kv_b.weight": "attn.kv_b",
             "o.weight": "attn.o"}
    p = {}
    for i, (name, param) in enumerate(layer.named_parameters()):
        value = rand(seed + i, *param.shape, scale=0.2) + \
            (1.0 if "norm" in name else 0.0)
        param.set_value(value)
        p[names[name]] = jnp.asarray(value)
    return layer, p, rand(seed + 50, 2, length, cfg["hidden_size"])


def _attention_grads(layer, p, x, cfg):
    xt = tensor(x, grad=True)
    out = layer(xt)
    (out * out).sum().backward()
    want, grads = jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(ref.latent_attention(cfg, p, x, MM) ** 2),
        (0, 1)))(p, jnp.asarray(x))
    return out, xt, want, grads


def test_latent_attention_forward_and_gradients_on_the_dense_path():
    cfg = ref_cfg()
    layer, p, x = _attention_pair(cfg, 10, 24)
    out, xt, want, (gp, gx) = _attention_grads(layer, p, x, cfg)
    np.testing.assert_allclose(
        out.numpy(), jax.jit(lambda p, x: ref.latent_attention(
            cfg, p, x, MM))(p, jnp.asarray(x)), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(xt.grad.numpy(), gx, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(layer.kv_b.weight.grad.numpy(),
                               gp["attn.kv_b"], rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(layer.q_a.weight.grad.numpy(), gp["attn.q_a"],
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("nope,rope,dv", [(128, 64, 128), (64, 64, 192)],
                         ids=["192-128", "128-192"])
def test_latent_attention_through_the_flash_kernels(
        kernels_in_the_interpreter, monkeypatch, nope, rope, dv):
    cfg = ref_cfg(qk_nope_head_dim=nope, qk_rope_head_dim=rope, v_head_dim=dv,
                  rope_scaling=dict(YARN, factor=64,
                                    original_max_position_embeddings=4096))
    layer, p, x = _attention_pair(cfg, 20, 128)
    seen = []
    whole = fa._forward

    def spy(q, k, v, bias, **kw):
        seen.append((q.shape, k.shape, v.shape))
        return whole(q, k, v, bias, **kw)

    monkeypatch.setattr(fa, "_forward", spy)
    out, xt, want, (gp, gx) = _attention_grads(layer, p, x, cfg)
    assert seen == [((4, 128, nope + rope),) * 2 + ((4, 128, dv),)]
    np.testing.assert_allclose(
        out.numpy(), jax.jit(lambda p, x: ref.latent_attention(
            cfg, p, x, MM))(p, jnp.asarray(x)), rtol=5e-4, atol=5e-5)
    np.testing.assert_allclose(xt.grad.numpy(), gx, rtol=5e-3, atol=5e-4)
    np.testing.assert_allclose(layer.kv_b.weight.grad.numpy(),
                               gp["attn.kv_b"], rtol=5e-3, atol=5e-4)
    np.testing.assert_allclose(layer.q_b.weight.grad.numpy(), gp["attn.q_b"],
                               rtol=5e-3, atol=5e-4)


def test_flash_routing_rule_and_block_rule_take_both_widths(
        kernels_in_the_interpreter):
    def spec(dqk, dv, length=128):
        q = (1, 2, length, dqk)
        return fa.flash_route(q, q, (1, 2, length, dv), True, None, 0.0)

    assert spec(192, 128) is not None and spec(64, 256) is not None
    assert spec(192, 96) is None and spec(320, 128) is None
    assert spec(64, 64, 120) is None
    # shapes routed before take the blocks they took
    assert fa.block_sizes(1024, 1024, 64, 2) == \
        fa.block_sizes(1024, 1024, 64, 2, None, 64)
    assert fa.vmem_bytes(512, 512, 256, 128, 2) == \
        fa.vmem_bytes(512, 512, 256, 128, 2, 128)
    assert fa.vmem_bytes(512, 512, 256, 192, 2, 128) < \
        fa.vmem_bytes(512, 512, 256, 192, 2)
    bq, bk, sub = fa.block_sizes(4096, 4096, 192, 2, None, 128)
    assert fa.vmem_bytes(bq, bk, sub, 192, 2, 128) <= fa.VMEM_BUDGET


# ---- the residual path ------------------------------------------------------------
def test_sinkhorn_rows_and_columns_sum_to_one():
    m = jnp.asarray(rand(30, 5, 4, 4))
    # the program's maps are stream-major, (n, n, tokens): row index first
    got = jnp.moveaxis(F.decoder.sinkhorn(jnp.moveaxis(m, 0, -1), 20, 1e-6),
                       -1, 0)
    # columns are normalised last: exact; rows to what 20 rounds leave
    np.testing.assert_allclose(got.sum(-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-4)
    assert (got > 0).all()
    far = F.decoder.sinkhorn(3.0 * jnp.moveaxis(m, 0, -1), 20, 1e-6)
    np.testing.assert_allclose(far.sum(1), 1.0, atol=0.05)   # slower from afar
    np.testing.assert_allclose(got, ref.sinkhorn(m, 20, 1e-6), rtol=1e-6)
    # the clamp bounds the logits before the exp: nothing overflows
    x = tensor(rand(31, 4, 2, 3, 8))
    pre, post, res = F.hc_maps(
        x, tensor(rand(32, 32, 24, scale=50.0)), tensor(np.ones(3, np.float32)),
        tensor(np.zeros(24, np.float32)), iters=20, eps=1e-6, clamp=(-30, 30))
    assert res.shape == [4, 4, 2, 3] and pre.shape == [4, 2, 3]
    assert np.isfinite(res.numpy()).all()
    np.testing.assert_allclose(res.numpy().sum(0), 1.0, atol=1e-3)


def _block_pair(cfg, seed, dense, program=None):
    pt.seed(seed)
    block = lm.LatentMoEBlock(program or prog_cfg(cfg), dense=dense)
    family = _family()
    names = family._block_names(dict(cfg, n_shared_experts=1), dense)
    p = {}
    for i, (name, param) in enumerate(block.named_parameters()):
        value = rand(seed + i, *param.shape, scale=0.15)
        if "norm" in name or name.endswith("alpha"):
            value = 1.0 + value
        param.set_value(value)
        p[names[name]] = jnp.asarray(value)
    return block, p


def _family():
    from benchmark import harness

    return harness.load_module("families", "xing4")


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "experts"])
def test_block_against_the_reference_with_maps_the_input_moves(dense):
    cfg = ref_cfg()
    block, p = _block_pair(cfg, 40, dense)
    x = rand(41, 2, 16, 4, 64)          # the reference's layout: (B, L, n, C)
    lead = np.moveaxis(x, 2, 0)         # the program's: streams first
    # the maps are far from constant over the tokens at these weights
    pre, post, res = block.attn_hc(tensor(lead))
    assert pre.numpy().std(axis=(1, 2)).min() > 0.02
    assert res.numpy().std(axis=(2, 3)).max() > 0.02
    xt = tensor(lead, grad=True)
    out, load = block(xt)
    (out * out).sum().backward()
    out = tensor(np.moveaxis(out.numpy(), 0, 2))
    run = ref.block(cfg, MM, dense)
    want, (gp, gx) = jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(run(p, x) ** 2), (0, 1)))(p, jnp.asarray(x))
    np.testing.assert_allclose(out.numpy(), jax.jit(run)(p, jnp.asarray(x)),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.moveaxis(xt.grad.numpy(), 0, 2), gx,
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(block.mlp_hc.phi.grad.numpy(),
                               gp["mlp_hc.phi"], rtol=5e-3, atol=5e-4)
    np.testing.assert_allclose(block.attn_hc.alpha.grad.numpy(),
                               gp["attn_hc.alpha"], rtol=5e-3, atol=5e-4)
    assert load.numpy().sum() == (0 if dense else 2 * 16 * 2)


# ---- the whole model -----------------------------------------------------------------
def _model_pair(cfg, seed, **kw):
    pt.seed(seed)
    model = lm.LatentMoE(prog_cfg(cfg, **kw))
    weights = rc.init_weights(ref.param_specs(cfg), seed, jnp.float32)
    state = {prog: weights[name]
             for prog, name in _family().name_map(
                 dict(cfg, n_shared_experts=1)).items()}
    missing, unexpected = model.set_state_dict(
        {k: tensor(v) for k, v in state.items()})
    assert not missing and not unexpected
    return model, weights


@pytest.mark.parametrize("mtp", [0, 1], ids=["plain", "mtp"])
def test_loss_with_and_without_multi_token_prediction(mtp):
    cfg = ref_cfg(num_nextn_predict_layers=mtp)
    model, weights = _model_pair(cfg, 50)
    rng = np.random.default_rng(51)
    ids = rng.integers(0, 256, (2, 17)).astype(np.int32)
    rows = (ids[:, :-1], ids[:, 1:])
    got = lm.latent_moe_loss(model, tensor(rows[0]), tensor(rows[1]))
    want = jax.jit(lambda w: ref.loss_part(cfg)(
        w, rows, ref.denominators(rows), MM))(weights)
    assert float(got.numpy()) == pytest.approx(float(want), rel=2e-5)
    if mtp:
        # lambda x the extra term, over the positions that have a target
        plain = jax.jit(lambda w: ref.loss_part(
            dict(cfg, num_nextn_predict_layers=0))(
                w, rows, ref.denominators(rows), MM))(weights)
        assert float(want) > float(plain) + 0.3 * 4.0


def test_recompute_keeps_loss_gradients_and_the_load_counter():
    cfg = ref_cfg(num_nextn_predict_layers=1)
    rng = np.random.default_rng(61)
    ids = tensor(rng.integers(0, 256, (2, 16)).astype(np.int32))
    labels = tensor(rng.integers(0, 256, (2, 16)).astype(np.int32))
    seen = []
    for recompute in (False, True):
        model, _ = _model_pair(cfg, 60, use_recompute=recompute)
        loss = lm.latent_moe_loss(model, ids, labels)
        loss.backward()
        seen.append((float(loss.numpy()), model.head.weight.grad.numpy(),
                     model.blocks[1].mlp.routed.router.grad.numpy(),
                     model.expert_load_counts()))
    assert seen[0][0] == pytest.approx(seen[1][0], rel=1e-6)
    np.testing.assert_allclose(seen[0][1], seen[1][1], atol=1e-6)
    np.testing.assert_allclose(seen[0][2], seen[1][2], atol=1e-6)
    # the counts left the recomputed blocks as outputs: 1 expert layer + MTP
    assert seen[1][3].shape == (2, 8) and (seen[1][3] == seen[0][3]).all()
    assert (seen[1][3].sum(axis=1) == 2 * 16 * 2).all()


def test_load_history_state_dict_and_gauges():
    from paddle_tpu import obs, optim

    cfg = ref_cfg()
    model, _ = _model_pair(cfg, 70, use_recompute=True)
    # buffers are not persistable: seeded weights cover the state dict
    assert set(model.state_dict()) == {n for n, _ in model.named_parameters()}
    step = pt.TrainStep(model, optim.AdamW(
        parameters=model.parameters(), learning_rate=1e-3,
        multi_precision=True, grad_clip=optim.ClipGradByGlobalNorm(1.0)),
        lm.latent_moe_loss)
    rng = np.random.default_rng(71)
    history = []
    for _ in range(3):
        ids = rng.integers(0, 256, (2, 17)).astype(np.int32)
        step(ids[:, :-1], ids[:, 1:])
        history.append(model.expert_load_counts())
        # the step publishes nothing; reading the registry does
        assert obs.snapshot()["moe.slots_held"] == history[-1].sum()
    assert model.expert_load.dtype == jnp.int32
    np.testing.assert_array_equal(model.expert_load_counts(3),
                                  np.stack(history))
    assert obs.gauge("moe.load_max_over_mean").value >= 1.0
    assert any((a != b).any() for a, b in zip(history, history[1:]))


@pytest.mark.parametrize("forced", [0, 1, 2])
def test_the_windows_gauges_follow_the_load_counts(forced):
    """``moe.window_passes_max`` is the most passes an expert layer ran over
    its windows and ``moe.window_live_share`` the held slots over the rows
    the layers worked on, as the step's own counts and the layers' R imply:
    one pass a layer under seeded routing, two in each layer whose bias
    sends every token to the two experts held."""
    from paddle_tpu import obs

    pt.seed(80)
    model = lm.LatentMoE(prog_cfg(ref_cfg(num_hidden_layers=3),
                                  first_expert=6, experts_held=2))
    ids = tensor(np.random.default_rng(81).integers(0, 256, (2, 256))
                 .astype(np.int32))
    routed = [model.blocks[i].mlp.routed for i in (1, 2)]
    rows = routed[0].window_rows(512)
    assert rows == 512 < 512 * 2        # a window: half of the 1,024 slots
    for layer in routed[:forced]:
        bias = np.zeros(8, np.float32)
        bias[6:] = 10.0
        layer.e_score_correction_bias.set_value(bias)
    with pt.no_grad():
        model(ids)
    model.publish_gauges()
    held = model.expert_load_counts()[:, 6:].sum(axis=1)
    assert (held[:forced] == 1024).all()
    assert (0 < held[forced:]).all() and (held[forced:] <= rows).all()
    passes = -(-held // rows)
    assert passes.tolist() == [2] * forced + [1] * (2 - forced)
    assert obs.gauge("moe.window_passes_max").value == passes.max()
    assert obs.gauge("moe.window_live_share").value == pytest.approx(
        held.sum() / (passes.sum() * rows))
    assert obs.gauge("moe.slots_held").value == held.sum()
