"""``LatentMoE(streams=1)``: the plain pre-norm residual with the
multi-token-prediction module, at a small size with seeded float32 weights
against the plain reference (``benchmark/reference/joyai.py``, which imports
nothing of the program): logits, loss with and without MTP, every leaf's
gradient; no residual map as leaf or as op; the program scope ``mtp`` on the
compiled step's forward and backward instructions; the loss's two terms as
gauges; the unscaled rotary tables and the layout the configuration assumes.

Tolerances. Program and reference both run in float32 on the CPU here and
differ in the order of their sums (the program's RMS norm and attention are
its registered ops, the reference's are written out; the experts' products
are grouped in one and looped in the other), which reads 1e-6 to 1e-5
relative on an activation and grows through five blocks and a softmax:
logits to 2e-4, the loss (a mean of 2 x 16 positions) to 2e-5, a leaf's
gradient to 2e-3 of its norm. Routing is discrete, but at these widths no
token's 2nd and 3rd scores lie within float32 rounding, so both sides choose
the same experts.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as pt  # noqa: E402
from benchmark import harness, scope_paths, scope_reduce  # noqa: E402
from benchmark.reference import _common as rc  # noqa: E402
from benchmark.reference import joyai as ref  # noqa: E402
from paddle_tpu import obs, optim  # noqa: E402
from paddle_tpu.core import dispatch  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.models.nlp import latent_moe as lm  # noqa: E402
from paddle_tpu.nn import functional as F  # noqa: E402

MM = rc.matmul_of("float32")
FAMILY = harness.load_module("families", "joyai")


def ref_cfg(**kw):
    """The reference's configuration (the source's keys) at a small size."""
    cfg = harness.load_json("configs", "joyai-llm-flash.json")
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_hidden_layers=3, num_attention_heads=2, q_lora_rank=32,
               kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, n_routed_experts=4, n_routed_experts_published=8,
               first_routed_expert=2, num_experts_per_tok=2, vocab_size=256,
               program={})
    cfg.update(kw)
    return cfg


def tensor(a):
    return Tensor(jnp.asarray(a), _internal=True)


def model_pair(cfg, seed, **program):
    pt.seed(seed)
    model = lm.LatentMoE(FAMILY.program_config(dict(cfg, program=program)))
    weights = rc.init_weights(ref.param_specs(cfg), seed, jnp.float32)
    missing, unexpected = model.set_state_dict(
        {prog: tensor(weights[name])
         for prog, name in FAMILY.name_map(cfg).items()})
    assert not missing and not unexpected
    return model, weights


def rows(seed, batch=2, length=16, vocab=256):
    ids = np.random.default_rng(seed).integers(
        0, vocab, (batch, length + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


# ---- the block has no maps ------------------------------------------------------
def test_one_stream_is_the_plain_residual_without_a_map_leaf():
    model, _ = model_pair(ref_cfg(), 1)
    assert model.cfg.streams == 1 and model.mtp is not None
    names = [n for n, _ in model.named_parameters()]
    assert not [n for n in names if "_hc." in n]
    assert set(model.state_dict()) == set(names)
    for block in list(model.blocks) + [model.mtp.block]:
        assert not hasattr(block, "attn_hc") and not hasattr(block, "mlp_hc")
    # the state is (B, L, C): nothing is expanded to streams or summed back
    h, loads = model.hidden(tensor(rows(2)[0]))
    assert h.shape == [2, 16, 64] and len(loads) == 2
    # and more than one stream still builds its maps
    many = lm.LatentMoE(lm.latent_moe_tiny(streams=2))
    assert [n for n, _ in many.named_parameters() if "attn_hc.phi" in n]


def test_a_block_is_x_plus_f_of_the_normed_x():
    cfg = ref_cfg()
    model, weights = model_pair(cfg, 3)
    x = np.random.default_rng(4).normal(size=(2, 16, 64)).astype(np.float32)
    for i, dense in ((0, True), (1, False)):
        got, load = model.blocks[i](tensor(x))
        want = jax.jit(ref.block(cfg, MM, dense))(
            ref._under(weights, f"layers.{i}."), jnp.asarray(x))
        np.testing.assert_allclose(got.numpy(), want, rtol=2e-4, atol=2e-5)
        assert load.numpy().sum() == (0 if dense else 2 * 16 * 2)


# ---- logits, loss, gradients ----------------------------------------------------
@pytest.mark.parametrize("mtp", [0, 1], ids=["plain", "mtp"])
def test_logits_against_the_reference(mtp):
    cfg = ref_cfg(num_nextn_predict_layers=mtp)
    model, weights = model_pair(cfg, 10)
    ids, labels = rows(11)

    def want(w):
        h = ref.hidden(cfg, w, ids, MM)
        main = ref.logits_of(cfg, w, h, MM)
        return (main, ref.mtp_logits(cfg, w, h, labels, MM)) if mtp else main

    want = jax.jit(want)(weights)
    if mtp:
        got = model.forward_mtp(tensor(ids), tensor(labels))
        for g, w in zip(got, want):
            np.testing.assert_allclose(g.numpy(), w, rtol=2e-4, atol=2e-5)
        # the MTP block's load is the last row of the counter
        assert model.expert_load_counts().shape == (3, 8)
    else:
        np.testing.assert_allclose(model(tensor(ids)).numpy(), want,
                                   rtol=2e-4, atol=2e-5)
        assert model.expert_load_counts().shape == (2, 8)


@pytest.mark.parametrize("mtp", [0, 1], ids=["plain", "mtp"])
def test_loss_with_and_without_multi_token_prediction(mtp):
    cfg = ref_cfg(num_nextn_predict_layers=mtp)
    model, weights = model_pair(cfg, 20)
    batch = rows(21)
    got = lm.latent_moe_loss(model, *map(tensor, batch))
    want = jax.jit(lambda w: ref.loss_part(cfg)(
        w, batch, ref.denominators(batch), MM))(weights)
    assert float(got.numpy()) == pytest.approx(float(want), rel=2e-5)
    if mtp:
        # main over rows x L positions + 0.3 x MTP over rows x (L - 1)
        plain = jax.jit(lambda w: ref.loss_part(
            dict(cfg, num_nextn_predict_layers=0))(
                w, batch, ref.denominators(batch), MM))(weights)
        lm_term, mtp_term = np.asarray(model.loss_terms._data)
        assert lm_term == pytest.approx(float(plain), rel=2e-5)
        assert float(want) == pytest.approx(lm_term + 0.3 * mtp_term, rel=1e-6)
        assert mtp_term > 4.0        # about ln(256) at seeded weights
    else:
        assert not hasattr(model, "loss_terms")


@pytest.mark.parametrize("recompute", [False, True], ids=["kept", "recompute"])
@pytest.mark.parametrize("mtp", [0, 1], ids=["plain", "mtp"])
def test_every_leafs_gradient_against_the_reference(mtp, recompute):
    cfg = ref_cfg(num_nextn_predict_layers=mtp)
    model, weights = model_pair(cfg, 30, use_recompute=recompute)
    batch = rows(31)
    lm.latent_moe_loss(model, *map(tensor, batch)).backward()
    want = jax.jit(jax.grad(lambda w: ref.loss_part(cfg)(
        w, batch, ref.denominators(batch), MM)))(weights)
    params = dict(model.named_parameters())
    names = FAMILY.name_map(cfg)
    assert set(names.values()) == set(want)
    for prog, name in names.items():
        got, w = params[prog].grad.numpy(), np.asarray(want[name])
        assert np.linalg.norm(w) > 0, name
        assert np.linalg.norm(got - w) <= 2e-3 * np.linalg.norm(w), name


# ---- the compiled step ----------------------------------------------------------
@pytest.fixture(scope="module")
def trained():
    """Three TrainStep calls of the tiny model with MTP and recompute, the
    loss's gauges as the registry gives them after the first, the held slots'
    after the last, and the compiled step's text."""
    cfg = ref_cfg()
    model, weights = model_pair(cfg, 40, use_recompute=True)
    step = pt.TrainStep(model, optim.AdamW(
        parameters=model.parameters(), learning_rate=1e-3,
        multi_precision=True, grad_clip=optim.ClipGradByGlobalNorm(1.0)),
        lm.latent_moe_loss)
    batch = rows(41)
    want = jax.jit(lambda w: ref.loss_part(dict(
        cfg, num_nextn_predict_layers=0))(
            w, batch, ref.denominators(batch), MM))(weights)
    losses = [float(step(*batch).numpy())]
    snap = obs.snapshot()      # runs the model's publish_gauges
    gauges = [snap["loss.lm"], snap["loss.mtp"]]
    losses += [float(step(*batch).numpy()) for _ in range(2)]
    gauges.append(obs.snapshot()["moe.slots_held"])
    return model, step.compiled().as_text(), losses, gauges, float(want)


def test_the_compiled_step_holds_no_residual_map_op(trained):
    _, text, _, _, _ = trained
    paths = set(scope_reduce._OP_NAME.findall(text))
    assert len(paths) > 100
    for path in paths:
        assert not [name for name, _ in scope_reduce.scopes(path)
                    if name.startswith("hc_")], path


def test_the_scope_mtp_is_on_forward_and_backward_instructions(trained):
    _, text, _, _, _ = trained
    paths = [p for p in set(scope_reduce._OP_NAME.findall(text))
             if scope_paths.holds(p, "mtp")]
    phases = {scope_reduce.phase_of(p) for p in paths}
    assert phases == {"forward", "backward"}, phases
    # the module's own ops, its block's, and the head and cross-entropy it
    # shares with the main model, in both passes
    registered = {"rms_norm", "linear_nobias", "embedding", "recompute",
                  "cross_entropy_hard", "sdpa", "moe_experts"}
    for phase in ("forward", "backward"):
        ops = {name for p in paths if scope_reduce.phase_of(p) == phase
               for name, _ in scope_reduce.scopes(p)[:-1]}
        assert registered <= ops, (phase, registered - ops)
    # the main stack's work is outside it
    outside = [p for p in set(scope_reduce._OP_NAME.findall(text))
               if not scope_paths.holds(p, "mtp")]
    assert any("sdpa" in p for p in outside)
    assert any("cross_entropy_hard" in p for p in outside)


def test_the_losss_two_terms_are_gauges_and_the_step_trains(trained):
    model, _, losses, (lm_term, mtp_term, slots_held), want = trained
    assert lm_term == pytest.approx(want, rel=2e-5)
    assert losses[0] == pytest.approx(lm_term + 0.3 * mtp_term, rel=1e-6)
    assert losses[2] < losses[1] < losses[0]
    assert slots_held == model.expert_load_counts()[:, 2:6].sum()


def test_the_windows_gauges_of_layers_without_a_window(trained):
    """2 x 16 tokens hold their 64 slots in one row tile, so the layers (the
    MTP block's among them) have no window: one pass each over all the rows,
    and the live share is the held slots over all of them."""
    model = trained[0]
    assert model.mtp.block.mlp.routed.window_rows(2 * 16) == 64
    model.publish_gauges()
    counts = model.expert_load_counts()
    assert obs.gauge("moe.window_passes_max").value == 1
    assert obs.gauge("moe.window_live_share").value == pytest.approx(
        counts[:, 2:6].sum() / counts.sum())


def test_program_scope_opens_and_closes_and_eager_ops_enter_none():
    assert dispatch._state().scopes == ()
    with dispatch.program_scope("a"):
        with dispatch.program_scope("b"):
            assert dispatch._state().scopes == ("a", "b")
            y = F.rms_norm(tensor(np.ones((2, 4), np.float32)), None, 1e-6)
        assert dispatch._state().scopes == ("a",)
    assert dispatch._state().scopes == ()
    np.testing.assert_allclose(y.numpy(), 1.0, rtol=1e-5)

    def traced(x):
        with dispatch.program_scope("part"):
            return F.rms_norm(Tensor(x, _internal=True), None, 1e-6)._data

    text = jax.jit(traced).lower(jnp.ones((2, 4))).as_text(debug_info=True)
    assert "part/rms_norm" in text


# ---- rotary: unscaled, and the layout the configuration assumes ------------------
def test_unscaled_rotary_tables_against_the_reference():
    cfg = ref_cfg(qk_rope_head_dim=64)
    cos, sin = F.rotary_cos_sin(48, 64, cfg["rope_theta"], None)
    want_cos, want_sin = ref.rope_tables(cfg, 48)
    np.testing.assert_allclose(cos, want_cos, atol=1e-6)
    np.testing.assert_allclose(sin, want_sin, atol=1e-6)
    # theta 3.2e7: the slowest dim turns by 48 / 3.2e7^(62/64) of a radian
    assert cos[-1, 31] == pytest.approx(1.0, abs=1e-9)
    assert lm.LatentMoEConfig(rope_scaling=None).softmax_scale == \
        pytest.approx(192 ** -0.5)
    with pytest.raises(ValueError):
        ref.rope_tables(dict(cfg, rope_scaling={"factor": 4}), 8)


def test_interleaved_and_rotate_half_layouts_give_the_same_scores():
    """``rope_interleave: true`` pairs dims (2i, 2i + 1); the program pairs
    (i, i + d/2). Column 2i of the interleaved layout is column i of the
    other and column 2i + 1 is column i + d/2: with the rope columns of
    ``q_b`` and ``kv_a`` permuted so, every score is what it was."""
    d, length = 8, 12
    rng = np.random.default_rng(5)
    q, k = rng.normal(size=(2, length, d)), rng.normal(size=(2, length, d))
    cos, sin = F.rotary_cos_sin(length, d, 32000000.0, None)
    half = np.einsum("bld,bmd->blm", ref.rotate(q, cos, sin),
                     ref.rotate(k, cos, sin))

    def interleaved(x):
        freq = 32000000.0 ** (-np.arange(0, d, 2) / d)
        angle = np.arange(length)[:, None] * freq[None, :]
        even, odd = x[..., 0::2], x[..., 1::2]
        out = np.empty_like(x)
        out[..., 0::2] = even * np.cos(angle) - odd * np.sin(angle)
        out[..., 1::2] = even * np.sin(angle) + odd * np.cos(angle)
        return out

    perm = np.empty(d, np.int64)       # interleaved column -> rotate-half's
    perm[0::2], perm[1::2] = np.arange(d // 2), np.arange(d // 2) + d // 2
    inter = np.einsum("bld,bmd->blm", interleaved(q[..., perm]),
                      interleaved(k[..., perm]))
    np.testing.assert_allclose(inter, half, rtol=1e-5, atol=1e-6)
