"""``dist.moe.DroplessMoE``: the router (sigmoid, the bias in the choice and
not in the weight, normalisation, scaling), the layer against a plain loop
over experts under even routing, with every token on one expert and with a
held subset, on the dense path and through the grouped-product kernels in the
interpreter, and THE SHARE TEST: the parts that the eight shares give, the
shared expert counted once, add up to the uncut reference's layer."""
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
from paddle_tpu.dist import moe  # noqa: E402
from paddle_tpu.ops import pallas as pk  # noqa: E402

MM = rc.matmul_of("float32")
T, C, W, E, K = 64, 32, 16, 8, 2


def rand(seed, *shape, scale=1.0):
    return (np.random.default_rng(seed).normal(size=shape) * scale) \
        .astype(np.float32)


WEIGHTS = {"mlp.router": rand(1, C, E, scale=0.3),
           "mlp.experts.gate": rand(2, E, C, W, scale=0.2),
           "mlp.experts.up": rand(3, E, C, W, scale=0.2),
           "mlp.experts.down": rand(4, E, W, C, scale=0.2),
           "mlp.shared.gate": rand(5, C, W, scale=0.2),
           "mlp.shared.up": rand(6, C, W, scale=0.2),
           "mlp.shared.down": rand(7, W, C, scale=0.2)}


def ref_cfg(first=0, held=E, shared=0):
    return dict(hc_mult=4, hidden_size=C, num_attention_heads=2,
                qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                n_routed_experts=held, n_routed_experts_published=E,
                first_routed_expert=first, num_experts_per_tok=K,
                norm_topk_prob=True, routed_scaling_factor=2.0,
                n_shared_experts=shared)


def ref_weights(first=0, held=E, router=None):
    p = {k: jnp.asarray(v) for k, v in WEIGHTS.items()}
    for k in ("mlp.experts.gate", "mlp.experts.up", "mlp.experts.down"):
        p[k] = p[k][first:first + held]
    if router is not None:
        p["mlp.router"] = jnp.asarray(router)
    return p


def layer(first=0, held=E, router=None):
    m = moe.DroplessMoE(C, W, E, K, first=first, held=held, routed_scale=2.0)
    p = ref_weights(first, held, router)
    m.router.set_value(p["mlp.router"])
    m.experts_gate.set_value(p["mlp.experts.gate"])
    m.experts_up.set_value(p["mlp.experts.up"])
    m.experts_down.set_value(p["mlp.experts.down"])
    return m


def tensor(a, grad=False):
    return Tensor(jnp.asarray(a), stop_gradient=not grad, _internal=True)


@pytest.fixture(params=["dense", "kernel"])
def path(request, monkeypatch):
    """Both paths of ``moe_experts``: the masked loop, and megablox's grouped
    products in the interpreter (asserted taken)."""
    pk.set_enabled(request.param == "kernel")
    taken = []
    whole = moe._grouped_swiglu
    monkeypatch.setattr(moe, "_grouped_swiglu",
                        lambda *a: taken.append(1) or whole(*a))
    yield request.param
    pk.set_enabled(None)
    assert bool(taken) == (request.param == "kernel")


# ---- the router ---------------------------------------------------------------
def test_router_scores_choice_and_weights():
    h, w = rand(10, 12, C), WEIGHTS["mlp.router"]
    scores = np.asarray(moe.sigmoid_route(jnp.asarray(h), jnp.asarray(w)))
    np.testing.assert_allclose(scores, 1 / (1 + np.exp(-(h @ w))), rtol=1e-5)
    assert scores.dtype == np.float32
    # the bias is in the choice ...
    bias = np.zeros(E, np.float32)
    bias[5] = 10.0
    choice, order, inv, sizes = (np.asarray(a) for a in moe.plan_slots(
        jnp.asarray(scores), jnp.asarray(bias), k=K))
    assert (choice == 5).any(axis=1).all() and sizes[5] == 12
    free = np.asarray(moe.plan_slots(jnp.asarray(scores),
                                     jnp.zeros(E), k=K)[0])
    np.testing.assert_array_equal(np.sort(free, 1),
                                  np.sort(np.argsort(-scores, 1)[:, :K], 1))
    # ... and the plan is a sort of the slots by expert with its inverse
    flat = choice.reshape(-1)
    assert (np.diff(flat[order]) >= 0).all() and (order[inv] ==
                                                  np.arange(12 * K)).all()
    np.testing.assert_array_equal(sizes, np.bincount(flat, minlength=E))
    # ... and not in the weight: normalised over the chosen, times the scale
    out = jnp.ones((12 * K, 1))
    got = np.asarray(moe._moe_combine(
        out, jnp.asarray(scores), jnp.asarray(choice), jnp.asarray(order),
        jnp.asarray(inv), scale=2.0, normalize=True))
    np.testing.assert_allclose(got[:, 0], 2.0, rtol=1e-6)
    raw = np.asarray(moe._moe_combine(
        out, jnp.asarray(scores), jnp.asarray(choice), jnp.asarray(order),
        jnp.asarray(inv), scale=1.0, normalize=False))
    np.testing.assert_allclose(
        raw[:, 0], np.take_along_axis(scores, choice, 1).sum(1), rtol=1e-6)
    # the reference's gate agrees entry by entry
    gate = np.asarray(ref.gate_weights(ref_cfg(), jnp.asarray(scores)))
    assert ((gate > 0).sum(axis=1) == K).all()
    np.testing.assert_allclose(gate.sum(axis=1), 2.0, rtol=1e-6)


# ---- the layer against a loop over experts ---------------------------------------
def loop_over_experts(h, p, first, held):
    scores = 1 / (1 + np.exp(-np.clip(h @ np.asarray(p["mlp.router"]), -80, 80)))
    choice = np.argsort(-scores, 1, kind="stable")[:, :K]
    out = np.zeros_like(h)
    for t in range(len(h)):
        w = scores[t, choice[t]]
        w = w / w.sum() * 2.0
        for j, e in enumerate(choice[t]):
            if first <= e < first + held:
                g, u, d = (np.asarray(p[f"mlp.experts.{n}"][e - first])
                           for n in ("gate", "up", "down"))
                a = h[t] @ g
                out[t] += w[j] * ((a / (1 + np.exp(-a))) * (h[t] @ u)) @ d
    return out, np.bincount(choice.reshape(-1), minlength=E)


@pytest.mark.parametrize("case", ["even", "one_expert", "held_subset",
                                  "none_held"])
def test_layer_against_a_loop_over_experts(path, case):
    first, held, router = 0, E, None
    if case == "one_expert":       # every token's first choice is expert 3
        router = WEIGHTS["mlp.router"] * 0.01
        router[:, 3] += 5.0 * np.sign(rand(20, 1, C)[0])
    if case == "held_subset":
        first, held = 2, 3
    x = rand(21, 2, T // 2, C)
    if case == "one_expert":
        x = np.abs(x) * np.sign(rand(20, 1, C)[0])
    if case == "none_held":        # route away from the two experts held
        first, held, router = 6, 2, WEIGHTS["mlp.router"].copy()
        router[:, 6:] = -np.abs(router[:, 6:]) * 50 * np.sign(x.mean((0, 1)))[:, None]
        x = np.abs(x) * np.sign(x.mean((0, 1)))
    m = layer(first, held, router)
    xt = tensor(x, grad=True)
    y, load = m(xt)
    (y * y).sum().backward()
    want, counts = loop_over_experts(x.reshape(-1, C),
                                     ref_weights(first, held, router),
                                     first, held)
    np.testing.assert_allclose(y.numpy().reshape(-1, C), want, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_array_equal(load.numpy(), counts)
    assert load.numpy().sum() == T * K          # dropless: every slot counted
    if case == "one_expert":
        assert counts[3] == T
    if case == "none_held":
        assert counts[6:].sum() == 0 and not y.numpy().any()
    # gradients against the reference's plain loop (jax)
    cfg, p = ref_cfg(first, held), ref_weights(first, held, router)
    gp, gx = jax.grad(lambda p, x: jnp.sum(ref.experts(cfg, p, x, MM) ** 2),
                      (0, 1))(p, jnp.asarray(x))
    np.testing.assert_allclose(xt.grad.numpy(), gx, rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(m.router.grad.numpy(), gp["mlp.router"],
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(m.experts_down.grad.numpy(),
                               gp["mlp.experts.down"], rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(m.experts_gate.grad.numpy(),
                               gp["mlp.experts.gate"], rtol=1e-3, atol=1e-5)


# ---- the share test ---------------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_layer(path):
    """Eight chips hold one expert each and all compute the shared expert:
    the routed parts summed, the shared expert counted once, are the uncut
    reference's layer; so are the reference's own eight shares."""
    x = rand(30, 2, T // 2, C)
    whole = np.asarray(ref.experts(ref_cfg(shared=1), ref_weights(),
                                   jnp.asarray(x), MM))
    shared = np.asarray(ref.swiglu(
        jnp.asarray(x), *(jnp.asarray(WEIGHTS[f"mlp.shared.{n}"])
                          for n in ("gate", "up", "down")), MM))
    parts, loads = [], []
    for chip in range(E):
        y, load = layer(chip, 1)(tensor(x))
        parts.append(y.numpy())
        loads.append(load.numpy())
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=1e-4,
                               atol=1e-5)
    # every chip routes over all experts alike
    assert all((l == loads[0]).all() for l in loads)
    reference_parts = [np.asarray(ref.experts(
        ref_cfg(chip, 1), ref_weights(chip, 1), jnp.asarray(x), MM))
        for chip in range(E)]
    np.testing.assert_allclose(sum(reference_parts) + shared, whole,
                               rtol=1e-4, atol=1e-5)
    # and a chip's part is its reference share's
    np.testing.assert_allclose(parts[3], reference_parts[3], rtol=1e-4,
                               atol=1e-5)


def test_the_model_level_share(path):
    """The same through ``ExpertMLP`` (shared + routed) in a block: two chips
    of four experts each; their MLP outputs less one shared expert's add up
    to the uncut layer's."""
    from paddle_tpu.models.nlp import latent_moe as lm

    x = rand(40, 2, 8, C)
    outs = []
    for first, held in ((0, 8), (0, 4), (4, 4)):
        pt.seed(5)
        cfg = lm.latent_moe_tiny(hidden=C, expert_width=W, experts=E,
                                 top_k=K, first_expert=first,
                                 experts_held=held)
        mlp = lm.ExpertMLP(cfg)
        p = ref_weights(first, held)
        mlp.routed.router.set_value(p["mlp.router"])
        for n in ("gate", "up", "down"):
            getattr(mlp.routed, f"experts_{n}").set_value(
                p[f"mlp.experts.{n}"])
            getattr(mlp.shared, n).weight.set_value(p[f"mlp.shared.{n}"])
        outs.append(mlp(tensor(x))[0].numpy())
        shared = mlp.shared(tensor(x)).numpy()
    np.testing.assert_allclose(outs[1] + outs[2] - shared, outs[0],
                               rtol=1e-4, atol=1e-5)


def test_bad_shares_are_refused():
    with pytest.raises(ValueError):
        moe.DroplessMoE(C, W, E, K, first=6, held=3)
