"""``dist.moe.DroplessMoE``: the router (sigmoid, the bias in the choice and
not in the weight, normalisation, scaling), the layer against a plain loop
over experts under even routing, with every token on one expert and with a
held subset, on the dense path and through the grouped-product kernels in the
interpreter, THE SHARE TEST: the parts that the eight shares give, the
shared expert counted once, add up to the uncut reference's layer, and THE
WINDOWS: a layer that holds a share of the experts, looped over the rows its
own experts hold, against the loop over experts and against the whole path
at every load from none to all, with what the compiled step may not hold."""
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


# 320 tokens, 640 slots in row tiles of 128, over a layer that holds experts 6
# and 7 of 16: its window is R = 256 rows, twice the even share of 80. The
# width is none of the other sizes, so that a shape names what it holds.
WT, WE, WW, WFIRST, WHELD, R = 320, 16, 24, 6, 2, 256
WINDOW_WEIGHTS = {"mlp.router": rand(51, C, WE, scale=0.3),
                  "mlp.experts.gate": rand(52, WE, C, WW, scale=0.2),
                  "mlp.experts.up": rand(53, WE, C, WW, scale=0.2),
                  "mlp.experts.down": rand(54, WE, WW, C, scale=0.2)}


def ref_cfg(first=0, held=E, shared=0, experts=E):
    return dict(hc_mult=4, hidden_size=C, num_attention_heads=2,
                qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                n_routed_experts=held, n_routed_experts_published=experts,
                first_routed_expert=first, num_experts_per_tok=K,
                norm_topk_prob=True, routed_scaling_factor=2.0,
                n_shared_experts=shared)


def ref_weights(first=0, held=E, router=None, weights=WEIGHTS):
    p = {k: jnp.asarray(v) for k, v in weights.items()}
    for k in ("mlp.experts.gate", "mlp.experts.up", "mlp.experts.down"):
        p[k] = p[k][first:first + held]
    if router is not None:
        p["mlp.router"] = jnp.asarray(router)
    return p


def layer(first=0, held=E, router=None, weights=WEIGHTS):
    experts, _, width = weights["mlp.experts.gate"].shape
    m = moe.DroplessMoE(C, width, experts, K, first=first, held=held,
                        routed_scale=2.0)
    p = ref_weights(first, held, router, weights)
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
    products in the interpreter (asserted taken: the whole path reaches them
    through ``_grouped_swiglu``, a window's pass by the kernels' module)."""
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend

    pk.set_enabled(request.param == "kernel")
    taken = []

    def spy(owner, name):
        real = getattr(owner, name)
        monkeypatch.setattr(owner, name, lambda *a, **kw: taken.append(name)
                            or real(*a, **kw))

    spy(moe, "_grouped_swiglu")
    spy(backend, "gmm")
    spy(backend, "tgmm")
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
    E = p["mlp.router"].shape[1]
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


# ---- the windows --------------------------------------------------------------
LOADS = [0, 1, R - 1, R, R + 1, 2 * R + 1, WT * K]


def steered(load):
    """(x, router) that put exactly ``load`` slots on experts 6 and 7: the
    first ``load // 2`` tokens choose both, the next ``load % 2`` the first
    of them, the others neither. Two coordinates of the hidden state say so,
    far from any tie."""
    x = rand(55, WT, C)
    both, one = load // 2, load % 2
    x[:, 0] = np.where(np.arange(WT) < both + one, 4.0, -4.0)
    x[:, 1] = np.where(np.arange(WT) < both, 4.0, -4.0)
    router = WINDOW_WEIGHTS["mlp.router"].copy()
    router[:2] = 0.0
    router[0, WFIRST], router[1, WFIRST + 1] = 5.0, 5.0
    return x.reshape(2, -1, C), router


def window_layer(router, whole=False, monkeypatch=None):
    if whole:       # the same layer over all its rows, as the parent ran it
        monkeypatch.setattr(moe, "window_rows", lambda t, k, h, e: t * k)
    m = layer(WFIRST, WHELD, router, WINDOW_WEIGHTS)
    assert m.window_rows(WT) == (WT * K if whole else R)
    return m


def value_and_gradients(m, x, region=False):
    """[y, d x, d router, d gate, d up, d down] of sum(y^2), load."""
    from paddle_tpu.framework.recompute import recompute

    xt = tensor(x, grad=True)
    y, load = recompute(m, xt) if region else m(xt)
    (y * y).sum().backward()
    return [y.numpy(), xt.grad.numpy()] + [
        p.grad.numpy() for p in (m.router, m.experts_gate, m.experts_up,
                                 m.experts_down)], load.numpy()


def same_to_rounding(a, b):
    """Two orders of the same float32 sums: against the array's own scale."""
    assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max() + 1e-30


@pytest.mark.parametrize("load", LOADS)
def test_the_looped_layer_at_every_load(path, load, monkeypatch):
    """No pass, one, one to the last row, one more row, three passes, every
    slot on the held experts: value and all five gradients are the loop over
    experts' and the whole path's."""
    x, router = steered(load)
    got, counts = value_and_gradients(window_layer(router), x)
    assert counts[WFIRST:WFIRST + WHELD].sum() == load
    assert counts.sum() == WT * K               # dropless: every slot counted
    p = ref_weights(WFIRST, WHELD, router, WINDOW_WEIGHTS)
    want, loop_counts = loop_over_experts(x.reshape(-1, C), p, WFIRST, WHELD)
    np.testing.assert_array_equal(counts, loop_counts)
    np.testing.assert_allclose(got[0].reshape(-1, C), want, rtol=1e-4,
                               atol=1e-5)
    cfg = ref_cfg(WFIRST, WHELD, experts=WE)
    gp, gx = jax.grad(lambda p, x: jnp.sum(ref.experts(cfg, p, x, MM) ** 2),
                      (0, 1))(p, jnp.asarray(x))
    for mine, theirs in zip(got[1:], [gx] + [gp[f"mlp.{n}"] for n in (
            "router", "experts.gate", "experts.up", "experts.down")]):
        np.testing.assert_allclose(mine, theirs, rtol=1e-3, atol=1e-5)
    whole, _ = value_and_gradients(
        window_layer(router, True, monkeypatch), x)
    for mine, theirs in zip(got, whole):
        same_to_rounding(mine, theirs)
    if load == 0:
        assert not any(a.any() for a in got[:2] + got[3:])


@pytest.mark.parametrize("load", [1, R + 1, WT * K])
def test_the_looped_layer_under_recompute(path, load):
    """A recomputed region keeps ``y`` beside the scores and the plan and
    makes the rest again in the backward's passes: the same gradients."""
    x, router = steered(load)
    plain, _ = value_and_gradients(window_layer(router), x)
    again, _ = value_and_gradients(window_layer(router), x, region=True)
    assert np.abs(plain[1]).max() > 0
    for a, b in zip(again, plain):
        same_to_rounding(a, b)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold, a
    kernel's own body left out (megablox's has a ``cond`` on its last tile)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(inner)


def traced(m, region=False):
    """The jaxpr of a layer's forward + backward over WT tokens."""
    from paddle_tpu.framework.recompute import RECOMPUTE_KEEP, _region, \
        _segment_params

    params = _segment_params(m, None)
    pure = _region(lambda x: m(x), params)
    if region:
        pure = jax.checkpoint(
            pure, policy=jax.checkpoint_policies.save_only_these_names(
                RECOMPUTE_KEEP))

    def loss(arrays, x):
        return jnp.sum(pure(*arrays, x)[0] ** 2)

    return jax.make_jaxpr(jax.grad(loss, (0, 1)))(
        [p._data for p in params], jnp.zeros((2, WT // 2, C))).jaxpr


def loops(jaxpr):
    """(grouped products in its body, rows it gathers from the hidden
    state) of each of the layer's own loops."""
    out = []
    for eqn in equations(jaxpr):
        if eqn.primitive.name != "while":
            continue
        body = list(equations(eqn.params["body_jaxpr"].jaxpr))
        gathers = [e for e in body if e.primitive.name == "gather"
                   and e.outvars[0].aval.shape == (R, C)]
        if gathers:
            # megablox's products are jitted functions round their kernel
            out.append(([e.params["name"] for e in body
                         if e.params.get("name") in ("gmm", "tgmm")],
                        len(gathers)))
    return out


@pytest.mark.parametrize("region", [False, True], ids=["plain", "recompute"])
def test_what_the_compiled_layer_may_not_hold(path, region):
    """What cost PR 38 its verdict: no ``cond`` (a second path), nothing of
    all T k rows by the hidden or the experts' width, one forward loop and
    one backward loop (a recomputed region makes no second forward loop),
    and each grouped product once a loop body: 3 + 9."""
    jaxpr = traced(layer(WFIRST, WHELD, None, WINDOW_WEIGHTS), region)
    names = [e.primitive.name for e in equations(jaxpr)]
    assert "cond" not in names and "switch" not in names
    wide = [v.aval.shape for e in equations(jaxpr) for v in e.outvars
            if len(getattr(v.aval, "shape", ())) > 1
            and v.aval.shape[0] == WT * K and v.aval.shape[-1] in (C, WW)]
    assert not wide, wide
    forward, backward = sorted(loops(jaxpr), key=lambda l: l[1])
    assert (forward[1], backward[1]) == (1, 2)      # h's rows; h's and g's
    if path == "kernel":
        assert sorted(forward[0]) == ["gmm"] * 3
        assert sorted(backward[0]) == ["gmm"] * 6 + ["tgmm"] * 3
        outside = names.count("pallas_call") - 12
        assert outside == 0, outside


def test_a_layer_that_holds_every_expert_traces_as_it_did():
    """All experts held, or so many that a window would be all the rows: the
    three stages over all T k rows as taped ops of their own, to the jaxpr
    that calling them by hand gives, and no loop."""
    def by_hand(m):
        def run(x):
            h = x.reshape(-1, C)
            scores = moe.sigmoid_route(h, m.router._data)
            choice, order, inv, sizes = moe.plan_slots(
                scores, m.e_score_correction_bias._data, k=K)
            out = moe._moe_experts(
                moe._moe_dispatch(h, order, inv, k=K), sizes,
                m.experts_gate._data, m.experts_up._data,
                m.experts_down._data, first=m.first)
            return moe._moe_combine(
                out, scores, choice, order, inv, scale=2.0,
                normalize=True).reshape(x.shape), sizes.astype(jnp.float32)
        return run

    x = jnp.zeros((2, WT // 2, C))
    for m in (layer(weights=WINDOW_WEIGHTS),
              layer(0, WE // 2, None, WINDOW_WEIGHTS)):
        assert m.window_rows(WT) == WT * K
        with pt.no_grad():      # the ops alone, without their tape
            mine = jax.make_jaxpr(lambda x: tuple(
                t._data for t in m(Tensor(x, _internal=True))))(x)
        assert "while" not in {e.primitive.name
                               for e in equations(mine.jaxpr)}
        assert str(mine) == str(jax.make_jaxpr(by_hand(m))(x))


@pytest.mark.parametrize("tokens,k,held,experts,rows", [
    (4096, 8, 8, 320, 2048),        # solar2_pretrain_tp8_ep40: share 819
    (8192, 8, 16, 256, 8192),       # joyai_pretrain_mtp_ep16: share 4,096
    (4096, 4, 8, 64, 4096),         # xing4_pretrain_ep8: share 2,048
    (4096, 1, 8, 64, 1024),         # top-1
    (4096, 4, 32, 64, 4096 * 4),    # half of the experts: no window
    (4096, 4, 64, 64, 4096 * 4),    # all of them
    (4096, 8, 1, 4096, 512),        # a sliver: one row tile at the least
    (WT, K, WHELD, WE, R),          # these tests': row tiles of 128
    (64, 2, 3, 8, 128),             # one small tile holds every slot
    (48, 2, 1, 8, 96),              # no whole 128-lane tile: all the rows
], ids=["solar2", "joyai", "xing4", "top1", "half", "all", "sliver", "tests",
        "one_tile", "no_tile"])
def test_window_rows_follow_the_shapes(tokens, k, held, experts, rows):
    assert moe.window_rows(tokens, k, held, experts) == rows
    assert rows <= tokens * k
    assert rows % moe._gmm_tiling(rows, C, WW)[0] == 0     # whole row tiles


# ---- the share test ---------------------------------------------------------------
@pytest.mark.parametrize("tokens", [T, WT], ids=["whole", "windows"])
def test_the_eight_shares_add_up_to_the_uncut_layer(path, tokens):
    """Eight chips hold one expert each and all compute the shared expert:
    the routed parts summed, the shared expert counted once, are the uncut
    reference's layer; so are the reference's own eight shares. At 64 tokens
    a share works on all its 128 rows, at 320 on windows of 256 of 640."""
    x = rand(30, 2, tokens // 2, C)
    assert layer(3, 1).window_rows(tokens) == {T: T * K, WT: 256}[tokens]
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
    from paddle_tpu.models.nlp import decoder_stack, latent_moe as lm

    x = rand(40, 2, 8, C)
    outs = []
    for first, held in ((0, 8), (0, 4), (4, 4)):
        pt.seed(5)
        cfg = lm.latent_moe_tiny(hidden=C, expert_width=W, experts=E,
                                 top_k=K, first_expert=first,
                                 experts_held=held)
        mlp = decoder_stack.ExpertMLP(cfg)
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
