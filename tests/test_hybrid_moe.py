"""``HybridMoE``: gated-delta-rule linear-attention layers beside a gated
softmax layer over grouped-query heads, a share of the heads held, at a small
size with seeded float32 weights against the plain reference
(``benchmark/reference/solar2.py``, which imports nothing of the program and
runs the rule token by token): the ops one by one, ``kda_chunk`` against the
recurrence (a row that is no multiple of the chunk; strong decay), grouped-
query ``sdpa``, logits, loss, every leaf's gradient, the program scopes on the
compiled step's forward and backward instructions, the gauges.

Tolerances. Program and reference both run in float32 on the CPU here and
differ in the order of their sums: the chunked rule solves a triangular
system a chunk where the reference steps a token at a time, the program's
norms and attention are its registered ops, the experts' products are
grouped in one and looped in the other. That reads 1e-6 to 1e-5 relative on
an activation and grows through four blocks: logits to 2e-4, the loss (a mean
of 2 x 24 positions) to 2e-5, a leaf's gradient to 2e-3 of its norm (``A_log``
and ``dt_bias``, whose gradients are sums of many small terms of both signs,
included). ``kda_chunk`` alone agrees with the recurrence to 2e-5 of the
output's scale, forward and in every gradient. Routing is discrete, but at
these widths no token's 2nd and 3rd scores lie within float32 rounding.
"""
import importlib
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
from benchmark.reference import solar2 as ref  # noqa: E402
from paddle_tpu import obs, optim  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.models.nlp import hybrid_moe as hm  # noqa: E402
from paddle_tpu.models.nlp.latent_moe import latent_moe_loss  # noqa: E402
from paddle_tpu.nn import functional as F  # noqa: E402
from paddle_tpu.nn.functional import linear_attention as la  # noqa: E402
from paddle_tpu.ops import pallas as pk  # noqa: E402

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
MM = rc.matmul_of("float32")
FAMILY = harness.load_module("families", "solar2")


def ref_cfg(**kw):
    """The reference's configuration (the source's keys) at a small size:
    heads 2-3 of 8 (one key/value head of 2 serves four), linear heads 2-3
    of 8, experts 2-5 of 8."""
    cfg = harness.load_json("configs", "solar-open2-250b.json")
    cfg.update(hidden_size=64, moe_intermediate_size=32, num_hidden_layers=4,
               gqa_layers=[0], num_attention_heads=2,
               num_attention_heads_published=8, num_key_value_heads=1,
               num_key_value_heads_published=2, first_head=2, head_dim=16,
               linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                                   "num_heads": 2, "num_kv_heads": None},
               kda_gate_rank=8, n_routed_experts=4,
               n_routed_experts_published=8, first_routed_expert=2,
               num_experts_per_tok=2, vocab_size=256, program={})
    cfg.update(kw)
    return cfg


def tensor(a):
    return Tensor(jnp.asarray(a), _internal=True)


def model_pair(cfg, seed, gates=None, **program):
    """The program's model and the reference's weights, the same numbers;
    ``gates`` = (A_log, dt_bias) puts other values than the seeded zeros into
    every linear layer's decay gate."""
    pt.seed(seed)
    model = hm.HybridMoE(FAMILY.program_config(dict(cfg, program=program)))
    weights = rc.init_weights(ref.param_specs(cfg), seed, jnp.float32)
    if gates is not None:
        for name in weights:
            if name.endswith("attn.A_log"):
                weights[name] = jnp.full_like(weights[name], gates[0])
            if name.endswith("attn.dt_bias"):
                weights[name] = jnp.full_like(weights[name], gates[1])
    missing, unexpected = model.set_state_dict(
        {prog: tensor(weights[name])
         for prog, name in FAMILY.name_map(cfg).items()})
    assert not missing and not unexpected
    return model, weights


def rows(seed, batch=2, length=24, vocab=256):
    ids = np.random.default_rng(seed).integers(
        0, vocab, (batch, length + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


# ---- the ops, one by one --------------------------------------------------------
def test_short_conv_is_causal_and_depthwise():
    rng = np.random.default_rng(0)
    x, w = rng.normal(size=(2, 9, 6)), rng.normal(size=(4, 6))
    got = F.short_conv(tensor(x.astype(np.float32)),
                       tensor(w.astype(np.float32))).numpy()
    want = np.zeros_like(x)
    for t in range(9):
        for j in range(4):          # tap 3 on the token itself
            if t - 3 + j >= 0:
                want[:, t] += w[j] * x[:, t - 3 + j]
    want = want / (1 + np.exp(-want))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        got, ref.short_conv(jnp.asarray(x, jnp.float32),
                            jnp.asarray(w, jnp.float32)), rtol=1e-5, atol=1e-6)
    # a later token changes nothing before it
    x2 = x.copy()
    x2[:, 5:] += 1.0
    again = F.short_conv(tensor(x2.astype(np.float32)),
                         tensor(w.astype(np.float32))).numpy()
    np.testing.assert_array_equal(again[:, :5], got[:, :5])


def test_the_gates_and_the_gated_norm():
    rng = np.random.default_rng(1)
    raw = rng.normal(size=(2, 5, 3 * 4)).astype(np.float32)
    a_log = np.log([0.5, 1.0, 16.0]).astype(np.float32)
    dt = rng.normal(size=(12,)).astype(np.float32)
    logits = rng.normal(size=(2, 5, 3)).astype(np.float32)
    g, beta = F.kda_gate(tensor(raw), tensor(a_log), tensor(dt),
                         tensor(logits), head_dim=4)
    want = -np.exp(a_log)[:, None] * np.log1p(np.exp(raw + dt)).reshape(
        2, 5, 3, 4)
    np.testing.assert_allclose(g.numpy(), want, rtol=1e-5)
    np.testing.assert_allclose(beta.numpy(), 2 / (1 + np.exp(-logits)),
                               rtol=1e-5)
    assert g.numpy().max() < 0 and g.numpy().dtype == np.float32
    _, plain = F.kda_gate(tensor(raw), tensor(a_log), tensor(dt),
                          tensor(logits), head_dim=4, neg_eigval=False)
    np.testing.assert_allclose(plain.numpy(), beta.numpy() / 2, rtol=1e-6)
    # RMS_w(x) * sigmoid(gate), a head's d at a time
    x = rng.normal(size=(2, 5, 3, 4)).astype(np.float32)
    gate = rng.normal(size=(2, 5, 3, 4)).astype(np.float32)
    w = rng.normal(size=(4,)).astype(np.float32)
    got = F.gated_rms_norm(tensor(x), tensor(gate), tensor(w), 1e-5).numpy()
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w / \
        (1 + np.exp(-gate))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---- the chunked rule against the recurrence ------------------------------------
def _rule_operands(seed, length, a, dt_bias, heads=2, d=16):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q, k, v = (normal(2, length, heads, d) for _ in range(3))
    g = -a * jax.nn.softplus(normal(2, length, heads, d) + dt_bias)
    beta = 2.0 * jax.nn.sigmoid(normal(2, length, heads))
    return (q, k, v, g, beta), normal(2, length, heads, d)


@pytest.mark.parametrize("length,a,dt_bias", [
    (200, 0.05, 0.0),    # slow decay: the state of earlier chunks matters
    (150, 1.0, 0.0),     # the seeded gates: -0.69 a token, -44 a chunk
    (256, 16.0, 2.0),    # a published A of 16: about -2,500 over a chunk
], ids=["slow", "seeded", "strong"])
def test_kda_chunk_against_the_token_by_token_rule(length, a, dt_bias):
    """Forward and all five gradients, chunks of 64, rows that are no
    multiple of the chunk (200, 150). With strong decay a chunk's log-decay
    passes float32's -88 thirty times over: a form that took ``exp(G)`` and
    ``exp(-G)`` apart would give inf x 0 here; this one is finite and right."""
    operands, weight = _rule_operands(length, length, a, dt_bias)
    d = operands[0].shape[-1]

    def chunked(*xs):
        o, low = la._kda_chunk(*xs, chunk=64)
        return jnp.sum(o * weight), (o, low)

    def stepped(q, k, v, g, beta):
        o = ref.delta_rule(ref.unit(q) * d ** -0.5, ref.unit(k), v, g, beta)
        return jnp.sum(o * weight), o

    every = tuple(range(5))
    (_, (got, low)), got_grads = jax.value_and_grad(
        chunked, every, has_aux=True)(*operands)
    (_, want), want_grads = jax.value_and_grad(
        stepped, every, has_aux=True)(*operands)
    scale = float(jnp.abs(want).max())
    assert scale > 0.1 and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)
    for name, g, w in zip("qkvgb", got_grads, want_grads):
        assert bool(jnp.isfinite(g).all()), name
        top = float(jnp.abs(w).max())
        assert top > 0, name
        np.testing.assert_allclose(g, w, atol=2e-5 * max(top, scale), rtol=0,
                                   err_msg=name)
    # the most negative log-decay a channel ran up over one chunk
    g = np.asarray(operands[3])
    pad = -length % 64
    g = np.pad(g, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sums = g.reshape(2, -1, 64, *g.shape[2:]).sum(axis=2)
    assert float(low) == pytest.approx(sums.min(), rel=1e-5)
    if a == 16.0:
        assert float(low) < -2000


def test_kda_chunk_carries_the_state_over_chunks_and_takes_bfloat16():
    """With slow decay the second chunk's outputs depend on the first
    chunk's tokens (the carried state is not dropped), and a chunk size that
    divides the row gives what one that does not gives."""
    operands, _ = _rule_operands(7, 64, 0.02, 0.0)
    base, _ = la._kda_chunk(*operands, chunk=16)
    other, _ = la._kda_chunk(*operands, chunk=24)
    np.testing.assert_allclose(base, other, atol=2e-5, rtol=0)
    moved = list(operands)
    moved[2] = operands[2].at[:, :8].add(1.0)        # v of the first tokens
    changed, _ = la._kda_chunk(*moved, chunk=16)
    assert float(jnp.abs(changed - base)[:, 48:].max()) > 1e-3
    # bfloat16 q, k, v in, bfloat16 out; gates stay float32
    q, k, v, g, beta = operands
    o, low = F.kda_chunk(*(tensor(x.astype(jnp.bfloat16)) for x in (q, k, v)),
                         tensor(g), tensor(beta), chunk=16)
    assert o._data.dtype == jnp.bfloat16 and low._data.dtype == jnp.float32


# ---- grouped-query attention ----------------------------------------------------
def _gqa_operands(length, d=16, dtype=jnp.float32):
    rng = np.random.default_rng(length)
    q = jnp.asarray(rng.normal(size=(2, 8, length, d)), dtype)
    k = jnp.asarray(rng.normal(size=(2, 2, length, d)), dtype)
    v = jnp.asarray(rng.normal(size=(2, 2, length, d)), dtype)
    return q, k, v


def _sdpa(q, k, v):
    return F.attention._sdpa(q, k, v, None, None, scale=q.shape[-1] ** -0.5,
                             is_causal=True, dropout_p=0.0)


def test_grouped_query_sdpa_against_the_expanded_dense_form():
    """Four query heads a key/value head: head j reads key/value head j //
    4; the gradient of a key/value head is the sum over its four readers.
    Both sides run the same dense path, so they agree to float32 rounding."""
    q, k, v = _gqa_operands(24)
    weight = jnp.asarray(np.random.default_rng(3).normal(size=q.shape),
                         jnp.float32)

    def grouped(q, k, v):
        return jnp.sum(_sdpa(q, k, v) * weight)

    def expanded(q, k, v):
        return jnp.sum(_sdpa(q, jnp.repeat(k, 4, 1), jnp.repeat(v, 4, 1)) *
                       weight)

    np.testing.assert_allclose(
        _sdpa(q, k, v), _sdpa(q, jnp.repeat(k, 4, 1), jnp.repeat(v, 4, 1)),
        rtol=1e-6, atol=1e-6)
    # and by hand for one query head: head 5 reads key/value head 1
    s = jnp.einsum("bqd,bkd->bqk", q[:, 5], k[:, 1]) * 0.25
    s = jnp.where(jnp.tril(jnp.ones((24, 24), bool)), s, -jnp.inf)
    np.testing.assert_allclose(
        _sdpa(q, k, v)[:, 5], jnp.einsum("bqk,bkd->bqd",
                                         jax.nn.softmax(s, -1), v[:, 1]),
        rtol=1e-5, atol=1e-6)
    got = jax.grad(grouped, (0, 1, 2))(q, k, v)
    want = jax.grad(expanded, (0, 1, 2))(q, k, v)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    # the public entry, (B, H, L, D) Tensors
    out = F.sdpa_bhld(tensor(q), tensor(k), tensor(v), is_causal=True)
    np.testing.assert_allclose(out.numpy(), _sdpa(q, k, v), rtol=1e-6)


def test_grouped_query_sdpa_runs_the_flash_kernels(monkeypatch):
    """In front of the kernels the key/value heads are expanded to the query
    heads, so a grouped call takes ``flash_fwd_causal`` and its two backward
    kernels and no dense path (interpreter; bfloat16 rounding of the
    kernels' inputs: 2e-2 of the output's scale)."""
    pk.set_enabled(True)
    monkeypatch.setattr(fa, "MIN_STEP_SCORES", 128 * 128)
    try:
        q, k, v = _gqa_operands(128, d=64)

        def loss(q, k, v):
            return jnp.sum(_sdpa(q, k, v) ** 2)

        text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, v).as_text(
            debug_info=True)
        for kernel in ("flash_fwd_causal", "flash_bwd_dq_causal",
                       "flash_bwd_dkv_causal"):
            assert kernel in text, kernel
        got = jax.grad(loss, (0, 1, 2))(q, k, v)
    finally:
        pk.set_enabled(None)
    want = jax.grad(loss, (0, 1, 2))(q, k, v)      # the dense path
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=2e-2 * float(jnp.abs(w).max()),
                                   rtol=0)


# ---- the model -------------------------------------------------------------------
def test_the_layer_pattern_the_share_and_the_leaves():
    cfg = ref_cfg()
    model, weights = model_pair(cfg, 1)
    c = model.cfg
    assert [b.softmax for b in model.blocks] == [True, False, False, False]
    assert (c.heads, c.heads_held, c.first_head) == (8, 2, 2)
    assert (c.kv_heads, c.kv_heads_held) == (2, 1)
    assert (c.linear_heads, c.linear_heads_held) == (8, 2)
    assert (c.experts, c.experts_held, c.first_expert) == (8, 4, 2)
    names = {n: tuple(p.shape) for n, p in model.named_parameters()}
    assert set(model.state_dict()) == set(names)
    assert names["blocks.0.attn.q.weight"] == (64, 2 * 16)
    assert names["blocks.0.attn.k.weight"] == (64, 1 * 16)
    assert names["blocks.0.attn.gate.weight"] == (64, 2 * 16)
    assert names["blocks.1.attn.q_conv"] == (4, 2 * 16)
    assert names["blocks.1.attn.f_a.weight"] == (64, 8)      # whole
    assert names["blocks.1.attn.f_b.weight"] == (8, 2 * 16)  # by head
    assert names["blocks.1.attn.A_log"] == (2,)
    assert names["blocks.1.attn.o.weight"] == (2 * 16, 64)
    assert {tuple(weights[r].shape) == names[p]
            for p, r in FAMILY.name_map(cfg).items()} == {True}
    # all heads held: eight query heads read the two key/value heads
    whole = hm.hybrid_moe_tiny(heads=8, kv_heads=2)
    assert (whole.heads_held, whole.kv_heads_held) == (8, 2)
    # a share is whole key/value groups or lies inside one, and starts on
    # a multiple of itself
    for bad in (dict(heads_held=3), dict(heads_held=2, first_head=1),
                dict(heads=8, kv_heads=3), dict(heads_held=2, first_head=8)):
        with pytest.raises(ValueError):
            hm.hybrid_moe_tiny(**{"heads": 8, "kv_heads": 2, **bad})


def test_a_block_of_each_kind_against_the_reference():
    cfg = ref_cfg()
    model, weights = model_pair(cfg, 3, gates=(-2.0, 0.5))
    x = np.random.default_rng(4).normal(size=(2, 24, 64)).astype(np.float32)
    for i, softmax in ((0, True), (1, False)):
        got, load, stats = model.blocks[i](tensor(x))
        want = jax.jit(ref.block(cfg, MM, softmax))(
            ref._under(weights, f"layers.{i}."), jnp.asarray(x))
        np.testing.assert_allclose(got.numpy(), want, rtol=2e-4, atol=2e-5)
        assert load.numpy().sum() == 2 * 24 * 2
        assert (stats.numpy() == 0).all() == softmax


@pytest.mark.parametrize("gates", [None, (-2.0, 0.5)], ids=["seeded", "slow"])
def test_logits_and_loss_against_the_reference(gates):
    cfg = ref_cfg()
    model, weights = model_pair(cfg, 10, gates)
    batch = rows(11)
    want = jax.jit(lambda w: ref.logits_of(
        cfg, w, ref.hidden(cfg, w, batch[0], MM), MM))(weights)
    np.testing.assert_allclose(model(tensor(batch[0])).numpy(), want,
                               rtol=2e-4, atol=2e-5)
    assert model.expert_load_counts().shape == (4, 8)
    got = latent_moe_loss(model, *map(tensor, batch))
    want = jax.jit(lambda w: ref.loss_part(cfg)(
        w, batch, ref.denominators(batch), MM))(weights)
    assert float(got.numpy()) == pytest.approx(float(want), rel=2e-5)


@pytest.mark.parametrize("recompute", [False, True], ids=["kept", "recompute"])
@pytest.mark.parametrize("gates", [None, (-2.0, 0.5)], ids=["seeded", "slow"])
def test_every_leafs_gradient_against_the_reference(gates, recompute):
    cfg = ref_cfg()
    model, weights = model_pair(cfg, 30, gates, use_recompute=recompute)
    batch = rows(31)
    latent_moe_loss(model, *map(tensor, batch)).backward()
    want = jax.jit(jax.grad(lambda w: ref.loss_part(cfg)(
        w, batch, ref.denominators(batch), MM)))(weights)
    params = dict(model.named_parameters())
    names = FAMILY.name_map(cfg)
    assert set(names.values()) == set(want) == \
        {n for n, _, _ in ref.param_specs(cfg)}
    for prog, name in names.items():
        got, w = params[prog].grad.numpy(), np.asarray(want[name])
        assert np.linalg.norm(w) > 0, name
        assert np.linalg.norm(got - w) <= 2e-3 * np.linalg.norm(w), name


# ---- the compiled step ----------------------------------------------------------
@pytest.fixture(scope="module")
def trained():
    """Three TrainStep calls of the tiny model with recompute, the gauges as
    the registry gives them after the first, and the compiled step's text."""
    cfg = ref_cfg()
    model, weights = model_pair(cfg, 40, use_recompute=True)
    step = pt.TrainStep(model, optim.AdamW(
        parameters=model.parameters(), learning_rate=1e-3,
        multi_precision=True, grad_clip=optim.ClipGradByGlobalNorm(1.0)),
        latent_moe_loss)
    batch = rows(41)
    losses = [float(step(*batch).numpy())]
    snap = obs.snapshot()      # runs the model's publish_gauges
    gauges = (snap["linear_attn.chunk_log_decay_min"],
              snap["linear_attn.beta_mean"], snap["moe.slots_held"])
    held = model.expert_load_counts()[:, 2:6].sum()
    losses += [float(step(*batch).numpy()) for _ in range(2)]
    return cfg, weights, batch, step.compiled().as_text(), losses, gauges, held


def test_the_windows_gauges_follow_the_load_counts():
    """Experts 2-3 of 8 under 320 tokens work on windows of 384 of the 640
    slots' rows: from counts that put 100, 384, 385 and 0 slots on them in
    the four layers, ``publish_gauges`` reads 1, 1, 2 and 0 passes."""
    model, _ = model_pair(ref_cfg(n_routed_experts=2), 42)
    R = model.blocks[0].mlp.routed.window_rows(320)
    assert R == 384 < 640   # twice the even share of 160, in tiles of 128
    counts = np.zeros(model.expert_load.shape, np.int32)
    for layer, held in enumerate((100, 384, 385, 0)):
        counts[-1, layer, 2], counts[-1, layer, 3] = held // 2, held - held // 2
        counts[-1, layer, 7] = 640 - held
    model.expert_load._replace(jnp.asarray(counts))
    model.publish_gauges()
    assert obs.gauge("moe.slots_held").value == 869
    assert obs.gauge("moe.window_passes_max").value == 2
    assert obs.gauge("moe.window_live_share").value == pytest.approx(
        869 / (4 * R))


def test_the_scopes_are_on_forward_and_backward_instructions(trained):
    text = trained[3]
    paths = set(scope_reduce._OP_NAME.findall(text))
    under = {scope: [p for p in paths if scope_paths.holds(p, scope)]
             for scope in ("linear_attn", "gqa_attn")}
    wanted = {"linear_attn": {"linear_nobias", "linear", "short_conv",
                              "kda_gate", "kda_chunk", "gated_rms_norm"},
              "gqa_attn": {"linear_nobias", "sdpa", "sigmoid"}}
    for scope, mine in under.items():
        assert {scope_reduce.phase_of(p) for p in mine} == \
            {"forward", "backward"}, scope
        for phase in ("forward", "backward"):
            ops = {name for p in mine if scope_reduce.phase_of(p) == phase
                   for name, _ in scope_reduce.scopes(p)[:-1]}
            assert wanted[scope] <= ops, (scope, phase, wanted[scope] - ops)
    # no instruction is under both, the rule is under its own alone, and the
    # experts, the norms in front of a sublayer and the head are under neither
    assert not set(under["linear_attn"]) & set(under["gqa_attn"])
    assert not [p for p in under["gqa_attn"] if "kda_chunk" in p]
    assert not [p for p in under["linear_attn"] if "sdpa" in p]
    outside = paths - set(under["linear_attn"]) - set(under["gqa_attn"])
    for op in ("moe_experts", "rms_norm", "cross_entropy_hard", "embedding"):
        assert any(op in p for p in outside), op
        assert not [p for s in under.values() for p in s
                    if op in {n for n, _ in scope_reduce.scopes(p)}], op


def test_the_gauges_read_what_the_references_gates_give(trained):
    """The most negative log-decay a channel runs up over a chunk of 64 (the
    24-token row is one chunk) and the mean beta, over the three linear
    layers, from the reference's own gate equations on its own hidden
    states."""
    cfg, weights, (ids, _), _, losses, (low, beta_mean, slots), held = trained
    eps = cfg["rms_norm_eps"]
    x = weights["embed"][ids]
    lows, betas = [], []
    for i in range(cfg["num_hidden_layers"]):
        p = ref._under(weights, f"layers.{i}.")
        if not ref.is_softmax(cfg, i):
            h = ref.rms(x, eps, p["input_norm"])
            g = -jnp.exp(p["attn.A_log"])[:, None] * jax.nn.softplus(
                MM(MM(h, p["attn.f_a"]), p["attn.f_b"]) +
                p["attn.dt_bias"]).reshape(2, 24, 2, 16)
            lows.append(float(jnp.sum(g, axis=1).min()))
            betas.append(
                float(2 * jax.nn.sigmoid(MM(h, p["attn.beta"])).mean()))
        x = ref.block(cfg, MM, ref.is_softmax(cfg, i))(p, x)
    assert low == pytest.approx(min(lows), rel=1e-4)
    assert -24 < low < -12            # about -0.69 a token over 24 tokens
    assert beta_mean == pytest.approx(np.mean(betas), rel=1e-4)
    assert slots == held and losses[2] < losses[1] < losses[0]


def test_tiny_preset_trains_in_bfloat16_with_a_share_of_the_heads():
    """``chip_smoke.py``'s [hybrid] line at the preset's own size: both kinds
    of layer, heads 2-3 of 4, bfloat16, recompute; the buffer keeps the
    model's type, so one compiled signature serves every step."""
    pt.seed(0)
    model = hm.HybridMoE(hm.hybrid_moe_tiny(heads_held=2, first_head=2,
                                            use_recompute=True))
    model.bfloat16()
    step = pt.TrainStep(model, optim.AdamW(
        parameters=model.parameters(), learning_rate=1e-3,
        multi_precision=True), latent_moe_loss)
    batch = rows(5)
    losses = [float(step(*batch).numpy()) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[2] < losses[0]
    assert len(step._compiled) == 1
    assert model.linear_attn_stats._data.dtype == jnp.bfloat16
    assert float(model.linear_attn_stats._data[0]) < 0
