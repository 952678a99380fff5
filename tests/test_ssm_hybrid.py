"""``SSMHybrid``: Mamba-2 state-space layers beside a grouped-query softmax
layer without positions, dense SwiGLUs, the four multipliers and a head tied
to the embedding, at a small size with seeded float32 weights against the
plain reference (``benchmark/reference/granite4h.py``, which imports nothing
of the program and runs the recurrence token by token): logits, loss, every
leaf's gradient (the tied leaf's the sum of its two uses), three ``TrainStep``
steps against ``reference/_common.train_steps``, recompute on and off, the
eight vocabulary slices' logits, the program scopes on the compiled step's
forward and backward instructions, the gauges. The ops alone:
``test_state_space.py``.

Tolerances. Program and reference both run in float32 on the CPU here and
differ in the order of their sums: the chunked scan adds a chunk's tokens in
one product where the reference steps a token at a time, the program's norms
and attention are its registered ops. That reads 1e-6 to 1e-5 relative on an
activation and grows through three blocks and the embedding's factor of 12:
logits to 2e-4, the loss (a mean of 2 x 24 positions) to 2e-5, a leaf's
gradient to 2e-3 of its norm (``A_log``, ``dt_bias`` and ``D``, sums over
every token of both signs, included). bfloat16 anywhere float32 is stated
moves a leaf's gradient by 1e-2 or more of its norm and the logits by 4e-3:
it would fail both. Three AdamW steps at 1e-3 move a weight by about 3e-3
whatever its gradient's size, so the losses after them agree to 1e-4 and a
leaf's change to 2% of its norm (entries whose gradient is within rounding of
zero step either way).
"""
import functools
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
from benchmark.reference import granite4h as ref  # noqa: E402
from paddle_tpu import obs, optim  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.models.nlp import ssm_hybrid as sh  # noqa: E402
from paddle_tpu.models.nlp.latent_moe import latent_moe_loss  # noqa: E402

MM = rc.matmul_of("float32")
FAMILY = harness.load_module("families", "granite4h")


def ref_cfg():
    """The reference's configuration (the source's keys) at a small size:
    mamba, attention, mamba; 4 state-space heads of 32 over a state of 8 in
    chunks of 8 (a row of 24 is three), 4 query heads over 2 key/value heads
    of 16 at a scale that is not 16^-1/2."""
    cfg = harness.load_json("configs", "granite-4.0-h-micro.json")
    cfg.update(hidden_size=64, intermediate_size=96,
               shared_intermediate_size=96, num_hidden_layers=3,
               layer_types=["mamba", "attention", "mamba"],
               num_attention_heads=4, num_key_value_heads=2,
               attention_multiplier=0.1, mamba_n_heads=4, mamba_d_head=32,
               mamba_d_state=8, mamba_chunk_size=8, vocab_size=256,
               program={})
    return cfg


def tensor(a):
    return Tensor(jnp.asarray(a), _internal=True)


def seeded(cfg, seed, published=False):
    """The reference's float32 weights; with ``published`` every state-space
    layer's ``A_log`` and ``dt_bias`` drawn as Mamba-2 draws them (A in U(1,
    16), Delta log-uniform in [0.001, 0.1] through dt_bias = softplus^-1), so
    that state is carried over the row's chunks."""
    weights = rc.init_weights(ref.param_specs(cfg), seed, jnp.float32)
    if published:
        rng = np.random.default_rng(seed)
        for name in weights:
            shape = weights[name].shape
            if name.endswith("mixer.A_log"):
                weights[name] = jnp.log(jnp.asarray(
                    rng.uniform(1, 16, shape), jnp.float32))
            if name.endswith("mixer.dt_bias"):
                dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), shape))
                weights[name] = jnp.asarray(np.log(np.expm1(dt)), jnp.float32)
    return weights


def model_pair(cfg, seed, published=False, **program):
    """The program's model and the reference's weights, the same numbers."""
    pt.seed(seed)
    model = sh.SSMHybrid(FAMILY.program_config(dict(cfg, program=program)))
    weights = seeded(cfg, seed, published)
    missing, unexpected = model.set_state_dict(
        {prog: tensor(weights[name])
         for prog, name in FAMILY.name_map(cfg).items()})
    assert not missing and not unexpected
    return model, weights


def rows(seed, batch=2, length=24, vocab=256):
    ids = np.random.default_rng(seed).integers(
        0, vocab, (batch, length + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


CFG = ref_cfg()


def ref_loss(weights, batch):
    return ref.loss_part(CFG)(weights, batch, ref.denominators(batch), MM)


# one compile each for the whole file: every test asks with the same shapes
REF_LOGITS = jax.jit(lambda w, ids: ref.logits_of(
    CFG, w, ref.hidden(CFG, w, ids, MM), MM))
REF_LOSS = jax.jit(ref_loss)
REF_GRAD = jax.jit(jax.grad(ref_loss))


# ---- the model --------------------------------------------------------------
def test_the_layer_pattern_the_leaves_and_the_defaults():
    cfg = CFG
    model, weights = model_pair(cfg, 1)
    c = model.cfg
    assert [b.mamba for b in model.blocks] == [True, False, True]
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.attention_multiplier, c.logits_scaling) == (12, 0.22, 0.1, 8)
    names = {n: tuple(p.shape) for n, p in model.named_parameters()}
    assert set(model.state_dict()) == set(names)       # the buffer is not in
    assert "head.weight" not in names          # the head is the embedding
    assert names["blocks.0.mixer.in_proj.weight"] == (64, 128 + 144 + 4)
    assert names["blocks.0.mixer.conv"] == (4, 144)
    assert names["blocks.0.mixer.conv_bias"] == (144,)
    assert names["blocks.0.mixer.A_log"] == names["blocks.0.mixer.D"] == (4,)
    assert names["blocks.0.mixer.norm"] == (128,)
    assert names["blocks.1.mixer.k.weight"] == (64, 2 * 16)
    assert "blocks.1.mixer.gate.weight" not in names
    assert {tuple(weights[r].shape) == names[p]
            for p, r in FAMILY.name_map(cfg).items()} == {True}
    assert sum(int(np.prod(s)) for s in names.values()) == \
        sum(int(np.prod(s)) for _, s, _ in ref.param_specs(cfg))
    # the published pattern where nothing else is said: the sixth of every ten
    whole = sh.SSMHybridConfig()
    assert [i for i, t in enumerate(whole.layer_types) if t == "attention"] \
        == [5, 15, 25, 35]
    assert whole.ssm_heads * whole.ssm_head_dim == 2 * whole.hidden
    with pytest.raises(ValueError, match="kind"):
        sh.ssm_hybrid_tiny(layer_types=("mamba", "full_attention", "mamba"))


@functools.lru_cache(maxsize=None)
def one_pass(published, recompute):
    """One forward and one backward pass of the program's model, for every
    test that reads them: (weights, batch, logits, stats, loss, {reference
    name: the leaf's gradient})."""
    model, weights = model_pair(CFG, 30, published, use_recompute=recompute)
    batch = rows(31)
    logits = model(tensor(batch[0])).numpy()
    stats = [float(t) for t in model.state_space_stats._data]
    loss = latent_moe_loss(model, *map(tensor, batch))
    loss.backward()
    params = dict(model.named_parameters())
    grads = {name: params[prog].grad.numpy()
             for prog, name in FAMILY.name_map(CFG).items()}
    return weights, batch, logits, stats, loss.numpy(), grads


# both without recompute: eager ops compile once a shape for the whole file,
# a recomputed block once a call; under recompute, the benchmark's path:
# ``test_recompute_on_and_off_give_the_same_loss_to_the_bit`` and ``trained``
PASSES = pytest.mark.parametrize(
    "published,recompute", [(False, False), (True, False)],
    ids=["seeded", "published"])


@PASSES
def test_logits_and_loss_against_the_reference(published, recompute):
    weights, batch, logits, (low, dt_mean), loss, _ = one_pass(published,
                                                               recompute)
    want = REF_LOGITS(weights, batch[0])
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-5)
    assert float(loss) == pytest.approx(float(REF_LOSS(weights, batch)),
                                        rel=2e-5)
    # the chunks' log-decays: ln 2 a token at the seeded zeros; at the
    # published ranges at most 16 x 0.1 a token
    if published:
        assert -8 * 1.6 < low < 0 and 0.001 < dt_mean < 0.1
    else:
        assert -8 < low < -3 and 0.6 < dt_mean < 0.8


@PASSES
def test_every_leafs_gradient_against_the_reference(published, recompute):
    weights, batch, _, _, _, grads = one_pass(published, recompute)
    want = REF_GRAD(weights, batch)
    assert set(grads) == set(want) == {n for n, _, _ in ref.param_specs(CFG)}
    for name, got in grads.items():
        w = np.asarray(want[name])
        assert np.linalg.norm(w) > 0, name
        assert np.linalg.norm(got - w) <= 2e-3 * np.linalg.norm(w), name


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses():
    """The reference with the matrix handed in twice, once to look tokens up
    and once as the head: the program's one leaf takes both gradients, and
    neither alone."""
    weights, batch, _, _, _, grads = one_pass(False, False)
    got = grads["embed"]

    def two(lookup, head):
        h = ref.hidden(CFG, dict(weights, embed=lookup), batch[0], MM)
        logits = ref.logits_of(CFG, dict(weights, embed=head), h, MM)
        return rc.ce_sum(logits, batch[1]) / ref.denominators(batch)["lm"]

    as_lookup, as_head = (np.asarray(g) for g in jax.jit(jax.grad(
        two, (0, 1)))(weights["embed"], weights["embed"]))
    both = np.linalg.norm(as_lookup + as_head)
    assert np.linalg.norm(got - as_lookup - as_head) <= 2e-3 * both
    for one in (as_lookup, as_head):
        assert np.linalg.norm(one) > 0.1 * both
        assert np.linalg.norm(got - one) > 0.1 * both


def test_recompute_on_and_off_give_the_same_loss_to_the_bit():
    _, batch, _, _, kept, _ = one_pass(False, False)
    model, _ = model_pair(CFG, 30, use_recompute=True)
    assert model.training
    recomputed = latent_moe_loss(model, *map(tensor, batch)).numpy()
    assert kept == recomputed


def test_the_eight_vocabulary_slices_logits_are_the_uncut_references():
    """The tied matrix vocabulary-parallel over eight chips: each holds 32 of
    256 rows, looks its own ids up and computes its own columns of the logits
    from the state every chip has alike. The first slice's chip (the
    benchmark's: ids from its own rows) gives the state; the eight slices'
    logits side by side are the uncut reference's on the same ids."""
    cfg = CFG
    weights = seeded(cfg, 50)
    ids = np.random.default_rng(51).integers(0, 32, (2, 24)).astype(np.int32)
    want = REF_LOGITS(weights, ids)
    assert want.shape == (2, 24, 256)
    share = dict(cfg, vocab_size=32)
    names = FAMILY.name_map(share)
    model = sh.SSMHybrid(FAMILY.program_config(share))
    parts, state = [], None
    for s in range(8):
        rows_held = dict(weights, embed=weights["embed"][32 * s:32 * s + 32])
        model.set_state_dict({p: tensor(rows_held[r])
                              for p, r in names.items()})
        if s == 0:
            state = model.hidden(tensor(ids))
            np.testing.assert_allclose(
                model(tensor(ids)).numpy(), want[..., :32], rtol=2e-4,
                atol=2e-5)
        parts.append(model._logits(state).numpy())
    np.testing.assert_allclose(np.concatenate(parts, -1), want, rtol=2e-4,
                               atol=2e-5)


# ---- three steps ------------------------------------------------------------
RECIPE = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
          "epsilon": 1e-8, "weight_decay": 0.01, "clip_global_norm": 1.0}


@pytest.fixture(scope="module")
def trained():
    """Three TrainStep calls of the tiny model with recompute on three
    batches, the gauges as the registry gives them after the first, the
    compiled step's text, and what the reference's three steps give."""
    cfg = CFG
    model, weights = model_pair(cfg, 40, use_recompute=True)
    step = pt.TrainStep(model, optim.AdamW(
        parameters=model.parameters(), learning_rate=RECIPE["learning_rate"],
        beta1=RECIPE["beta1"], beta2=RECIPE["beta2"],
        epsilon=RECIPE["epsilon"], weight_decay=RECIPE["weight_decay"],
        multi_precision=True,
        grad_clip=optim.ClipGradByGlobalNorm(RECIPE["clip_global_norm"])),
        latent_moe_loss)
    batches = [rows(41 + i) for i in range(3)]
    losses = [float(step(*batches[0]).numpy())]
    snap = obs.snapshot()      # runs the model's publish_gauges
    gauges = (snap["state_space.chunk_log_decay_min"],
              snap["state_space.dt_mean"])
    losses += [float(step(*b).numpy()) for b in batches[1:]]
    names = FAMILY.name_map(cfg)
    params = dict(model.named_parameters())
    delta = {ref_name: float(np.linalg.norm(
        params[prog].numpy() - np.asarray(weights[ref_name])))
        for prog, ref_name in names.items()}
    specs = ref.param_specs(cfg)
    want = rc.train_steps(ref.loss_part(cfg), ref.denominators,
                          dict(weights), batches, RECIPE,
                          rc.sample_index(specs))
    return cfg, weights, batches, step, losses, gauges, delta, want


def test_three_trainstep_steps_follow_the_references(trained):
    _, _, _, step, losses, _, delta, want = trained
    assert len(step._compiled) == 1
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-4)
    assert set(delta) == set(want["delta_norms"])
    for name, w in want["delta_norms"].items():
        assert w > 0 and abs(delta[name] - w) <= 0.02 * w, name


def test_the_scopes_are_on_forward_and_backward_instructions(trained):
    text = trained[3].compiled().as_text()
    paths = set(scope_reduce._OP_NAME.findall(text))
    under = {scope: [p for p in paths if scope_paths.holds(p, scope)]
             for scope in ("state_space", "gqa_attn")}
    wanted = {"state_space": {"linear_nobias", "short_conv", "ssm_gate",
                              "ssm_chunk", "gated_rms_norm"},
              "gqa_attn": {"linear_nobias", "sdpa"}}
    for scope, mine in under.items():
        assert {scope_reduce.phase_of(p) for p in mine} == \
            {"forward", "backward"}, scope
        for phase in ("forward", "backward"):
            ops = {name for p in mine if scope_reduce.phase_of(p) == phase
                   for name, _ in scope_reduce.scopes(p)[:-1]}
            assert wanted[scope] <= ops, (scope, phase, wanted[scope] - ops)
    # no instruction is under both, the scan is under its own alone, and the
    # MLPs, the norms in front of a sublayer and the head are under neither
    assert not set(under["state_space"]) & set(under["gqa_attn"])
    assert not [p for p in under["gqa_attn"] if "ssm_chunk" in p]
    assert not [p for p in under["state_space"] if "sdpa" in p]
    outside = paths - set(under["state_space"]) - set(under["gqa_attn"])
    for op in ("swiglu", "rms_norm", "cross_entropy_hard", "embedding",
               "matmul"):
        assert any(op in p for p in outside), op
        assert not [p for s in under.values() for p in s
                    if op in {n for n, _ in scope_reduce.scopes(p)}], op
    # the registered op's own name is what ``ssm_scan_ms`` sums
    from paddle_tpu.ops import OP_REGISTRY
    assert {"ssm_chunk", "ssm_gate"} <= set(OP_REGISTRY)
    assert any(scope_reduce.program_op_of(p, set(OP_REGISTRY)) == "ssm_chunk"
               for p in under["state_space"])


def test_the_gauges_read_what_the_references_gates_give(trained):
    """The most negative log-decay a head runs up over a chunk of 8 and the
    mean step size, over the two state-space layers, from the reference's
    own equations on its own hidden states."""
    cfg, weights, batches, _, losses, (low, dt_mean), _, _ = trained
    ids = batches[0][0]
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def gates(weights):
        x = cfg["embedding_multiplier"] * weights["embed"][ids]
        lows, means = [], []
        for i in range(cfg["num_hidden_layers"]):
            p = ref._under(weights, f"layers.{i}.")
            if ref.is_mamba(cfg, i):
                h = ref.rms(x, eps, p["input_norm"])
                dt = jax.nn.softplus(MM(h, p["mixer.in_proj"])[..., -4:] +
                                     p["mixer.dt_bias"])
                steps = -jnp.exp(p["mixer.A_log"]) * dt     # (2, 24, 4)
                lows.append(steps.reshape(2, 3, 8, 4).sum(2).min())
                means.append(dt.mean())
            x = ref.block(cfg, MM, ref.is_mamba(cfg, i))(p, x)
        return jnp.stack(lows), jnp.stack(means)

    lows, means = (np.asarray(a) for a in gates(weights))
    assert low == pytest.approx(min(lows), rel=1e-4)
    assert -8 < low < -4              # about -0.69 a token over 8 tokens
    assert dt_mean == pytest.approx(np.mean(means), rel=1e-4)
    assert losses[2] < losses[0]


# ---- the scan's kernels in the step -----------------------------------------
@pytest.mark.parametrize("kernels", [True, False], ids=["kernels", "dense"])
def test_the_scan_in_the_compiled_step_is_under_ssm_chunk(kernels):
    """The step of a small model whose state-space shapes ``ssm_scan_route``
    takes (2 heads of 64 over a state of 128, chunks of 128, rows of 256)
    with the kernels forced on (the interpreter here): ``ssm_scan_fwd`` and
    ``ssm_scan_bwd`` lie under ``state_space/ssm_chunk`` on forward,
    recomputed and ``transpose(..)`` paths, so ``program_op_of`` still
    answers ``ssm_chunk`` for them and ``ssm_scan_ms`` and
    ``ssm_scan_roofline_pct`` cannot fall to 0; with the kernels off the same
    paths hold the ``lax.scan``'s ``while``. One form either way."""
    from paddle_tpu.ops import OP_REGISTRY, pallas as pk

    cfg = dict(CFG, mamba_n_heads=2, mamba_d_head=64, mamba_d_state=128,
               mamba_chunk_size=128)
    pk.set_enabled(kernels)
    try:
        model, _ = model_pair(cfg, 50, use_recompute=True)
        step = pt.TrainStep(model, optim.AdamW(
            parameters=model.parameters(), learning_rate=1e-3),
            latent_moe_loss)
        loss = float(step(*rows(51, batch=1, length=256)).numpy())
        text = step.compiled().as_text()
    finally:
        pk.set_enabled(None)
    assert np.isfinite(loss)
    paths = set(scope_reduce._OP_NAME.findall(text))
    scan = [p for p in paths if scope_reduce.program_op_of(
        p, set(OP_REGISTRY)) == "ssm_chunk"]
    assert scan and all(scope_paths.holds(p, "state_space") for p in scan)
    assert {scope_reduce.phase_of(p) for p in scan} == {"forward", "backward"}
    fwd, bwd = ([p for p in scan if f"/{name}/" in p]
                for name in ("ssm_scan_fwd", "ssm_scan_bwd"))
    assert bool(fwd) is kernels and bool(bwd) is kernels
    assert all("/state_space/ssm_chunk/" in p for p in fwd + bwd)
    # every ssm_scan_* instruction is the op's, and the loop is the dense
    # path's alone
    assert not [p for p in paths - set(scan) if "ssm_scan_" in p]
    assert any("/while/" in p and "ssm_scan_" not in p for p in scan) \
        is not kernels
    if kernels:
        # forward, the block's recompute inside the backward pass, backward
        assert {scope_reduce.phase_of(p) for p in fwd} == \
            {"forward", "backward"}
        assert any("rematted_computation" in p for p in fwd)
        assert any("transpose(" not in p for p in fwd)
        assert all("transpose(" in p and
                   scope_reduce.phase_of(p) == "backward" for p in bwd)
