"""``LFM2MoE``: double-gated short-convolution layers beside a grouped-query
softmax layer with an RMS norm on every head of q and k and a rotary
embedding, a leading dense SwiGLU, sigmoid-routed experts without a shared
one and a head tied to the embedding, at a small size with seeded float32
weights against the plain reference (``benchmark/reference/lfm2.py``, which
imports nothing of the program): logits, loss, every leaf's gradient (the
tied leaf's the sum of its two uses), the four expert-parallel shares against
the uncut expert layer, the three kinds of head a stack has, and
``GatedGroupedAttention`` with the norm off. The op alone:
``test_gated_short_conv.py``; three ``TrainStep`` steps in bfloat16, the
program scopes and the benchmark's entries:
``tests/benchmark/test_bench_lfm2.py``.

Tolerances. Program and reference both run in float32 on the CPU here and
differ in the order of their sums (the program's norms, rotation, attention
and expert layer are its registered ops; the experts' sum goes slot by slot
where the reference's goes expert by expert). That reads 1e-7 to 1e-6
relative on an activation through three blocks: logits to 2e-5 of their
scale, the loss (a mean of 2 x 24 positions) to 2e-6, a leaf's gradient to
2e-5 of its norm. The reference with bfloat16 operands in its matrix
products (``matmul_of("bfloat16")``: what computing in the precision below
would do) moves the logits by 3e-3 of their scale and a leaf's gradient by
1e-2 or more of its norm: it fails both, by a hundred times
(``test_bfloat16_operands_in_the_reference_fail_both_tolerances``).
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
from benchmark import harness  # noqa: E402
from benchmark.reference import _common as rc  # noqa: E402
from benchmark.reference import lfm2 as ref  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.models.nlp import decoder_stack as ds  # noqa: E402
from paddle_tpu.models.nlp import lfm2_moe as lf  # noqa: E402
from paddle_tpu.models.nlp import laguna_moe as lg  # noqa: E402
from paddle_tpu.models.nlp import ssm_hybrid as sh  # noqa: E402
from paddle_tpu.models.nlp.latent_moe import latent_moe_loss  # noqa: E402
from paddle_tpu.nn import functional as F  # noqa: E402

MM = rc.matmul_of("float32")
FAMILY = harness.load_module("families", "lfm2")
LOGITS_TOL, GRAD_TOL = 2e-5, 2e-5


def ref_cfg(**kw):
    """The reference's configuration (the source's keys) at a small size: a
    dense convolution layer, then an attention layer and a convolution
    layer over experts; 4 query heads over 2 key/value heads of 16; top-2 of
    8 experts, all held."""
    cfg = harness.load_json("configs", "lfm2-8b-a1b.json")
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_hidden_layers=3,
               layer_types=["conv", "full_attention", "conv"],
               num_attention_heads=4, num_key_value_heads=2, num_experts=8,
               num_experts_published=8, num_experts_per_tok=2,
               vocab_size=256, program={})
    cfg.update(kw)
    return cfg


def tensor(a):
    return Tensor(jnp.asarray(a), _internal=True)


def model_pair(cfg, seed, **program):
    """The program's model and the reference's weights, the same numbers."""
    pt.seed(seed)
    model = lf.LFM2MoE(FAMILY.program_config(dict(cfg, program=program)))
    weights = rc.init_weights(ref.param_specs(cfg), seed, jnp.float32)
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


def ref_loss(weights, batch, mm=MM):
    return ref.loss_part(CFG)(weights, batch, ref.denominators(batch), mm)


def ref_logits(weights, ids, mm=MM):
    return ref.logits_of(CFG, weights, ref.hidden(CFG, weights, ids, mm), mm)


# ---- the model --------------------------------------------------------------
def test_the_layer_pattern_the_leaves_and_the_defaults():
    model, weights = model_pair(CFG, 1)
    assert [b.kind for b in model.blocks] == ["conv", "full_attention",
                                              "conv"]
    assert [b.dense for b in model.blocks] == [True, False, False]
    names = {n: tuple(p.shape) for n, p in model.named_parameters()}
    assert set(model.state_dict()) == set(names)     # the buffers are not in
    assert "head.weight" not in names and model.head is None   # tied
    assert names["blocks.0.op.in_proj.weight"] == (64, 192)
    assert names["blocks.0.op.conv"] == (3, 64)
    assert names["blocks.0.op.out_proj.weight"] == (64, 64)
    assert names["blocks.1.op.q_norm.weight"] == \
        names["blocks.1.op.k_norm.weight"] == (16,)
    assert names["blocks.1.op.k.weight"] == (64, 2 * 16)
    assert "blocks.1.op.gate.weight" not in names
    assert "blocks.1.mlp.shared.gate.weight" not in names   # no shared expert
    assert names["blocks.1.mlp.routed.router"] == (64, 8)
    assert {tuple(weights[r].shape) == names[p]
            for p, r in FAMILY.name_map(CFG).items()} == {True}
    assert sum(int(np.prod(s)) for s in names.values()) == \
        sum(int(np.prod(s)) for _, s, _ in ref.param_specs(CFG))
    # the published pattern where nothing else is said: 18 to 6
    whole = lf.LFM2MoEConfig()
    assert [i for i, t in enumerate(whole.layer_types)
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert (whole.dense_layers, whole.experts, whole.top_k, whole.conv_size,
            whole.rope_theta, whole.rms_eps) == (2, 32, 4, 3, 1e6, 1e-5)
    assert lf.LFM2MoEConfig(layers=6).layer_types == whole.layer_types[:6]
    with pytest.raises(ValueError, match="kind"):
        lf.lfm2_moe_tiny(layer_types=("conv", "attention", "conv", "conv"))
    assert model.expert_load._data.shape == (ds.LOAD_HISTORY, 2, 8)


@functools.lru_cache(maxsize=None)
def one_pass():
    """One forward and one backward pass of the program's model, for every
    test that reads them: (weights, batch, logits, loss, {reference name:
    the leaf's gradient}, the experts' load)."""
    model, weights = model_pair(CFG, 30)
    batch = rows(31)
    logits = model(tensor(batch[0])).numpy()
    loss = latent_moe_loss(model, *map(tensor, batch))
    loss.backward()
    params = dict(model.named_parameters())
    grads = {name: params[prog].grad.numpy()
             for prog, name in FAMILY.name_map(CFG).items()}
    return weights, batch, logits, loss.numpy(), grads, \
        model.expert_load_counts()


def test_logits_and_loss_against_the_reference():
    weights, batch, logits, loss, _, load = one_pass()
    want = np.asarray(jax.jit(ref_logits)(weights, batch[0]))
    scale = float(np.abs(want).max())
    assert scale > 0.5
    assert np.abs(logits - want).max() <= LOGITS_TOL * scale
    assert float(loss) == pytest.approx(float(jax.jit(ref_loss)(
        weights, batch)), rel=2e-6)
    # every slot is counted: 2 x 24 tokens x top-2 a layer, two layers
    assert load.shape == (2, 8) and (load.sum(axis=1) == 96).all()


def test_every_leafs_gradient_against_the_reference():
    weights, batch, _, _, grads, _ = one_pass()
    want = jax.jit(jax.grad(ref_loss))(weights, batch)
    assert set(grads) == set(want) == {n for n, _, _ in ref.param_specs(CFG)}
    for name, got in grads.items():
        w = np.asarray(want[name])
        assert np.linalg.norm(w) > 0, name
        assert np.linalg.norm(got - w) <= GRAD_TOL * np.linalg.norm(w), name


def test_bfloat16_operands_in_the_reference_fail_both_tolerances():
    """What the tolerances above are worth: the reference itself, with its
    matrix products' operands rounded to bfloat16, is outside both."""
    weights, batch, logits, _, grads, _ = one_pass()
    low = rc.matmul_of("bfloat16")
    rounded = np.asarray(jax.jit(functools.partial(ref_logits, mm=low))(
        weights, batch[0]))
    scale = float(np.abs(rounded).max())
    assert np.abs(logits - rounded).max() > 20 * LOGITS_TOL * scale
    want = jax.jit(jax.grad(functools.partial(ref_loss, mm=low)))(weights,
                                                                  batch)
    off = {name: np.linalg.norm(got - np.asarray(want[name])) /
           np.linalg.norm(np.asarray(want[name]))
           for name, got in grads.items()}
    assert min(off.values()) > 20 * GRAD_TOL, off


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses():
    """The reference with the matrix handed in twice, once to look tokens up
    and once as the head: the program's one leaf takes both gradients, and
    neither alone. A tied head over a stack WITH routed experts."""
    weights, batch, _, _, grads, _ = one_pass()
    got = grads["embed"]

    def two(lookup, head):
        h = ref.hidden(CFG, dict(weights, embed=lookup), batch[0], MM)
        logits = ref.logits_of(CFG, dict(weights, embed=head), h, MM)
        return rc.ce_sum(logits, batch[1]) / ref.denominators(batch)["lm"]

    as_lookup, as_head = (np.asarray(g) for g in jax.jit(jax.grad(
        two, (0, 1)))(weights["embed"], weights["embed"]))
    both = np.linalg.norm(as_lookup + as_head)
    assert np.linalg.norm(got - as_lookup - as_head) <= GRAD_TOL * both
    for one in (as_lookup, as_head):
        assert np.linalg.norm(one) > 0.1 * both
        assert np.linalg.norm(got - one) > 0.1 * both


@functools.lru_cache(maxsize=None)
def tiny_stacks():
    """One stack of each kind of head: (untied over experts, tied without
    experts, tied over experts)."""
    return (lg.LagunaMoE(lg.laguna_moe_tiny()),
            sh.SSMHybrid(sh.ssm_hybrid_tiny()),
            lf.LFM2MoE(lf.lfm2_moe_tiny()))


def test_the_three_kinds_of_head_a_stack_has():
    untied, tied_plain, tied_experts = tiny_stacks()
    assert isinstance(untied, ds.ExpertStack) and untied.head is not None
    assert not isinstance(tied_plain, ds.ExpertStack) and \
        not hasattr(tied_plain, "head")
    assert isinstance(tied_experts, ds.ExpertStack) and \
        tied_experts.head is None and hasattr(tied_experts, "expert_load")
    assert "head.weight" in dict(untied.named_parameters())
    for model in (tied_plain, tied_experts):
        assert "head.weight" not in dict(model.named_parameters())
    # the tie is the stack's: a configuration's key, no family's code
    assert lf.LFM2MoEConfig.tie_head and not hasattr(lg.LagunaMoEConfig,
                                                     "tie_head")
    h = tensor(np.random.default_rng(2).normal(size=(1, 3, 64)).astype(
        np.float32))
    np.testing.assert_array_equal(
        tied_experts._logits(h).numpy(), tied_experts._tied_logits(h).numpy())
    for name in ("untied over experts", "tied without experts",
                 "tied over experts"):
        assert name in " ".join(ds.__doc__.replace("*", "").split()), name


# ---- the share ----------------------------------------------------------------
def test_the_four_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """Four chips share an expert layer of 32 top-4 experts: each routes every
    token over all 32 and computes its own 8 experts' part. There is no
    shared expert, so nothing is computed alike on every chip: the four
    parts, summed, are the uncut reference's layer; and each part alone is
    the reference's share of the same experts."""
    cfg = ref_cfg(num_experts=32, num_experts_published=32,
                  num_experts_per_tok=4)
    weights = rc.init_weights(ref.param_specs(cfg), 60, jnp.float32)
    p = ref._under(weights, "layers.2.")     # a convolution layer's experts
    x = jnp.asarray(np.random.default_rng(61).normal(size=(2, 24, 64)),
                    jnp.float32)
    whole = np.asarray(ref.routed_part(cfg, p, x, MM))
    assert np.abs(whole).max() > 1e-3
    total, loads = 0.0, []
    for first in (0, 8, 16, 24):
        share = dict(cfg, num_experts=8, first_routed_expert=first)
        layer = ds.ExpertMLP(FAMILY.program_config(share))
        assert layer.shared is None
        held = {k: p["mlp.experts." + k][first:first + 8]
                for k in ("gate", "up", "down")}
        layer.routed.router._replace(p["mlp.router"])
        for k, w in held.items():
            getattr(layer.routed, "experts_" + k)._replace(w)
        part, load = layer(tensor(x))
        want = ref.routed_part(share, {
            "mlp.router": p["mlp.router"],
            **{"mlp.experts." + k: w for k, w in held.items()}}, x, MM)
        np.testing.assert_allclose(part.numpy(), want, rtol=1e-4, atol=2e-8)
        total = total + part.numpy()
        loads.append(load.numpy())
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=2e-8)
    # every chip counts the same routing: all 32 experts, 2 x 24 x 4 slots
    assert all((load == loads[0]).all() for load in loads)
    assert loads[0].shape == (32,) and loads[0].sum() == 192


# ---- the attention sublayer's new option ----------------------------------------
def test_qk_norm_off_leaves_the_attention_sublayer_as_it_was():
    cfg = lf.lfm2_moe_tiny()
    pt.seed(5)
    plain = ds.GatedGroupedAttention(cfg, heads=4, kv_heads=2, gated=False,
                                     rope=(16, 1e6))
    assert plain.q_norm is None and plain.k_norm is None
    assert [n for n, _ in plain.named_parameters()] == [
        "q.weight", "k.weight", "v.weight", "o.weight"]
    normed = ds.GatedGroupedAttention(cfg, heads=4, kv_heads=2, gated=False,
                                      rope=(16, 1e6), qk_norm=1e-5)
    assert [n for n, _ in normed.named_parameters()] == [
        "q.weight", "k.weight", "v.weight", "o.weight", "q_norm.weight",
        "k_norm.weight"]
    normed.set_state_dict({n: p for n, p in plain.named_parameters()})
    x = tensor(np.random.default_rng(6).normal(size=(2, 12, 64)).astype(
        np.float32))

    def by_hand(layer, norm):
        """The sublayer's equations from its own leaves."""
        def heads(t, n):
            return t.reshape([2, 12, n, 16]).transpose([0, 2, 1, 3])
        q, k = heads(layer.q(x), 4), heads(layer.k(x), 2)
        if norm:
            q, k = (F.rms_norm(t, w.weight, 1e-5)
                    for t, w in ((q, layer.q_norm), (k, layer.k_norm)))
        cos, sin = F.rotary_cos_sin(12, 16, 1e6)
        att = F.sdpa_bhld(F.rotary(q, cos, sin), F.rotary(k, cos, sin),
                          heads(layer.v(x), 2), is_causal=True, scale=0.25)
        return layer.o(att.transpose([0, 2, 1, 3]).reshape([2, 12, 64]))

    np.testing.assert_array_equal(plain(x).numpy(),
                                  by_hand(plain, False).numpy())
    np.testing.assert_array_equal(normed(x).numpy(),
                                  by_hand(normed, True).numpy())
    # unit-norm heads attend differently: the option is not a no-op
    assert np.abs(normed(x).numpy() - plain(x).numpy()).max() > 1e-4
    # every other family's call builds no norm
    for model in tiny_stacks()[:2]:
        for block in model.blocks:
            mixer = getattr(block, "attn", None) or block.mixer
            if isinstance(mixer, ds.GatedGroupedAttention):
                assert mixer.q_norm is None and mixer.k_norm is None
