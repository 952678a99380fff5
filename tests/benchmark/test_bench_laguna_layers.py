"""The ``laguna`` family's layers and readers alone: thirty-two shares of the
experts and the shared expert counted once add up to the uncut layer; the
reference's blocked window against its own dense band; ``window_costs`` by
hand and the two readers on a tiny table. The family through ``TrainStep``:
``test_bench_laguna.py``, whose tiny configuration this borrows."""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmark import harness, scope_reduce, window_costs
from benchmark.reference import _common as rc
from benchmark.reference import laguna as ref
from test_bench_laguna import tiny_config


# ---- the shares -------------------------------------------------------------------
def test_thirty_two_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Thirty-two experts, top-4, one a share: the routed parts the shares
    give (the program's ``ExpertMLP``, built from this family's config, told
    which expert it holds) plus the shared expert, which every chip computes
    alike, counted once, are the uncut sparse layer of the reference. float32
    on the CPU: 1e-5 of the output's scale."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.nlp import latent_moe as lm

    cfg = tiny_config(num_experts=32, n_routed_experts=32,
                      num_experts_published=32, first_routed_expert=0,
                      num_experts_per_tok=4, program={})
    specs = [(n.split(".", 2)[2], s, i) for n, s, i in ref._layer_specs(cfg, 1)
             if ".mlp." in n]
    p = rc.init_weights(specs, 5, jnp.float32)
    p = {k: v * 8.0 for k, v in p.items()}     # outputs of order 1
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 24, 64)),
                    jnp.float32)
    mm = rc.matmul_of("float32")
    whole = ref.shared_part(p, x, mm) + ref.routed_part(cfg, p, x, mm)
    family = harness.load_module("families", "laguna")
    total, total_ref, slots = ref.shared_part(p, x, mm), 0.0, 0
    for first in range(32):
        share = dict(cfg, num_experts=1, first_routed_expert=first)
        part = {k: (v[first:first + 1] if ".experts." in k else v)
                for k, v in p.items()}
        total_ref = total_ref + ref.routed_part(share, part, x, mm)
        pcfg = family.program_config(share)
        assert (pcfg.experts, pcfg.experts_held, pcfg.first_expert,
                pcfg.router_score) == (32, 1, first, "softmax")
        layer = lm.ExpertMLP(pcfg).routed
        params = dict(layer.named_parameters())
        for prog, name in family._EXPERTS.items():
            if "routed" in prog:
                params[prog[len("mlp.routed."):]].set_value(
                    np.asarray(part[name]))
        y, load = layer(Tensor(x, _internal=True))
        total = total + y._data
        slots += int(load.numpy()[first])
        assert load.numpy().sum() == 2 * 24 * 4    # it routes over all 32
    scale = float(jnp.abs(whole).max())
    assert scale > 0.1
    np.testing.assert_allclose(total, whole, atol=1e-5 * scale, rtol=1e-5)
    np.testing.assert_allclose(total_ref + ref.shared_part(p, x, mm), whole,
                               atol=1e-5 * scale, rtol=1e-5)
    assert slots == 2 * 24 * 4      # every slot landed in exactly one share


def test_the_references_blocked_window_is_its_dense_band(monkeypatch):
    """Rows of 8,192 go through the windowed layers 512 queries at a time;
    here 48 in blocks of 16 under a window of 20 against the whole band."""
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 3, 48, 8)), jnp.float32)
               for _ in range(3))
    mm = rc.matmul_of("float32")
    whole = ref.window_attention(q, k, v, 20, 0.3, mm)    # one block of 48
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    blocked = ref.window_attention(q, k, v, 20, 0.3, mm)
    d = np.arange(48)[:, None] - np.arange(48)[None, :]
    s = jnp.where((d >= 0) & (d < 20), mm(q, jnp.swapaxes(k, -1, -2)) * 0.3,
                  -jnp.inf)
    want = mm(jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(whole, want, atol=2e-6)
    np.testing.assert_allclose(blocked, want, atol=2e-6)
    grads = jax.grad(lambda k: jnp.sum(ref.window_attention(
        q, k, v, 20, 0.3, mm) ** 2))(k)
    want_g = jax.grad(lambda k: jnp.sum(mm(jax.nn.softmax(jnp.where(
        (d >= 0) & (d < 20), mm(q, jnp.swapaxes(k, -1, -2)) * 0.3, -jnp.inf),
        -1), v) ** 2))(k)
    np.testing.assert_allclose(grads, want_g, atol=2e-5)


# ---- the readers ------------------------------------------------------------------
SWA_LINE = (
    '  %swa_bwd_dkv_w512.3 = (bf16[72,8192,128]{2,1,0:T(8,128)(2,1)}, '
    'bf16[72,8192,128]{2,1,0:T(8,128)(2,1)}) custom-call(%a, %b, %c, %d, %e, '
    '%f), custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{bf16[72,8192,128]{2,1,0}, bf16[72,8192,128]{2,1,0}, '
    'bf16[72,8192,128]{2,1,0}, bf16[72,8192,128]{2,1,0}, f32[72,1,8192]'
    '{2,1,0}, f32[72,1,8192]{2,1,0}}, metadata={op_name="jit(pure)/backward/'
    'transpose(jvp(recompute))/window_attn/sdpa/swa_bwd_dkv_w512/pallas_call"}')


def test_swa_flops_against_a_count_by_hand():
    """72 heads of 8,192 under a window of 512: the first 512 queries see 1
    .. 512 keys, the other 7,680 see 512; a score costs the call's widths."""
    scores = sum(range(1, 513)) + 7680 * 512
    assert window_costs.band_scores(8192, 512) == scores == 4_063_488
    assert window_costs.band_scores(256, 512) == 256 * 257 / 2     # causal
    for call, width in (("fwd", 256), ("bwd_dq", 256), ("bwd_dkv", 384)):
        line = SWA_LINE.replace("bwd_dkv", call)
        assert window_costs.swa_flops(f"swa_{call}_w512", line) == \
            2.0 * 72 * scores * width
    # an eighth of what the causal call of the same shape must do, and a bit
    from benchmark import kernel_costs
    causal = kernel_costs.flash_flops(
        "flash_fwd_causal", SWA_LINE.replace("swa_bwd_dkv_w512", "flash_fwd"))
    assert 0.121 < window_costs.swa_flops(
        "swa_fwd_w512", SWA_LINE) / causal < 0.1212


def test_the_two_readers_on_a_tiny_table(monkeypatch):
    text = (
        'ENTRY %main (p: f32[8]) -> f32[8] {\n'
        '  %a.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(pure)/'
        'forward/jvp(recompute)/window_attn/linear_nobias/dot_general"}\n'
        + SWA_LINE.replace("%swa_bwd_dkv_w512.3", "%b.2") + '\n'
        '  %c.3 = f32[8]{0} add(%a.1, %p), metadata={op_name="jit(pure)/'
        'forward/jvp(recompute)/gqa_attn/sdpa/flash_fwd_causal/pallas_call"}\n'
        '  ROOT %e.5 = f32[8]{0} add(%c.3, %p), metadata={op_name="jit(pure)/'
        'optimizer/mul"}\n}\n')

    def row(instruction, phase, op, kernel, ms):
        return scope_reduce.Row(instruction, phase, op, kernel, ms, 1.0,
                                False)

    table = [row("a.1 fusion", "forward", "linear_nobias", None, 2.0),
             row("b.2", "backward", "sdpa", "swa_bwd_dkv_w512", 4.0),
             row("c.3 fusion", "forward", "sdpa", "flash_fwd_causal", 5.0),
             row("e.5 fusion", "optimizer", None, None, 11.0)]
    lines = {"b.2": SWA_LINE}
    window = types.SimpleNamespace(compiled_text=text)
    window.scope_table = (table, lines)
    monkeypatch.setattr(harness, "peaks",
                        lambda kind: {"bf16_flops_per_s": 197e12})
    whole = harness.load_module("layer_metrics", "window_attention_ms")
    share = harness.load_module("layer_metrics", "swa_roofline_pct")
    assert whole.read(window) == 6.0
    need = 2.0 * 72 * 4_063_488 * 384
    assert share.read(window) == pytest.approx(
        100.0 * need / 197e12 / 4e-3)
    assert (whole.LAYER, whole.UNIT) == ("window attention", "ms")
    assert (share.LAYER, share.UNIT, share.KERNELS) == ("kernels", "%", "swa_")
    assert not hasattr(whole, "reports") and not hasattr(share, "reports")
    # a step with no windowed layer reads 0, not nothing: both are owed in
    # every training cell, the seven that were there too
    window.compiled_text = text.replace("window_attn", "mtp")
    window.scope_table = ([r for r in table if not (
        r.kernel or "").startswith("swa_")], lines)
    assert whole.read(window) == 0.0 and share.read(window) == 0.0
    # a program that names no phase has nothing under a scope to read
    window.scope_table = (None, {})
    assert whole.read(window) is None
