"""Which calls go to a pallas kernel and which to the dense path.

End to end: a tiny GPT trains with the kernels force-enabled (interpret on
CPU) as the LIVE code path — layernorm, flash attention, and softmax-CE all
route through ops/pallas/ — and the first-step loss matches the dense path.
(Compiled-mode TPU validation is chip_smoke.py's kernels phase.)

Case by case: one table a kernel of (shapes, options, mesh) -> kernel or
dense, through the public call, read off the ``tpu_custom_call``s of the
call lowered for a TPU platform (nothing is compiled and libtpu is not
loaded). The rules live in the kernels' modules (``flash_route``,
``softmax_ce_route``, ``layer_norm_route``, ``hc_route``, ``rotary_route``,
``ssm_scan_route``; the expert layer's, ``expert_route``, in ``dist/moe.py``);
a PR that changes what a kernel takes edits that rule and its table here.
Beside them: the flash kernels' calls that a recomputed decoder's step holds
(the forward once a layer: the region keeps its outputs)."""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import distributed as dist
from paddle_tpu import optim
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.dist import moe
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import pallas as pk
from paddle_tpu.models.nlp import bert, hybrid_moe as hm, laguna_moe as lg, \
    latent_moe as lm
from paddle_tpu.models.nlp.gpt import GPT, GPTConfig, gpt_loss

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def test_pallas_routing_end_to_end(monkeypatch):
    pk.set_enabled(True)   # force the pallas routing; auto_interpret -> CPU
    # the flash route's floor keeps L=128 dense on the chip; lowered here so
    # that the interpreter's short model still runs all three kernels
    monkeypatch.setattr(fa, "MIN_STEP_SCORES", 128 * 128)
    try:
        _run()
    finally:
        pk.set_enabled(None)


def _run():
    pt.seed(0)
    # shapes chosen to satisfy the pallas gates: L%128==0, D%64==0, V%128==0
    cfg = GPTConfig(vocab_size=512, hidden=128, layers=2, heads=2, max_seq=128,
                    dropout=0.0)
    model = GPT(cfg)
    opt = optim.AdamW(parameters=model.parameters(), learning_rate=3e-3,
                      grad_clip=optim.ClipGradByGlobalNorm(1.0))
    step = pt.TrainStep(model, opt, gpt_loss)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, 128)).astype("int32")
    labels = np.roll(ids, -1, axis=1).astype("int32")

    losses = []
    for i in range(8):
        losses.append(float(np.asarray(step(ids, labels)._data)))
    print("losses:", [round(x, 3) for x in losses])
    assert all(np.isfinite(x) for x in losses), losses
    assert losses[-1] < losses[0] - 0.5, f"no learning: {losses[0]} -> {losses[-1]}"

    # parity: same model, pallas off, must agree on the loss value closely
    pk.set_enabled(False)
    pt.seed(0)
    model2 = GPT(cfg)
    opt2 = optim.AdamW(parameters=model2.parameters(), learning_rate=3e-3,
                       grad_clip=optim.ClipGradByGlobalNorm(1.0))
    step2 = pt.TrainStep(model2, opt2, gpt_loss)
    l_dense = float(np.asarray(step2(ids, labels)._data))
    assert abs(l_dense - losses[0]) < 1e-2, (l_dense, losses[0])
    print(f"pallas-vs-dense first-step loss parity: {losses[0]:.4f} vs {l_dense:.4f}")
    print("DRIVE OK")


# ---- the tables ---------------------------------------------------------------
KERNEL, DENSE = True, False
DP4, DP2_TP2 = {"data": 4}, {"data": 2, "model": 2}


@pytest.fixture
def lowered_for_tpu(monkeypatch):
    """Kernels routed as on a TPU backend and lowered through Mosaic, not
    the interpreter; returns ``takes(mesh_axes, fn, *structs)``: whether
    ``fn`` of tensors of those shapes holds a kernel call."""
    pk.set_enabled(True)
    monkeypatch.setattr(pk, "auto_interpret", lambda: False)

    def takes(mesh_axes, fn, *structs):
        if mesh_axes is not None:
            n = int(np.prod(list(mesh_axes.values())))
            dist.init_mesh(mesh_axes, devices=jax.devices()[:n])

        def pure(*arrays):
            return fn(*(Tensor(a, _internal=True) for a in arrays))._data

        traced = jax.jit(pure).trace(*structs)
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
        # for a test that asks which kernels they were, and how many calls
        takes.text, takes.jaxpr = text, traced.jaxpr
        return "tpu_custom_call" in text

    yield takes
    pk.set_enabled(None)
    dist.set_mesh(None)


def _struct(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


# what a row's call shows of its mask: shape (of B, H, Lq, Lk), dtype, and
# whether the tensor wants a gradient (``stop_gradient=False``)
KEY = lambda b, h, lq, lk: (b, 1, 1, lk)          # noqa: E731
MASKS = {
    "key": (KEY, jnp.float32, False),       # BERT's padding mask
    "key_1": (lambda b, h, lq, lk: (1, 1, 1, lk), jnp.float32, False),
    "key_bool": (KEY, jnp.bool_, False),
    "key_bf16": (KEY, jnp.bfloat16, False),
    "key_wants_grad": (KEY, jnp.float32, True),
    "key_int": (KEY, jnp.int32, False),
    "per_query": (lambda b, h, lq, lk: (b, 1, lq, lk), jnp.float32, False),
    "per_head": (lambda b, h, lq, lk: (b, h, 1, lk), jnp.float32, False),
    "rank_2": (lambda b, h, lq, lk: (lq, lk), jnp.float32, False),
}


@pytest.mark.parametrize("q,lk,dv,causal,mask,dropout,mesh,want", [
    # the cells
    ((16, 12, 1024, 64), 1024, 64, True, None, 0.0, None, KERNEL),
    ((64, 12, 1024, 64), 1024, 64, True, None, 0.0, DP4, KERNEL),
    ((24, 12, 512, 64), 512, 64, False, "key", 0.0, None, KERNEL),
    ((128, 12, 128, 64), 128, 64, False, "key", 0.0, None, DENSE),
    ((1, 32, 4096, 192), 4096, 128, True, None, 0.0, None, KERNEL),
    # BERT's shapes without their mask, other widths, unequal lengths
    ((24, 12, 512, 64), 512, 64, False, None, 0.0, None, KERNEL),
    ((2, 4, 256, 64), 256, 256, False, None, 0.0, None, KERNEL),
    ((2, 4, 256, 64), 1152, 64, True, None, 0.0, None, KERNEL),
    ((2, 4, 2048, 64), 1024, 64, False, None, 0.0, None, KERNEL),
    ((8, 4, 256, 64), 256, 64, True, None, 0.0, DP2_TP2, KERNEL),
    ((3, 4, 256, 64), 256, 64, True, None, 0.0, DP2_TP2, KERNEL),
    # the masks that are a bias on the keys, alone and under a mesh
    ((24, 12, 512, 64), 512, 64, False, "key_1", 0.0, None, KERNEL),
    ((24, 12, 512, 64), 512, 64, False, "key_bf16", 0.0, None, KERNEL),
    ((24, 12, 512, 64), 512, 64, False, "key", 0.0, DP4, KERNEL),
    ((24, 12, 512, 64), 512, 64, False, "key", 0.0, DP2_TP2, KERNEL),
    ((24, 12, 512, 64), 512, 64, False, "key_1", 0.0, DP2_TP2, KERNEL),
    ((3, 4, 256, 64), 512, 64, False, "key", 0.0, DP2_TP2, KERNEL),
    # the refusals
    ((2, 4, 2048, 64), 1024, 64, True, None, 0.0, None, DENSE),
    ((2, 4, 1000, 64), 1000, 64, True, None, 0.0, None, DENSE),
    ((2, 4, 256, 64), 120, 64, False, None, 0.0, None, DENSE),
    ((2, 4, 256, 320), 256, 64, True, None, 0.0, None, DENSE),
    ((2, 4, 256, 192), 256, 96, True, None, 0.0, None, DENSE),
    ((16, 12, 1024, 64), 1024, 64, True, None, 0.1, None, DENSE),
    ((16, 12, 1024, 64), 1024, 64, True, "key", 0.0, None, DENSE),
    ((24, 12, 512, 64), 512, 64, False, "key", 0.1, None, DENSE),
    ((24, 12, 512, 64), 512, 64, False, "key_wants_grad", 0.0, None, DENSE),
    ((24, 12, 512, 64), 512, 64, False, "key_int", 0.0, None, DENSE),
    ((24, 12, 512, 64), 512, 64, False, "key_bool", 0.0, None, DENSE),
    ((24, 12, 512, 64), 512, 64, False, "per_query", 0.0, None, DENSE),
    ((24, 12, 512, 64), 512, 64, False, "per_head", 0.0, None, DENSE),
    ((24, 12, 512, 64), 512, 64, False, "rank_2", 0.0, None, DENSE),
    # too few scores a grid step, mask or no mask
    ((128, 12, 128, 64), 128, 64, False, None, 0.0, None, DENSE),
    ((8, 4, 128, 64), 128, 64, True, None, 0.0, DP2_TP2, DENSE),
    ((2, 4, 128, 64), 384, 64, True, None, 0.0, None, DENSE),
], ids=["gpt2s", "gpt2s_dp4", "bert512_masked", "bert128_masked", "xing4",
        "bert512_no_mask", "dv256", "causal_lk_longer", "lk_shorter",
        "dp2_tp2", "dp2_tp2_odd_batch", "mask_of_one_row", "bf16_key_mask",
        "masked_dp4", "masked_dp2_tp2", "mask_of_one_row_dp2_tp2",
        "masked_dp2_tp2_odd_batch",
        "causal_lk_shorter", "l1000", "lk120", "d320", "dv96", "dropout",
        "causal_masked", "masked_dropout", "mask_wants_a_gradient",
        "integer_mask", "boolean_key_mask", "mask_a_query", "mask_a_head",
        "mask_of_rank_2", "bert128_no_mask", "l128_dp2_tp2", "l128_by_384"])
def test_attention_routing(lowered_for_tpu, q, lk, dv, causal, mask, dropout,
                           mesh, want):
    b, h, lq, _ = q
    structs = [_struct(q), _struct((b, h, lk, q[3])), _struct((b, h, lk, dv))]
    wants_grad = False
    if mask:
        shape, dtype, wants_grad = MASKS[mask]
        structs.append(_struct(shape(b, h, lq, lk), dtype))

    def call(q, k, v, attn_mask=None):
        if wants_grad:
            attn_mask.stop_gradient = False
        return F.sdpa_bhld(q, k, v, attn_mask=attn_mask, is_causal=causal,
                           dropout_p=dropout)

    assert lowered_for_tpu(mesh, call, *structs) is want


SWA, CAUSAL = "swa_fwd_w", "flash_fwd_causal"


@pytest.mark.parametrize("q,lk,window,mask,dropout,mesh,want", [
    # the cell: 72 heads of 8,192 under a window of 512
    ((1, 72, 8192, 128), 8192, 512, None, 0.0, None, SWA),
    # other windows, widths, a value head of its own, a mesh
    ((2, 4, 1024, 64), 1024, 100, None, 0.0, None, SWA),
    ((2, 4, 1024, 64), 1024, 1023, None, 0.0, None, SWA),
    ((1, 4, 4096, 192), 4096, 512, None, 0.0, None, SWA),
    ((8, 4, 512, 64), 512, 128, None, 0.0, DP2_TP2, SWA),
    # a window that reaches the whole row is the causal call
    ((2, 4, 1024, 64), 1024, 1024, None, 0.0, None, CAUSAL),
    ((2, 4, 1024, 64), 1024, 5000, None, 0.0, None, CAUSAL),
    ((2, 4, 256, 64), 1152, 2048, None, 0.0, None, CAUSAL),
    # the refusals: the band-masked dense path
    ((2, 4, 256, 64), 1152, 512, None, 0.0, None, DENSE),
    ((2, 4, 1000, 64), 1000, 128, None, 0.0, None, DENSE),
    ((2, 4, 1024, 320), 1024, 128, None, 0.0, None, DENSE),
    ((2, 4, 1024, 64), 1024, 128, None, 0.1, None, DENSE),
    ((2, 4, 1024, 64), 1024, 128, "key", 0.0, None, DENSE),
    ((2, 4, 128, 64), 128, 64, None, 0.0, None, DENSE),
], ids=["laguna", "w100", "w_L_minus_1", "dqk192_dv128", "dp2_tp2",
        "w_is_L", "w_over_L", "w_over_lk_longer", "lk_longer", "l1000",
        "d320", "dropout", "masked", "l128"])
def test_windowed_attention_routing(lowered_for_tpu, q, lk, window, mask,
                                    dropout, mesh, want):
    b, h, lq, d = q
    dv = 128 if d == 192 else d
    structs = [_struct(q), _struct((b, h, lk, d)), _struct((b, h, lk, dv))]
    if mask:
        shape, dtype, _ = MASKS[mask]
        structs.append(_struct(shape(b, h, lq, lk), dtype))

    def call(q, k, v, attn_mask=None):
        return F.sdpa_bhld(q, k, v, attn_mask=attn_mask, is_causal=True,
                           dropout_p=dropout, window=window)

    took = lowered_for_tpu(mesh, call, *structs)
    assert took is (want is not DENSE)
    if took:    # which kernels: the band's, or the causal ones
        assert want in lowered_for_tpu.text
        other = CAUSAL if want is SWA else SWA
        assert other not in lowered_for_tpu.text


# ---- what a recomputed region keeps of the flash kernels ------------------------
# widths of whole 128-lane columns: the experts' grouped products lower
WIDTHS = dict(hidden=128, expert_width=128, dense_width=256)


def _latent():
    return lm.LatentMoE(lm.latent_moe_tiny(
        layers=2, streams=1, qk_nope_dim=64, qk_rope_dim=64, v_head_dim=64,
        use_recompute=True, **WIDTHS))


def _laguna():
    return lg.LagunaMoE(lg.laguna_moe_tiny(
        layers=2, heads=(2, 4), head_dim=64, window=512,
        layer_types=(lg.FULL, lg.SLIDING), use_recompute=True, **WIDTHS))


ONCE_A_LAYER = {"flash_fwd_causal": 2, "flash_bwd_dq_causal": 2,
                "flash_bwd_dkv_causal": 2}


@pytest.mark.parametrize("make,rows,mesh,want", [
    (_latent, (1, 2048), None, ONCE_A_LAYER),
    (_latent, (1, 1024), None, ONCE_A_LAYER),
    (_laguna, (1, 2048), None,
     {"flash_fwd_causal": 1, "flash_bwd_dq_causal": 1,
      "flash_bwd_dkv_causal": 1, "swa_fwd_w512": 1, "swa_bwd_dq_w512": 1,
      "swa_bwd_dkv_w512": 1}),
    (_latent, (2, 2048), DP2_TP2, ONCE_A_LAYER),
], ids=["latent_2048", "latent_1024", "laguna_full_and_window",
        "latent_2048_dp2_tp2"])
def test_a_recomputed_block_runs_its_flash_forward_once(
        lowered_for_tpu, kernel_calls, make, rows, mesh, want):
    """A two-layer decoder under ``use_recompute``, its gradient lowered for
    the chip: the step holds the forward kernel once a layer, causal and
    windowed alike, and the backward kernels once a layer (without the names
    of ``flash_attention._kept`` the forward would be there twice a layer:
    ``tests/test_pallas.py::TestKeptByARecomputedRegion``). Under a mesh the
    names sit inside the ``shard_map`` and the step lowers the same."""
    pt.seed(0)
    model = make()
    model.bfloat16()
    params = [p for _, p in model.named_parameters()]

    def grads(ids):
        lm.latent_moe_loss(model, ids, ids).backward()
        total = sum(p.grad.astype("float32").sum() for p in params
                    if p.grad is not None)
        for p in params:
            p.clear_gradient()
        return total

    assert lowered_for_tpu(mesh, grads, _struct(rows, jnp.int32))
    calls = kernel_calls.of(lowered_for_tpu.jaxpr)
    assert {k: n for k, n in calls.items()
            if str(k).startswith(("flash_", "swa_"))} == want


@pytest.mark.parametrize("logits,label,options,mesh,want", [
    # the cells
    ((16384, 50304), (16384,), {}, None, KERNEL),
    ((65536, 50304), (65536,), {}, DP4, KERNEL),
    ((12288, 30522), (12288,), {}, None, DENSE),
    ((16384, 30522), (16384,), {}, None, DENSE),
    ((4096, 16384), (4096,), {}, None, KERNEL),
    # labels with a trailing 1, a named last axis, rows over both mesh axes
    ((1024, 512), (1024, 1), {}, None, KERNEL),
    ((1024, 512), (1024,), {"axis": 1}, None, KERNEL),
    ((1024, 512), (1024,), {"reduction": "none"}, DP2_TP2, KERNEL),
    # the refusals
    ((1024, 512), (1024,), {"label_smoothing": 0.1}, None, DENSE),
    ((1024, 512), (1024,), {"weight": (512,)}, None, DENSE),
    ((1024, 512), (1024, 512), {"soft_label": True}, None, DENSE),
    ((1024, 512), (1024,), {"use_softmax": False}, None, DENSE),
    ((1020, 512), (1020,), {}, None, DENSE),
    ((16, 512), (16,), {}, DP2_TP2, DENSE),
    ((8, 128, 512), (8, 128), {}, None, DENSE),
    ((512, 1024), (1024,), {"axis": 0}, None, DENSE),
], ids=["gpt2s", "gpt2s_dp4", "bert512", "bert128", "xing4", "label_n1",
        "axis1", "dp2_tp2", "label_smoothing", "class_weights", "soft_labels",
        "no_softmax", "n1020", "dp2_tp2_4_rows_a_device", "logits_3d",
        "axis0"])
def test_cross_entropy_routing(lowered_for_tpu, logits, label, options, mesh,
                               want):
    options = dict(options)
    soft = options.get("soft_label", False)
    structs = [_struct(logits),
               _struct(label, jnp.float32 if soft else jnp.int32)]
    if "weight" in options:
        structs.append(_struct(options.pop("weight"), jnp.float32))

    def call(x, y, weight=None):
        return F.cross_entropy(x, y, weight=weight, **options)

    assert lowered_for_tpu(mesh, call, *structs) is want


@pytest.mark.parametrize("x,normalized,affine,mesh,want", [
    # the cells (xing4 has RMS norms only)
    ((16, 1024, 768), (768,), True, None, KERNEL),
    ((64, 1024, 768), (768,), True, DP4, KERNEL),
    ((24, 512, 768), (768,), True, None, KERNEL),
    ((128, 128, 768), (768,), True, None, KERNEL),
    ((4, 2, 128), (128,), True, None, KERNEL),
    ((8, 4, 128), (128,), True, DP2_TP2, KERNEL),
    # the refusals
    ((16, 1024, 100), (100,), True, None, DENSE),
    ((3, 5, 128), (128,), True, None, DENSE),
    ((4, 8, 128), (8, 128), True, None, DENSE),
    ((16, 1024, 768), (768,), False, None, DENSE),
    ((4, 1, 128), (128,), True, DP4, DENSE),
], ids=["gpt2s", "gpt2s_dp4", "bert512", "bert128", "8_rows", "dp2_tp2",
        "d100", "15_rows", "two_axes", "no_scale", "dp4_1_row_a_device"])
def test_layer_norm_routing(lowered_for_tpu, x, normalized, affine, mesh,
                            want):
    structs = [_struct(x)]
    if affine:
        structs += [_struct(normalized, jnp.float32)] * 2

    def call(x, weight=None, bias=None):
        return F.layer_norm(x, list(normalized), weight, bias)

    assert lowered_for_tpu(mesh, call, *structs) is want


@pytest.mark.parametrize("x,dtype,mesh,want", [
    # the cell: four streams of one packed row of 4,096 tokens
    ((4, 1, 4096, 3584), jnp.bfloat16, None, KERNEL),
    ((2, 2, 64, 256), jnp.bfloat16, None, KERNEL),
    # the refusals
    ((4, 1, 4096, 3520), jnp.bfloat16, None, DENSE),
    ((4, 1, 200, 128), jnp.bfloat16, None, DENSE),
    ((4, 1, 192, 128), jnp.bfloat16, None, DENSE),
    ((4, 1, 4096, 3584), jnp.float32, None, DENSE),
    ((6, 1, 256, 128), jnp.bfloat16, None, DENSE),
    ((4, 4, 256, 128), jnp.bfloat16, DP4, DENSE),
], ids=["xing4", "two_streams", "c_off_128", "tokens_no_tile_divides",
        "tokens_no_whole_lane_tile_divides", "float32_streams", "six_streams",
        "dp4"])
def test_hyper_connection_routing(lowered_for_tpu, x, dtype, mesh, want):
    """One rule for the three ops of the multi-stream residual: each of them
    holds a kernel call (``hc_read``'s is its backward's) or none does."""
    n = x[0]
    maps = dict(iters=2, eps=1e-6, clamp=(-30.0, 30.0))

    def sublayer(x, phi, alpha, bias):
        x.stop_gradient = False
        pre, post, res = F.hc_maps(x, phi, alpha, bias, **maps)
        h = F.hc_read(x, pre)
        out = F.hc_mix(x, h, post, res)
        out.sum().backward()
        return x.grad

    structs = [_struct(x, dtype), _struct((n * x[-1], 2 * n + n * n)),
               _struct((3,)), _struct((2 * n + n * n,))]
    assert lowered_for_tpu(mesh, sublayer, *structs) is want
    assert (pk.hc_route(x, dtype) is not None) is want


def test_hyper_connection_route_needs_a_tpu_backend():
    """Nothing forced: on the host CPU the route says dense."""
    assert pk.hc_route((4, 1, 4096, 3584), jnp.bfloat16) is None
    pk.set_enabled(True)
    try:
        assert pk.hc_route((4, 1, 4096, 3584), jnp.bfloat16) is not None
    finally:
        pk.set_enabled(None)


def test_causal_attention_with_fewer_keys_than_queries_matches_dense():
    """The first ``Lq - Lk`` queries of such a call see no key, and where
    the kernels' sweep skips their blocks the rows differ from the dense
    path's mean of the values (by 0.08 here): the call is refused."""
    rng = np.random.RandomState(0)
    q = pt.to_tensor(rng.randn(1, 2, 2048, 64).astype("float32"))
    k = pt.to_tensor(rng.randn(1, 2, 1024, 64).astype("float32"))
    v = pt.to_tensor(rng.randn(1, 2, 1024, 64).astype("float32"))
    pk.set_enabled(False)
    try:
        dense = F.sdpa_bhld(q, k, v, is_causal=True).numpy()
        pk.set_enabled(True)
        got = F.sdpa_bhld(q, k, v, is_causal=True).numpy()
    finally:
        pk.set_enabled(None)
    np.testing.assert_allclose(got, dense, atol=1e-5, rtol=1e-5)


@pytest.fixture
def bert_like_call():
    """q, k, v ``(4, 2, 256, 64)`` float32 and a padding mask over the keys,
    its rows of four lengths (one of them 0)."""
    rng = np.random.RandomState(1)
    q, k, v = (pt.to_tensor(rng.randn(4, 2, 256, 64).astype("float32"),
                            stop_gradient=False) for _ in range(3))
    kept = np.array([256, 100, 0, 201])[:, None]
    mask = np.where(np.arange(256) < kept, 0.0, -1e30).astype("float32")
    yield q, k, v, mask.reshape(4, 1, 1, 256)
    pk.set_enabled(None)
    dist.set_mesh(None)


def _out_and_grads(q, k, v, mask):
    out = F.sdpa_bhld(q, k, v, attn_mask=mask)
    (out * out).sum().backward()
    got = [out.numpy()] + [t.grad.numpy().copy() for t in (q, k, v)]
    if not mask.stop_gradient:
        got.append(mask.grad.numpy().copy())
    for t in (q, k, v):
        t.clear_gradient()
    return got


@pytest.mark.parametrize("kind,mesh", [
    ("additive", None), ("one_row", None),
    ("additive", DP4), ("additive", DP2_TP2), ("one_row", DP2_TP2)])
def test_masked_attention_through_the_kernels_matches_dense(
        bert_like_call, monkeypatch, kind, mesh):
    """BERT's call, op to op: the padding mask as the kernels' key bias gives
    the dense path's result and gradients, padded query rows and the wholly
    padded batch row included; under a mesh the bias splits with the batch."""
    q, k, v, mask = bert_like_call
    if kind == "one_row":
        mask = mask[1:2]
    mask = pt.to_tensor(mask)
    assert mask.stop_gradient
    pk.set_enabled(False)
    want = _out_and_grads(q, k, v, mask)
    taken = []
    whole = fa._forward
    monkeypatch.setattr(fa, "_forward", lambda *a, **kw: (
        taken.append(a[3].shape), whole(*a, **kw))[1])
    pk.set_enabled(True)
    if mesh is not None:
        n = int(np.prod(list(mesh.values())))
        dist.init_mesh(mesh, devices=jax.devices()[:n])
    got = _out_and_grads(q, k, v, mask)
    rows = (1 if kind == "one_row" else 4 // (mesh or {}).get("data", 1))
    assert taken == [(rows, 1, 256)]
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


def test_mask_that_wants_a_gradient_gets_the_dense_paths(bert_like_call,
                                                         monkeypatch):
    """The kernels return zeros for their bias, so the route refuses a mask
    with ``stop_gradient=False``: no silent zero."""
    q, k, v, mask = bert_like_call
    mask = np.where(mask < 0, -3.0, 0.0).astype("float32")     # a soft mask
    pk.set_enabled(False)
    want = _out_and_grads(q, k, v, pt.to_tensor(mask, stop_gradient=False))
    monkeypatch.setattr(fa, "_forward", None)   # a kernel call would raise
    pk.set_enabled(True)
    got = _out_and_grads(q, k, v, pt.to_tensor(mask, stop_gradient=False))
    assert len(got) == 5 and np.abs(got[4]).max() > 1e-3
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ---- the rotary embedding -----------------------------------------------------
@pytest.mark.parametrize("x,r,offset,dtype,mesh,want", [
    # the cells: a sliding layer's queries, a full layer's (the first half of
    # a head turns), latent queries (a head's last 64 of 192), the shared key
    ((1, 72, 8192, 128), 128, 0, jnp.bfloat16, None, KERNEL),
    ((1, 48, 8192, 128), 64, 0, jnp.bfloat16, None, KERNEL),
    ((1, 32, 8192, 192), 64, 128, jnp.bfloat16, None, KERNEL),
    ((2, 1, 4096, 64), 64, 0, jnp.bfloat16, None, KERNEL),
    # the refusals
    ((1, 8, 256, 128), 128, 0, jnp.float32, None, DENSE),
    ((1, 8, 256, 128), 63, 0, jnp.bfloat16, None, DENSE),
    ((1, 8, 256, 128), 96, 0, jnp.bfloat16, None, DENSE),
    ((1, 8, 256, 96), 64, 32, jnp.bfloat16, None, DENSE),
    ((1, 8, 200, 128), 128, 0, jnp.bfloat16, None, DENSE),
    ((4, 8, 256, 128), 128, 0, jnp.bfloat16, DP4, DENSE),
    ((4, 8, 256, 128), 128, 0, jnp.bfloat16, DP2_TP2, DENSE),
], ids=["laguna_sliding", "laguna_full", "latent_q", "latent_shared_k",
        "float32_heads", "odd_r", "r_off_half_a_lane_row",
        "offset_off_half_a_lane_row", "rows_no_tile_divides", "dp4",
        "dp2_tp2"])
def test_rotary_routing(lowered_for_tpu, x, r, offset, dtype, mesh, want):
    """Forward and backward are the same kernel: the backward's call is what
    the gradient's program holds."""
    def call(x, cos, sin):
        x.stop_gradient = False
        F.rotary(x, cos, sin, offset=offset).sum().backward()
        return x.grad

    if r % 2:       # no rotate-half of an odd width: the route alone
        assert pk.rotary_route(x, dtype, r, offset) is None
        return
    tables = [_struct((x[-2], r), jnp.float32)] * 2
    assert lowered_for_tpu(mesh, call, _struct(x, dtype), *tables) is want
    assert (pk.rotary_route(x, dtype, r, offset) is not None) is want
    if want:
        assert f'kernel_name = "rope_r{r}"' in lowered_for_tpu.text


def test_rotary_route_needs_a_tpu_backend():
    """Nothing forced: on the host CPU the route says dense."""
    assert pk.rotary_route((1, 72, 8192, 128), jnp.bfloat16, 128) is None


# ---- the state-space scan -----------------------------------------------------
@pytest.mark.parametrize("x,n,chunk,dtype,mesh,want", [
    # the cell: one packed row of 8,192, 64 heads of 64 over a state of 128
    ((1, 8192, 64, 64), 128, 256, jnp.bfloat16, None, KERNEL),
    # float32 heads, a head a slab, a row its chunk does not divide, batch 2
    ((2, 1000, 8, 128), 128, 128, jnp.float32, None, KERNEL),
    ((1, 512, 8, 32), 256, 128, jnp.bfloat16, None, KERNEL),
    # the refusals
    ((1, 512, 4, 64), 128, 16, jnp.bfloat16, None, DENSE),
    ((1, 512, 4, 64), 128, 24, jnp.bfloat16, None, DENSE),
    ((1, 512, 4, 64), 64, 128, jnp.bfloat16, None, DENSE),
    ((1, 512, 4, 48), 128, 128, jnp.bfloat16, None, DENSE),
    ((1, 512, 3, 64), 128, 128, jnp.bfloat16, None, DENSE),
    ((1, 512, 4, 64), 128, 128, jnp.float16, None, DENSE),
    ((1, 4096, 64, 64), 128, 1024, jnp.bfloat16, None, DENSE),
    ((4, 512, 4, 64), 128, 128, jnp.bfloat16, DP4, DENSE),
    ((4, 512, 4, 64), 128, 128, jnp.bfloat16, DP2_TP2, DENSE),
], ids=["granite4h", "float32_p128_ragged", "p32_n256", "chunk_16",
        "chunk_24", "state_64", "head_width_48", "heads_half_a_slab",
        "float16_heads", "chunk_over_the_vmem_budget", "dp4", "dp2_tp2"])
def test_ssm_scan_routing(lowered_for_tpu, x, n, chunk, dtype, mesh, want):
    """One form in the program: both kernels or the ``lax.scan``, by what the
    route reads off its input."""
    def call(x, dt, a_log, b, c, d):
        x.stop_gradient = False
        F.ssm_chunk(x, dt, a_log, b, c, d, chunk=chunk)[0].sum().backward()
        return x.grad

    rows, length, heads, _ = x
    structs = [_struct(x, dtype), _struct((rows, length, heads), jnp.float32),
               _struct((heads,), jnp.float32), _struct((rows, length, n), dtype),
               _struct((rows, length, n), dtype),
               _struct((heads,), jnp.float32)]
    assert lowered_for_tpu(mesh, call, *structs) is want
    assert (pk.ssm_scan_route(x, dtype, n, chunk) is not None) is want
    held = set(re.findall(r'kernel_name = "(\w+)"', lowered_for_tpu.text))
    assert held == ({"ssm_scan_fwd", "ssm_scan_bwd"} if want else set())
    assert ("stablehlo.while" in lowered_for_tpu.text) is not want


def test_ssm_scan_route_needs_a_tpu_backend():
    """Nothing forced: on the host CPU the route says dense."""
    assert pk.ssm_scan_route((1, 8192, 64, 64), jnp.bfloat16, 128,
                             256) is None


# ---- the expert layer's grouped products ---------------------------------------
@pytest.mark.parametrize("held,mesh,want", [
    (8, None, KERNEL),      # every expert held: the three stages, no loop
    (2, None, KERNEL),      # a share held: the windows (``moe_held``)
    (8, DP4, DENSE), (2, DP4, DENSE),
], ids=["all_held", "windows", "all_held_dp4", "windows_dp4"])
def test_expert_routing(lowered_for_tpu, held, mesh, want):
    """Megablox's grouped products where the program is one chip's, the
    dense forms under a mesh: one question, asked by both bodies."""
    layer = moe.DroplessMoE(128, 128, 8, 2, held=held)
    assert (layer.window_rows(512) < 512 * 2) is (held == 2)

    def call(x):
        x.stop_gradient = False
        layer(x)[0].sum().backward()
        return x.grad

    assert lowered_for_tpu(mesh, call, _struct((4, 128, 128))) is want
    assert moe.expert_route() is want


def test_expert_route_needs_a_tpu_backend():
    """Nothing forced: on the host CPU the route says dense."""
    assert moe.expert_route() is False


def test_the_package_names_every_kernel_and_route():
    doc = pk.__doc__
    for name in ("flash_route", "softmax_ce_route", "layer_norm_route",
                 "hc_route", "rotary_route", "ssm_scan_route", "ssm_scan",
                 "rope", "hc_mix"):
        assert name in pk.__all__ and callable(getattr(pk, name)), name
    for word in ("flash_attention", "fused_layer_norm",
                 "softmax_cross_entropy", "hyper_connection", "rotary",
                 "ssm_scan", "ssm_scan_route"):
        assert word in doc, word


def _first_step(make, loss_fn, ids):
    """(loss, {parameter: first gradient}, compiled text) of a bfloat16
    model's first ``TrainStep`` call; the gradient as AdamW read it."""
    pt.seed(0)
    model = make()
    model.bfloat16()
    opt = optim.AdamW(parameters=model.parameters(), learning_rate=1e-3)
    step = pt.TrainStep(model, opt, loss_fn)
    loss = float(step(ids[:, :-1], ids[:, 1:]).numpy())
    grads = {n: np.asarray(opt._accumulators[p.name]["moment1"],
                           np.float32) / (1.0 - opt._beta1)
             for n, p in model.named_parameters()}
    return loss, grads, step.compiled().as_text()


@pytest.mark.parametrize("make,kernels", [
    (lambda: lm.LatentMoE(lm.latent_moe_tiny(
        layers=2, streams=1, qk_nope_dim=64, qk_rope_dim=64, v_head_dim=64,
        use_recompute=True)), {"rope_r64"}),
    (lambda: lg.LagunaMoE(lg.laguna_moe_tiny(
        layers=2, heads=(2, 4), head_dim=128, window=32,
        use_recompute=True)), {"rope_r64", "rope_r128"}),
], ids=["latent_moe_tiny", "laguna_moe_tiny"])
def test_a_decoders_step_through_the_rotary_kernel_matches_dense(
        make, kernels, monkeypatch):
    """The route forced on, a recomputed block's rotations are ``rope_*``
    calls (in the interpreter here) and the step reaches the dense path's
    first loss and gradients. The route alone is switched: the other kernels
    run on both sides."""
    ids = np.random.default_rng(0).integers(0, 256, (1, 65)).astype(np.int32)
    pk.set_enabled(True)
    try:
        got = _first_step(make, lm.latent_moe_loss, ids)
        monkeypatch.setattr(pk, "rotary_route", lambda *a, **k: None)
        want = _first_step(make, lm.latent_moe_loss, ids)
    finally:
        pk.set_enabled(None)
    assert set(re.findall(r"rope_r\d+", got[2])) == kernels
    assert "rope_r" not in want[2]
    assert abs(got[0] - want[0]) < 1e-2, (got[0], want[0])
    assert set(got[1]) == set(want[1])
    for name, w in want[1].items():
        err = np.linalg.norm(got[1][name] - w) / (np.linalg.norm(w) + 1e-12)
        assert err < 2e-2, (name, err)


STEPS = {
    "gpt2": lambda: (GPT(GPTConfig(vocab_size=512, hidden=128, layers=2,
                                   heads=2, max_seq=128, dropout=0.0)),
                     gpt_loss, lambda ids: (ids[:, :-1], ids[:, 1:])),
    "bert": lambda: (bert.BertForPretraining(bert.bert_tiny(dropout=0.0)),
                     bert.bert_pretrain_loss,
                     lambda ids: (ids[:, :-1], ids[:, :-1] * 0,
                                  ids[:, :-1] * 0 + 1, ids[:, 1:],
                                  ids[:, 0] % 2)),
    "hybrid_moe_nope": lambda: (hm.HybridMoE(hm.hybrid_moe_tiny()),
                                lm.latent_moe_loss,
                                lambda ids: (ids[:, :-1], ids[:, 1:])),
}


@pytest.mark.parametrize("build,switch", [
    ("gpt2", "rotary_route"), ("bert", "rotary_route"),
    ("hybrid_moe_nope", "rotary_route"),
    ("gpt2", "kept_names"), ("bert", "kept_names")],
    ids=["gpt2", "bert", "hybrid_moe_nope", "gpt2_kept_names",
         "bert_kept_names"])
def test_models_without_rotary_lower_to_the_same_step(build, switch,
                                                      monkeypatch):
    """Learned positions, and ``GatedGroupedAttention(rope=None)``: the op is
    never called, so the step's program is the same text whatever the route
    would say (cells 1-4 and 7 compile to what they did). ``kept_names``: a
    step without a recomputed region is the same program whether the flash
    kernels' forward rule names its outputs for one or not (rows of 128
    through the kernels): the name is the identity and lowers to nothing."""
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 129)),
                      jnp.int32)

    def text():
        pt.seed(0)
        model, loss_fn, batch = STEPS[build]()
        model.bfloat16()
        params = [p for _, p in model.named_parameters()]

        def pure(ids):
            loss_fn(model, *(Tensor(a, _internal=True)
                             for a in batch(ids))).backward()
            grads = [p.grad._data for p in params if p.grad is not None]
            for p in params:
                p.clear_gradient()
            return grads

        return jax.jit(pure).lower(ids).as_text()

    if switch == "kept_names":      # rows of 128 through the kernels
        monkeypatch.setattr(fa, "MIN_STEP_SCORES", 128 * 128)
    pk.set_enabled(True)
    try:
        on = text()
        if switch == "kept_names":
            monkeypatch.setattr(fa, "_kept", lambda outputs: outputs)
        else:
            monkeypatch.setattr(pk, "rotary_route", lambda *a, **k: None)
        off = text()
    finally:
        pk.set_enabled(None)
    assert "rope_r" not in on
    if switch == "kept_names":
        # jax lowers a primitive's rule into a private function of the
        # primitive's name, one a signature, inlines it and erases it; names
        # that collide take a suffix from ONE counter a module. ``name`` is
        # lowered for ``o`` and again for ``lse``: the second collides with
        # the first and takes a count, so every private function that is
        # numbered after it (``@_take_244``) reads one higher than without
        # the names (``@_take_243``). Nothing else differs, and the compiled
        # step holds neither: the functions are compared by their names
        # without the module's counter.
        assert on != off
        on, off = (re.sub(r"@(\w+?)_\d+\b", r"@\1", t) for t in (on, off))
    assert on == off
