"""Which calls go to a pallas kernel and which to the dense path.

End to end: a tiny GPT trains with the kernels force-enabled (interpret on
CPU) as the LIVE code path — layernorm, flash attention, and softmax-CE all
route through ops/pallas/ — and the first-step loss matches the dense path.
(Compiled-mode TPU validation is chip_smoke.py's kernels phase.)

Case by case: one table a kernel of (shapes, options, mesh) -> kernel or
dense, through the public call, read off the ``tpu_custom_call``s of the
call lowered for a TPU platform (nothing is compiled and libtpu is not
loaded). The rules live in the kernels' modules (``flash_route``,
``softmax_ce_route``, ``layer_norm_route``); a PR that changes what a
kernel takes edits that rule and its table here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import distributed as dist
from paddle_tpu import optim
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import pallas as pk
from paddle_tpu.models.nlp.gpt import GPT, GPTConfig, gpt_loss


def test_pallas_routing_end_to_end():
    pk.set_enabled(True)   # force the pallas routing; auto_interpret -> CPU
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

        text = jax.jit(pure).trace(*structs).lower(
            lowering_platforms=("tpu",)).as_text()
        return "tpu_custom_call" in text

    yield takes
    pk.set_enabled(None)
    dist.set_mesh(None)


def _struct(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


@pytest.mark.parametrize("q,lk,dv,causal,mask,dropout,mesh,want", [
    # the cells
    ((16, 12, 1024, 64), 1024, 64, True, False, 0.0, None, KERNEL),
    ((64, 12, 1024, 64), 1024, 64, True, False, 0.0, DP4, KERNEL),
    ((24, 12, 512, 64), 512, 64, False, True, 0.0, None, DENSE),
    ((128, 12, 128, 64), 128, 64, False, True, 0.0, None, DENSE),
    ((1, 32, 4096, 192), 4096, 128, True, False, 0.0, None, KERNEL),
    # BERT's shapes without their mask, other widths, unequal lengths
    ((24, 12, 512, 64), 512, 64, False, False, 0.0, None, KERNEL),
    ((2, 4, 256, 64), 256, 256, False, False, 0.0, None, KERNEL),
    ((2, 4, 128, 64), 1152, 64, True, False, 0.0, None, KERNEL),
    ((2, 4, 2048, 64), 1024, 64, False, False, 0.0, None, KERNEL),
    ((8, 4, 128, 64), 128, 64, True, False, 0.0, DP2_TP2, KERNEL),
    ((3, 4, 128, 64), 128, 64, True, False, 0.0, DP2_TP2, KERNEL),
    # the refusals
    ((2, 4, 2048, 64), 1024, 64, True, False, 0.0, None, DENSE),
    ((2, 4, 1000, 64), 1000, 64, True, False, 0.0, None, DENSE),
    ((2, 4, 128, 64), 120, 64, False, False, 0.0, None, DENSE),
    ((2, 4, 128, 320), 128, 64, True, False, 0.0, None, DENSE),
    ((2, 4, 128, 192), 128, 96, True, False, 0.0, None, DENSE),
    ((16, 12, 1024, 64), 1024, 64, True, False, 0.1, None, DENSE),
    ((16, 12, 1024, 64), 1024, 64, True, True, 0.0, None, DENSE),
], ids=["gpt2s", "gpt2s_dp4", "bert512_masked", "bert128_masked", "xing4",
        "bert512_no_mask", "dv256", "causal_lk_longer", "lk_shorter",
        "dp2_tp2", "dp2_tp2_odd_batch", "causal_lk_shorter", "l1000",
        "lk120", "d320", "dv96", "dropout", "causal_masked"])
def test_attention_routing(lowered_for_tpu, q, lk, dv, causal, mask, dropout,
                           mesh, want):
    b, h, lq, _ = q
    structs = [_struct(q), _struct((b, h, lk, q[3])), _struct((b, h, lk, dv))]
    if mask:
        structs.append(_struct((b, 1, 1, lk), jnp.float32))

    def call(q, k, v, attn_mask=None):
        return F.sdpa_bhld(q, k, v, attn_mask=attn_mask, is_causal=causal,
                           dropout_p=dropout)

    assert lowered_for_tpu(mesh, call, *structs) is want


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
