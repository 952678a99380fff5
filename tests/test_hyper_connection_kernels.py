"""The multi-stream residual's kernels (``ops/pallas/hyper_connection.py``)
against the jnp forms they replace (``nn/functional/decoder.py``), in the
Pallas interpreter at tiny shapes: every output and every gradient of
``hc_maps`` (streams, ``phi``, ``alpha``, ``bias``), ``hc_read`` (streams,
``pre``) and ``hc_mix`` (streams, ``y``, ``post``, ``res``); a token count no
tile divides, which the route leaves dense; and a sublayer of the model under
``Recompute`` against the same sublayer without it.

Float32 quantities (the maps, their gradients, the parameters' gradients)
agree to float32's rounding of sums taken in another order. Bfloat16 ones
(the streams, ``y`` and their gradients) are one rounding of float32 values
that agree like that, so they are equal but where a value sits on a rounding
boundary: at most one bf16 ulp apart, in a small share of the entries."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.framework.recompute import recompute
from paddle_tpu.models.nlp import latent_moe as lm
from paddle_tpu.nn import RMSNorm
from paddle_tpu.nn.functional import decoder as D
from paddle_tpu.ops import pallas as pk

MAPS = dict(iters=3, eps=1e-6, clamp=(-30.0, 30.0), alpha_scale=0.01,
            res_offset=0.0, norm_eps=1e-6)


def _operands(n, tokens, c, param_dtype, seed):
    rng = np.random.RandomState(seed)
    k = 2 * n + n * n

    def arr(shape, scale, dtype):
        return jnp.asarray(scale * rng.randn(*shape), dtype)

    return dict(
        x=arr((n, 2, tokens // 2, c), 1.0, jnp.bfloat16),
        y=arr((2, tokens // 2, c), 1.0, jnp.bfloat16),
        phi=arr((n * c, k), 0.05, param_dtype),
        alpha=jnp.asarray(0.5 + rng.rand(3), param_dtype),
        bias=arr((k,), 0.3, param_dtype),
        pre=jnp.asarray(rng.rand(n, 2, tokens // 2), jnp.float32),
        post=jnp.asarray(2 * rng.rand(n, 2, tokens // 2), jnp.float32),
        res=jnp.asarray(rng.rand(n, n, 2, tokens // 2), jnp.float32))


def _maps(o, **attrs):
    return D._hc_maps(o["x"], o["phi"], o["alpha"], o["bias"],
                      **{**MAPS, **attrs})


OPS = {
    "maps": (_maps, ("x", "phi", "alpha", "bias")),
    "maps_strong_diagonal": (
        lambda o: _maps(o, res_offset=4.0, alpha_scale=2.0, iters=20),
        ("x", "phi", "alpha", "bias")),
    "read": (lambda o: D._hc_read(o["x"], o["pre"]), ("x", "pre")),
    "mix": (lambda o: D._hc_mix(o["x"], o["y"], o["post"], o["res"]),
            ("x", "y", "post", "res")),
}


def _outputs_and_gradients(fn, operands, wrt, seed):
    """fn's outputs and the gradients of a fixed random functional of them."""
    rng = np.random.RandomState(seed)
    cots = None

    def loss(diff):
        nonlocal cots
        out = fn({**operands, **diff})
        out = out if isinstance(out, tuple) else (out,)
        if cots is None:
            cots = [jnp.asarray(rng.randn(*o.shape), jnp.float32) for o in out]
        return sum(jnp.sum(o.astype(jnp.float32) * c)
                   for o, c in zip(out, cots)), out

    (_, out), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        {name: operands[name] for name in wrt})
    return list(out) + [grads[name] for name in wrt]


def _both_paths(fn, operands, wrt, seed):
    try:
        pk.set_enabled(False)
        dense = _outputs_and_gradients(fn, operands, wrt, seed)
        pk.set_enabled(True)
        assert pk.hc_route(operands["x"].shape, jnp.bfloat16) is not None
        return dense, _outputs_and_gradients(fn, operands, wrt, seed)
    finally:
        pk.set_enabled(None)


def _assert_close(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.abs(w).max() + 1e-30
    if want.dtype == jnp.float32:
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6 * scale)
        return
    # one bf16 ulp (2^-8 of the value's binade, so up to 2^-7 of the value)
    np.testing.assert_allclose(g, w, rtol=2 ** -7, atol=2 ** -8 * 1e-3 * scale)
    assert np.mean(g != w) < 0.02


@pytest.mark.parametrize("op,n,tokens,c,param_dtype", [
    ("maps", 4, 64, 256, jnp.float32),
    ("maps", 2, 128, 128, jnp.bfloat16),
    ("maps_strong_diagonal", 2, 128, 128, jnp.bfloat16),
    ("read", 4, 128, 640, None),
    ("read", 2, 128, 128, None),
    ("mix", 4, 128, 640, None),
    ("mix", 2, 128, 128, None),
], ids=["maps_n4_f32_phi", "maps_n2_bf16_phi", "maps_res_offset_4_clamp_30",
        "read_n4", "read_n2", "mix_n4", "mix_n2"])
def test_kernel_matches_the_jnp_form_it_replaces(op, n, tokens, c,
                                                 param_dtype):
    fn, wrt = OPS[op]
    operands = _operands(n, tokens, c, param_dtype or jnp.float32, seed=n + c)
    dense, kernel = _both_paths(fn, operands, wrt, seed=7)
    for got, want in zip(kernel, dense):
        _assert_close(got, want)


def test_a_token_count_no_tile_divides_stays_dense(monkeypatch):
    """200 tokens: the route says ``None`` and the three ops run their jnp
    forms with no kernel call, forward and backward."""
    operands = _operands(2, 200, 128, jnp.float32, seed=3)
    monkeypatch.setattr(pk, "run", None)        # a kernel call would raise
    pk.set_enabled(True)
    try:
        assert pk.hc_route(operands["x"].shape, jnp.bfloat16) is None
        for op in ("maps", "read", "mix"):
            fn, wrt = OPS[op]
            for got in _outputs_and_gradients(fn, operands, wrt, seed=5):
                assert np.isfinite(np.asarray(got, np.float32)).all()
    finally:
        pk.set_enabled(None)


def test_sublayer_under_recompute_equals_the_sublayer_without_it():
    """One sublayer of the model through the kernels, taped op by op and as
    one recomputed region: the same output and the same gradients (the
    kernels' rules hold inside ``jax.checkpoint``, and what their backward
    needs is made again there); XLA fuses the layer between them differently
    in the two programs, so bfloat16 values agree to a rounding."""
    pt.seed(0)
    cfg = lm.latent_moe_tiny(hidden=128, streams=2, hc_res_init=4.0,
                             sinkhorn_iters=2)
    hc, norm = lm.HyperConnection(cfg), RMSNorm(cfg.hidden, cfg.rms_eps)
    hc.bfloat16()
    norm.bfloat16()
    rng = np.random.RandomState(1)
    x0 = jnp.asarray(rng.randn(2, 1, 32, 128), jnp.bfloat16)
    cot = jnp.asarray(rng.randn(2, 1, 32, 128), jnp.float32)
    params = {**{f"hc.{k}": p for k, p in hc.named_parameters()},
              **{f"norm.{k}": p for k, p in norm.named_parameters()}}

    def sublayer(x):      # the block's own, round a layer that halves
        return lm.LatentMoEBlock._sublayer(None, x, hc, norm,
                                           lambda h: h * 0.5)[0]

    def run(fn):
        @jax.jit
        def traced(x_arr):
            x = Tensor(x_arr, _internal=True)
            x.stop_gradient = False
            out = fn(x)
            (out.astype("float32") *
             Tensor(cot, _internal=True)).sum().backward()
            got = {"out": out._data, "x": x.grad._data}
            for name, p in params.items():
                if p.grad is not None:
                    got[name] = p.grad._data
                p.clear_gradient()
            return got

        return traced(x0)

    pk.set_enabled(True)
    try:
        assert pk.hc_route(x0.shape, x0.dtype) is not None
        plain = run(sublayer)
        again = run(lambda x: recompute(sublayer, x, models=[hc, norm]))
    finally:
        pk.set_enabled(None)
    assert {"out", "x", "hc.phi", "hc.alpha", "hc.bias",
            "norm.weight"} == set(plain) == set(again)
    for name, want in plain.items():
        _assert_close(again[name], want)
