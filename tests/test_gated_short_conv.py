"""``gated_short_conv``, the token mixer of a short-convolution layer, against
the recurrence written a token at a time in plain ``jax.numpy`` (nothing of
the program): values, and the hand-written backward pass against autodiff of
that recurrence, at kernel sizes 3 and 4, on a row shorter than the kernel,
through the tape, and in bfloat16.

Tolerances. Both sides run in float32 on the CPU and add a token's K products
in a different order, which reads 1e-7 of the output's scale; the bound is
2e-6, two thousand times under bfloat16's rounding (4e-3), so streams or taps
rounded to bfloat16 anywhere inside would fail it. In bfloat16 the op rounds
its result once (float32 inside): half a bfloat16 ulp, 4e-3 relative.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import gated_conv as gc
from paddle_tpu.ops import OP_REGISTRY


def stepped(bcx, w):
    """``y_t = C_t * sum_j w[j] (B X)_{t - (K - 1) + j}``, a token at a time
    over a window of the last K products kept as the recurrent state (what a
    decoder's ``conv_L_cache`` holds), zeros before the row's start."""
    taps = w.shape[0]
    b, c, x = jnp.split(bcx, 3, axis=-1)

    def token(state, inputs):
        bt, ct, xt = inputs                       # (B, C) each
        state = jnp.concatenate([state[:, 1:], (bt * xt)[:, None]], axis=1)
        return state, ct * jnp.einsum("bkc,kc->bc", state, w)

    start = jnp.zeros((bcx.shape[0], taps, w.shape[1]), bcx.dtype)
    _, y = jax.lax.scan(token, start, tuple(
        jnp.moveaxis(t, 1, 0) for t in (b, c, x)))
    return jnp.moveaxis(y, 0, 1)


def operands(seed, batch, length, channels, taps):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(batch, length, 3 * channels)),
                        jnp.float32),
            jnp.asarray(rng.normal(size=(taps, channels)), jnp.float32),
            jnp.asarray(rng.normal(size=(batch, length, channels)),
                        jnp.float32))


CASES = pytest.mark.parametrize("length,taps", [
    (24, 3), (24, 4), (2, 3), (1, 4), (3, 3)],
    ids=["k3", "k4", "shorter-than-k3", "one-token-k4", "as-long-as-k3"])


@CASES
def test_the_op_against_the_token_by_token_recurrence(length, taps):
    bcx, w, _ = operands(length * taps, 2, length, 8, taps)
    got, want = gc._gated_short_conv(bcx, w), stepped(bcx, w)
    assert got.shape == want.shape == (2, length, 8)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


@CASES
def test_the_hand_written_backward_against_autodiff_of_the_recurrence(
        length, taps):
    bcx, w, weight = operands(100 + length * taps, 2, length, 8, taps)

    def loss(fn):
        return lambda bcx, w: jnp.sum(fn(bcx, w) * weight)

    got = jax.grad(loss(gc._gated_short_conv), (0, 1))(bcx, w)
    want = jax.grad(loss(stepped), (0, 1))(bcx, w)
    for name, g, v in zip(("streams", "taps"), got, want):
        assert g.shape == v.shape and float(jnp.abs(v).max()) > 0.1, name
        scale = float(jnp.abs(v).max())
        np.testing.assert_allclose(g, v, rtol=2e-6, atol=2e-6 * scale,
                                   err_msg=name)
    # each stream's gradient is its own third, in the streams' order B, C, X
    db, dc, dx = jnp.split(got[0], 3, axis=-1)
    b, c, x = jnp.split(bcx, 3, axis=-1)
    conv = stepped(jnp.concatenate([b, jnp.ones_like(c), x], -1), w)
    np.testing.assert_allclose(dc, weight * conv, rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(db - dx).max()) > 0.1


def test_the_result_is_causal_and_a_channel_sees_only_itself():
    bcx, w, _ = operands(7, 1, 12, 4, 3)
    base = gc._gated_short_conv(bcx, w)
    later = gc._gated_short_conv(bcx.at[:, 6:].add(1.0), w)
    np.testing.assert_array_equal(base[:, :6], later[:, :6])
    assert float(jnp.abs(base[:, 6:] - later[:, 6:]).min()) > 0
    # channel 1 of B moved: channel 1 of the result alone follows, and for
    # K - 1 tokens after the one moved
    moved = gc._gated_short_conv(bcx.at[:, 4, 1].add(1.0), w)
    changed = np.asarray(jnp.abs(moved - base) > 0)[0]
    assert changed[:, [0, 2, 3]].sum() == 0
    assert list(np.nonzero(changed[:, 1])[0]) == [4, 5, 6]


def test_no_activation_no_bias_and_short_conv_is_the_one_with_the_silu():
    """With ``C = 1`` and ``B = 1`` the op is the bare convolution of ``X``:
    ``short_conv`` of the same taps is its SiLU."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 10, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 6)), jnp.float32)
    ones = jnp.ones_like(x)
    bare = gc._gated_short_conv(jnp.concatenate([ones, ones, x], -1), w)
    silu = F.short_conv(Tensor(x, _internal=True),
                        Tensor(w, _internal=True)).numpy()
    np.testing.assert_allclose(jax.nn.silu(bare), silu, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(bare - silu).max()) > 0.1
    assert float(bare.min()) < -0.5         # no SiLU clipped it from below
    assert "no activation" in gc.__doc__.lower()
    assert "silu" in F.short_conv.__doc__.lower() and \
        "gated_short_conv" in F.short_conv.__doc__


def test_a_registered_op_on_the_tape_in_bfloat16():
    assert OP_REGISTRY["gated_short_conv"] is gc._gated_short_conv
    bcx, w, weight = operands(5, 2, 16, 8, 3)
    want = stepped(bcx.astype(jnp.bfloat16).astype(jnp.float32),
                   w.astype(jnp.bfloat16).astype(jnp.float32))
    a = Tensor(bcx.astype(jnp.bfloat16), stop_gradient=False, _internal=True)
    t = Tensor(w.astype(jnp.bfloat16), stop_gradient=False, _internal=True)
    y = F.gated_short_conv(a, t)
    assert y._data.dtype == jnp.bfloat16 and tuple(y.shape) == (2, 16, 8)
    np.testing.assert_allclose(y.numpy().astype(np.float32), want, rtol=4e-3,
                               atol=1e-6)
    (y.astype("float32") * Tensor(weight, _internal=True)).sum().backward()
    assert a.grad._data.dtype == t.grad._data.dtype == jnp.bfloat16
    assert tuple(a.grad.shape) == (2, 16, 24) and tuple(t.grad.shape) == (3, 8)
    assert float(jnp.abs(t.grad._data.astype(jnp.float32)).max()) > 0
