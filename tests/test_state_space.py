"""The ops of a Mamba-2 state-space layer against plain ``jax.numpy``:
``ssm_chunk`` against the token-by-token recurrence of
``benchmark/reference/granite4h.py`` (which imports nothing of the program),
values and all six gradients, at chunk sizes that do and do not divide the
row; the biased ``short_conv``; the SiLU-gated norm; ``ssm_gate``.

Tolerances. Both sides run in float32 on the CPU and differ in the order of
their sums: the chunked form adds a chunk's tokens in one matrix product
where the recurrence adds them one at a time into a state it has decayed 256
times. That reads 2e-6 to 1e-5 of the output's scale, forward and in every
gradient (``a_log``'s, a sum over every token of both signs, included); the
bound is 2e-5, a hundred times under bfloat16's rounding (4e-3), so operands
rounded to bfloat16 anywhere inside would fail it.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import granite4h as ref  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.nn import functional as F  # noqa: E402
from paddle_tpu.nn.functional import state_space as ss  # noqa: E402


def tensor(a):
    return Tensor(jnp.asarray(a), _internal=True)


def _operands(seed, length, init, heads=3, width=8, state=16):
    """(x, dt, a_log, B, C, D) and a weight for the outputs. ``init``
    ``published``: A in U(1, 16) and Delta log-uniform in [0.001, 0.1], as
    Mamba-2 draws them, so that a token keeps 20% to 99.9% of the state and
    chunks see what earlier chunks wrote; ``zeros``: the benchmark's seeded
    leaves, A = -1 and Delta = softplus(~N(0, 1)) about 0.69."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    if init == "published":
        a_log = jnp.log(jnp.asarray(rng.uniform(1, 16, heads), jnp.float32))
        dt = jnp.exp(jnp.asarray(rng.uniform(
            np.log(0.001), np.log(0.1), (2, length, heads)), jnp.float32))
    else:
        a_log = jnp.zeros((heads,), jnp.float32)
        dt = jax.nn.softplus(normal(2, length, heads))
    return (normal(2, length, heads, width), dt, a_log,
            normal(2, length, state), normal(2, length, state),
            normal(heads)), normal(2, length, heads, width)


def _stepped(x, dt, a_log, b, c, d):
    return ref.recurrence(x, dt, -jnp.exp(a_log), b, c) + d[:, None] * x


@pytest.mark.parametrize("length,chunk,init", [
    (96, 16, "published"),     # six whole chunks, state carried through all
    (100, 24, "published"),    # a row that is no multiple of its chunk
    (300, 256, "zeros"),       # the benchmark's leaves at the model's chunk
], ids=["divides", "ragged", "zeros"])
def test_ssm_chunk_against_the_token_by_token_recurrence(length, chunk, init):
    operands, weight = _operands(length, length, init)

    def chunked(*xs):
        y, low = ss._ssm_chunk(*xs, chunk=chunk)
        return jnp.sum(y * weight), (y, low)

    def stepped(*xs):
        y = _stepped(*xs)
        return jnp.sum(y * weight), y

    every = tuple(range(6))
    (_, (got, low)), got_grads = jax.value_and_grad(
        chunked, every, has_aux=True)(*operands)
    (_, want), want_grads = jax.value_and_grad(
        stepped, every, has_aux=True)(*operands)
    scale = float(jnp.abs(want).max())
    assert scale > 0.1 and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)
    for name, g, w in zip(("x", "dt", "a_log", "B", "C", "D"), got_grads,
                          want_grads):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all()), name
        top = float(jnp.abs(w).max())
        assert top > 0, name
        np.testing.assert_allclose(g, w, atol=2e-5 * max(top, scale), rtol=0,
                                   err_msg=name)
    # the most negative log-decay a head ran up over one chunk
    steps = np.asarray(operands[1]) * -np.exp(np.asarray(operands[2]))
    steps = np.pad(steps, ((0, 0), (0, -length % chunk), (0, 0)))
    sums = steps.reshape(2, -1, chunk, steps.shape[-1]).sum(axis=2)
    assert float(low) == pytest.approx(sums.min(), rel=1e-5)


def test_a_chunks_log_decay_of_minus_177_gives_neither_inf_nor_nan():
    """The benchmark's seeded leaves at the model's sizes: ``dt_bias`` and
    ``A_log`` zeros and a zero ``raw`` make Delta ln 2 for every token, a
    chunk of 256 runs up -177.4, twice past float32's exp(-88). A quotient
    of cumulative products would be 0 / 0 from the 128th token on; the
    differences are finite, and so is every gradient."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1, 512, 2, 8)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(1, 512, 16)), jnp.float32)
            for _ in range(2))
    dt = ss._ssm_gate(jnp.zeros((1, 512, 2)), jnp.zeros((2,)))
    a_log, d = jnp.zeros((2,)), jnp.ones((2,))
    y, low = ss._ssm_chunk(x, dt, a_log, b, c, d, chunk=256)
    assert float(low) == pytest.approx(-256 * np.log(2.0), rel=1e-6)
    assert float(low) < -177 and bool(jnp.isfinite(y).all())
    grads = jax.grad(lambda *xs: jnp.sum(
        ss._ssm_chunk(*xs, chunk=256)[0] ** 2), tuple(range(6)))(
            x, dt, a_log, b, c, d)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    np.testing.assert_allclose(y, _stepped(x, dt, a_log, b, c, d),
                               atol=2e-5 * float(jnp.abs(y).max()), rtol=0)


def test_ssm_chunk_carries_the_state_over_chunks_and_takes_bfloat16():
    """At the published ranges the last chunk's outputs depend on the first
    chunk's tokens (the carried state is not dropped), and a chunk size that
    divides the row gives what one that does not gives."""
    operands, _ = _operands(7, 64, "published")
    base, _ = ss._ssm_chunk(*operands, chunk=16)
    other, _ = ss._ssm_chunk(*operands, chunk=24)
    scale = float(jnp.abs(base).max())
    np.testing.assert_allclose(base, other, atol=2e-5 * scale, rtol=0)
    moved = list(operands)
    moved[0] = operands[0].at[:, :8].add(100.0)      # x of the first tokens
    changed, _ = ss._ssm_chunk(*moved, chunk=16)
    assert float(jnp.abs(changed - base)[:, 48:].max()) > 1e-3 * scale
    # bfloat16 x, B, C in, bfloat16 out; the step size stays float32
    x, dt, a_log, b, c, d = operands
    y, low = F.ssm_chunk(tensor(x.astype(jnp.bfloat16)), tensor(dt),
                         tensor(a_log), tensor(b.astype(jnp.bfloat16)),
                         tensor(c.astype(jnp.bfloat16)), tensor(d), chunk=16)
    assert y._data.dtype == jnp.bfloat16 and low._data.dtype == jnp.float32
    assert y.shape == list(x.shape)


def test_the_step_size_the_biased_convolution_and_the_silu_gated_norm():
    rng = np.random.default_rng(1)
    raw = rng.normal(size=(2, 5, 3)).astype(np.float32)
    bias = rng.normal(size=(3,)).astype(np.float32)
    dt = F.ssm_gate(tensor(raw.astype(jnp.bfloat16)), tensor(bias))
    assert dt._data.dtype == jnp.float32 and dt.numpy().min() > 0
    np.testing.assert_allclose(
        dt.numpy(), np.log1p(np.exp(np.asarray(
            jnp.asarray(raw, jnp.bfloat16), np.float32) + bias)), rtol=1e-5)
    # SiLU(conv(x) + b), tap 3 on the token itself, causal
    x, w = rng.normal(size=(2, 9, 6)), rng.normal(size=(4, 6))
    b = rng.normal(size=(6,))
    f32 = [tensor(a.astype(np.float32)) for a in (x, w, b)]
    got = F.short_conv(*f32).numpy()
    want = np.zeros_like(x)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += w[j] * x[:, t - 3 + j]
    want = want + b
    want = want / (1 + np.exp(-want))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got, ref.biased_conv(
        *(jnp.asarray(a, jnp.float32) for a in (x, w, b))),
        rtol=1e-5, atol=1e-6)
    # without a bias it is the op it was, and a zero bias changes nothing
    plain = F.short_conv(f32[0], f32[1]).numpy()
    np.testing.assert_array_equal(
        plain, F.short_conv(f32[0], f32[1], tensor(np.zeros(6, np.float32)))
        .numpy())
    assert np.abs(plain - got).max() > 0.1
    # RMS_w(x * SiLU(gate)) over all the channels; the sigmoid form is
    # another function of the same operands
    x = rng.normal(size=(2, 5, 12)).astype(np.float32)
    gate = rng.normal(size=(2, 5, 12)).astype(np.float32)
    w = rng.normal(size=(12,)).astype(np.float32)
    got = F.gated_rms_norm(tensor(x), tensor(gate), tensor(w), 1e-5,
                           silu_first=True).numpy()
    gated = x * gate / (1 + np.exp(-gate))
    want = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    other = F.gated_rms_norm(tensor(x), tensor(gate), tensor(w), 1e-5).numpy()
    np.testing.assert_allclose(
        other, x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w /
        (1 + np.exp(-gate)), rtol=1e-5, atol=1e-6)
