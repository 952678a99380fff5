"""The token mixer of a short-convolution decoder layer (LiquidAI's LFM2,
``Lfm2ShortConv``): a causal depthwise convolution gated on both sides, one
registered op so that the compiled step names it.

A layer projects its input to three streams of C channels, ``[B, C, X] =
split3(W_in u)``, and the op makes of them

    z_t = B_t * X_t
    c_t = sum_j w[j] z_{t - (K - 1) + j}        (K taps a channel, zeros
                                                 before the row's start)
    y_t = C_t * c_t

**No activation and no bias**: the two gates are the products themselves.
``linear_attention.short_conv`` is the other convolution of this package: one
input, an optional bias and a SiLU after it (the state-space and delta-rule
layers'). Float32 inside, the streams' type in and out. The backward pass is
written by hand: it keeps the projected streams and the taps, nothing in
float32, and makes ``z`` and ``c`` again.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops._base import register, apply

__all__ = ["gated_short_conv"]


def _streams(bcx):
    return jnp.split(bcx.astype(jnp.float32), 3, axis=-1)


def _conv(z, w):
    """(``c_t = sum_j w[j] z_{t - (K - 1) + j}``, ``z`` behind K - 1 zeros)
    over (B, L, C) with taps (K, C)."""
    taps, length = w.shape[0], z.shape[1]
    z = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(z[:, j:j + length] * w[j] for j in range(taps)), z


@jax.custom_vjp
def _gated_conv(bcx, w):
    b, c, x = _streams(bcx)
    return (c * _conv(b * x, w.astype(jnp.float32))[0]).astype(bcx.dtype)


def _gated_conv_fwd(bcx, w):
    return _gated_conv(bcx, w), (bcx, w)


def _gated_conv_bwd(kept, g):
    bcx, w = kept
    taps, length = w.shape[0], bcx.shape[1]
    b, c, x = _streams(bcx)
    wf, gf = w.astype(jnp.float32), g.astype(jnp.float32)
    conv, z = _conv(b * x, wf)
    d_conv = gf * c
    # tap j took z from K - 1 - j tokens back: its gradient goes as far ahead
    ahead = jnp.pad(d_conv, ((0, 0), (0, taps - 1), (0, 0)))
    dz = sum(ahead[:, taps - 1 - j:taps - 1 - j + length] * wf[j]
             for j in range(taps))
    dw = jnp.stack([jnp.sum(d_conv * z[:, j:j + length], axis=(0, 1))
                    for j in range(taps)])
    d_bcx = jnp.concatenate([dz * x, gf * conv, dz * b], axis=-1)
    return d_bcx.astype(bcx.dtype), dw.astype(w.dtype)


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


@register("gated_short_conv")
def _gated_short_conv(bcx, w):
    # bcx: (B, L, 3 C), the streams B, C, X side by side; w: (K, C), w[K - 1]
    # on the token itself
    return _gated_conv(bcx, w)


def gated_short_conv(bcx, weight):
    """``y_t = C_t * sum_j w[j] (B X)_{t - (K - 1) + j}`` a channel: ``bcx``
    (B, L, 3 C) holds the streams ``B``, ``C``, ``X`` in this order along its
    last axis, ``weight`` (K, C) the taps, the last one on the token itself;
    the result is (B, L, C). Causal and depthwise, **without an activation**
    (``short_conv`` is the one with a SiLU)."""
    return apply("gated_short_conv", bcx, weight)
