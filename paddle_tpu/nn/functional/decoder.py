"""Functionals of current decoder blocks, each a registered op (so the
compiled step names them): rotary position embedding with YaRN frequencies,
the SwiGLU gate, and the maps and mixes of a multi-stream residual
(manifold-constrained hyper-connections).
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...ops._base import register, apply

__all__ = ["yarn_inv_freq", "yarn_mscale", "rotary_cos_sin", "rotary",
           "swiglu", "hc_maps", "hc_read", "hc_mix", "sinkhorn"]


# ---- rotary embedding ------------------------------------------------------
def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention temperature (Peng et al. 2023, eq. 22 as DeepSeek-V2
    uses it): 0.1 * mscale * ln(factor) + 1, and 1 for no extension."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, scaling=None):
    """The ``dim / 2`` inverse frequencies of a rotary embedding. With a YaRN
    ``scaling`` (``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``) the dimensions that turn more than
    ``beta_fast`` times over the original length keep their frequency, those
    that turn less than ``beta_slow`` times are interpolated by ``factor``,
    and a linear ramp blends the ones between."""
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** pos
    if not scaling:
        return extra
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) /
                   max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rotary_cos_sin(length, dim, theta, scaling=None, attention_factor=None):
    """(cos, sin), each ``(length, dim)`` float32, for the rotate-half
    layout (the frequencies repeated over both halves). Under YaRN both are
    scaled by ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)``, or by ``attention_factor`` where the configuration
    states one (Hugging Face's ``rope_parameters.attention_factor``). ``dim``
    may be part of a head: :func:`rotary` turns the first ``dim`` of it."""
    angles = np.outer(np.arange(length, dtype=np.float64),
                      yarn_inv_freq(dim, theta, scaling))
    angles = np.concatenate([angles, angles], axis=-1)
    scale = 1.0
    if attention_factor is not None:
        scale = float(attention_factor)
    elif scaling:
        scale = yarn_mscale(scaling["factor"], scaling.get("mscale", 1.0)) / \
            yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0.0))
    return (np.cos(angles) * scale).astype(np.float32), \
        (np.sin(angles) * scale).astype(np.float32)


def _rotate_half(x, cos, sin):
    # every column of x turns: x * cos + rotate_half(x) * sin, in float32
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    turned = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return (xf * cos + turned * sin).astype(x.dtype)


@register("rotary")
def _rotary(x, cos, sin, *, offset=0):
    # x: (..., L, d); cos, sin: (L, r), offset + r <= d. Over the r columns
    # of d from offset: x * cos + rotate_half(x) * sin; the other d - r pass
    # as they are. bf16 heads on a TPU take one kernel (ops.pallas.rotary);
    # the jnp body is every other call's path and the kernel's oracle
    from ...ops import pallas as pk

    r = cos.shape[-1]
    specs = pk.rotary_route(x.shape, x.dtype, r, offset)
    if specs is not None:
        return pk.run(pk.rope, specs, (x, cos, sin), offset)
    if r == x.shape[-1]:
        return _rotate_half(x, cos, sin)
    parts = [x[..., :offset], _rotate_half(x[..., offset:offset + r], cos, sin),
             x[..., offset + r:]]
    return jnp.concatenate([p for p in parts if p.shape[-1]], axis=-1)


def rotary(x, cos, sin, offset=0):
    """Rotate ``x`` ``(..., L, d)`` by its positions: ``cos`` and ``sin`` are
    ``rotary_cos_sin``'s for the same ``L`` (arrays or Tensors), and for ``d``
    or for ``rotary_dim < d`` dims of a head, those from ``offset`` (the
    first ones unless said), which are then the ones rotated (among
    themselves, rotate-half) while the rest pass unrotated
    (``partial_rotary_factor``; a latent head's rope columns)."""
    def constant(a):
        return a if isinstance(a, Tensor) else Tensor(jnp.asarray(a),
                                                      _internal=True)

    return apply("rotary", x, constant(cos), constant(sin),
                 offset=int(offset))


# ---- gated MLP -------------------------------------------------------------
@register("swiglu")
def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


def swiglu(gate, up):
    """silu(gate) * up (Shazeer 2020): the inside of a gated MLP."""
    return apply("swiglu", gate, up)


# ---- multi-stream residual -------------------------------------------------
# Layout: the streams lead. ``x`` is (n, ..., C) and the maps are (n, ...),
# (n, ...) and (n, n, ...): the token axes are the minor ones, so every
# Sinkhorn round and every mix runs lane-dense (an (..., n, n) layout would
# put 4 values on a 128-lane row, and (..., n, C) in bfloat16 pads n to 16).
def sinkhorn(m, iters, eps):
    """exp(m) normalised ``iters`` times over rows, then over columns: towards
    a doubly stochastic matrix. ``m``: (n, n, ...) float32, row index first."""
    m = jnp.exp(m)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


@register("hc_maps")
def _hc_maps(x, phi, alpha, bias, *, iters, eps, clamp, alpha_scale,
             res_offset, norm_eps):
    # x: (n, ..., C) streams. phi: (n C, 2 n + n^2), rows stream by stream;
    # alpha: (3,) gates of the input-dependent part, stored as multiples of
    # alpha_scale; bias: (2 n + n^2,), its n^2 part an offset from
    # res_offset * I.
    from ...ops import pallas as pk

    n, c = x.shape[0], x.shape[-1]
    # RMS over the n C values of a token, and its projection, without
    # forming the flattened state: the norm is a scalar a token
    specs = pk.hc_route(x.shape, x.dtype)
    if specs is not None:
        ss, dyn = pk.run(pk.hc_norm_proj, specs, (x.reshape(n, -1, c), phi))
        ss, dyn = ss.reshape(x.shape[1:-1]), dyn.reshape(x.shape[1:-1] + (-1,))
    else:
        xf = x.astype(jnp.float32)
        ss = jnp.sum(jnp.square(xf), axis=(0, -1))
        w = phi.astype(jnp.float32).reshape(n, c, -1)
        dyn = sum(jnp.matmul(xf[j], w[j], precision=jax.lax.Precision.HIGHEST)
                  for j in range(n))
    dyn = dyn * jax.lax.rsqrt(ss / (n * c) + norm_eps)[..., None]
    dyn = jnp.moveaxis(dyn, -1, 0)                      # (2 n + n^2, ...)
    a = alpha.astype(jnp.float32) * alpha_scale
    b = bias.astype(jnp.float32).reshape((-1,) + (1,) * (dyn.ndim - 1))
    pre = jax.nn.sigmoid(a[0] * dyn[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * dyn[n:2 * n] + b[n:2 * n])
    res = (a[2] * dyn[2 * n:] + b[2 * n:]).reshape((n, n) + dyn.shape[1:])
    res = res + res_offset * jnp.eye(n, dtype=jnp.float32).reshape(
        (n, n) + (1,) * (dyn.ndim - 1))
    res = sinkhorn(jnp.clip(res, clamp[0], clamp[1]), iters, eps)
    return pre, post, res


def hc_maps(x, phi, alpha, bias, *, iters, eps, clamp, alpha_scale=1.0,
            res_offset=0.0, norm_eps=1e-6):
    """The three per-token maps of a constrained multi-stream residual over
    the streams ``x`` (n, ..., C), all float32 and stream-major: ``H_pre``
    (n, ...) in (0, 1) reads the streams into the layer's input, ``H_post``
    (n, ...) in (0, 2) writes the layer's output back, and ``H_res`` (n, n,
    ...), Sinkhorn-normalised, mixes the streams (row i: the new stream
    i)."""
    return apply("hc_maps", x, phi, alpha, bias, iters=int(iters),
                 eps=float(eps), clamp=(float(clamp[0]), float(clamp[1])),
                 alpha_scale=float(alpha_scale), res_offset=float(res_offset),
                 norm_eps=float(norm_eps))


@register("hc_read")
def _hc_read(x, pre):
    # h = sum_j pre[j] x[j]; a handful of streams: elementwise, not a matmul
    from ...ops import pallas as pk

    specs = pk.hc_route(x.shape, x.dtype)
    if specs is not None:
        return pk.run(pk.hc_read, specs, (x, pre))
    h = sum(pre[j][..., None] * x[j].astype(jnp.float32)
            for j in range(x.shape[0]))
    return h.astype(x.dtype)


def hc_read(x, pre):
    """The layer's input (..., C) from the streams ``x`` (n, ..., C)."""
    return apply("hc_read", x, pre)


@register("hc_mix")
def _hc_mix(x, y, post, res):
    # x'[i] = sum_j res[i, j] x[j] + post[i] y
    from ...ops import pallas as pk

    specs = pk.hc_route(x.shape, x.dtype)
    if specs is not None:
        return pk.run(pk.hc_mix, specs, (x, y, post, res))
    n = x.shape[0]
    xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
    rows = [sum(res[i, j][..., None] * xf[j] for j in range(n)) +
            post[i][..., None] * yf for i in range(n)]
    return jnp.stack(rows, axis=0).astype(x.dtype)


def hc_mix(x, y, post, res):
    """The streams (n, ..., C) after a layer whose output is ``y`` (..., C)."""
    return apply("hc_mix", x, y, post, res)
