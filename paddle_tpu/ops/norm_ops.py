"""Normalization ops (functional).

Covers the reference's ``batch_norm_op.cc``, ``layer_norm_op.cc``,
``group_norm_op.cc``, ``instance_norm_op.cc``, ``norm_op.cc`` (l2_normalize),
``lrn_op.cc``. Running-stat updates are returned functionally; the Layer
wrappers own the mutable state (XLA-friendly: no in-place buffers).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ._base import register, apply, unwrap


@register("batch_norm_infer")
def _bn_infer(x, mean, var, weight, bias, *, epsilon, axis):
    shape = [1] * x.ndim
    shape[axis] = -1
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + epsilon).astype(x.dtype)
    out = (x - mean.reshape(shape)) * inv.reshape(shape)
    return out * weight.reshape(shape) + bias.reshape(shape)


@register("batch_norm_train")
def _bn_train(x, weight, bias, *, epsilon, axis):
    axes = tuple(i for i in range(x.ndim) if i != axis)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.var(xf, axis=axes)
    shape = [1] * x.ndim
    shape[axis] = -1
    inv = jax.lax.rsqrt(var + epsilon)
    out = (xf - mean.reshape(shape)) * inv.reshape(shape)
    out = out * weight.astype(jnp.float32).reshape(shape) + bias.astype(jnp.float32).reshape(shape)
    return out.astype(x.dtype), mean, var


def batch_norm(x, running_mean, running_var, weight, bias, training=False,
               momentum=0.9, epsilon=1e-5, data_format="NCHW", use_global_stats=None, name=None):
    axis = 1 if data_format.startswith("NC") else unwrap(x).ndim - 1
    if use_global_stats is None:
        use_global_stats = not training
    if use_global_stats:
        return apply("batch_norm_infer", x, running_mean, running_var, weight, bias,
                     epsilon=float(epsilon), axis=axis)
    out, mean, var = apply("batch_norm_train", x, weight, bias,
                           epsilon=float(epsilon), axis=axis)
    # functional running-stat update (ref: batch_norm_op.cc MomentumUpdate)
    n = 1
    for i, s in enumerate(unwrap(x).shape):
        if i != axis:
            n *= s
    unbiased = var * (n / max(n - 1, 1))
    new_mean = running_mean * momentum + mean.astype(running_mean.dtype) * (1 - momentum)
    new_var = running_var * momentum + unbiased.astype(running_var.dtype) * (1 - momentum)
    running_mean.set_value(new_mean)
    running_var.set_value(new_var)
    return out


@register("layer_norm")
def _layer_norm(x, weight, bias, *, epsilon, begin_norm_axis):
    # the fused kernels where they take the call (ref: the hand-fused
    # layer_norm_op.cu): one VMEM pass + fused bwd
    from . import pallas as pk

    specs = pk.layer_norm_route(x.shape, begin_norm_axis, weight.ndim,
                                bias.ndim)
    if specs is not None:
        return pk.run(pk.fused_layer_norm, specs, (x, weight, bias),
                      float(epsilon))
    axes = tuple(range(begin_norm_axis, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + epsilon)
    shape = [1] * begin_norm_axis + list(x.shape[begin_norm_axis:])
    out = out * weight.astype(jnp.float32).reshape(shape) + bias.astype(jnp.float32).reshape(shape)
    return out.astype(x.dtype)


@register("layer_norm_noaffine")
def _layer_norm_noaffine(x, *, epsilon, begin_norm_axis):
    axes = tuple(range(begin_norm_axis, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + epsilon)).astype(x.dtype)


@register("rms_norm")
def _rms_norm(x, weight, *, epsilon):
    """x / sqrt(mean(x^2) + eps) over the last axis, times ``weight`` where
    there is one; statistics in float32, the result in x's dtype."""
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(
        jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + epsilon)
    if weight is not None:
        out = out * weight.astype(jnp.float32)
    return out.astype(x.dtype)


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """Root-mean-square norm over the last axis (Zhang & Sennrich 2019): no
    mean is taken off and there is no shift; ``weight`` is optional."""
    return apply("rms_norm", x, weight, epsilon=float(epsilon))


def layer_norm(x, normalized_shape=None, weight=None, bias=None, epsilon=1e-5, name=None):
    nd = unwrap(x).ndim
    if normalized_shape is None:
        begin = nd - 1
    else:
        ns = [normalized_shape] if isinstance(normalized_shape, int) else list(normalized_shape)
        begin = nd - len(ns)
    if weight is None:
        return apply("layer_norm_noaffine", x, epsilon=float(epsilon), begin_norm_axis=begin)
    return apply("layer_norm", x, weight, bias, epsilon=float(epsilon), begin_norm_axis=begin)


@register("group_norm")
def _group_norm(x, weight, bias, *, num_groups, epsilon, channel_axis):
    # NCHW path: reshape channels into groups
    n = x.shape[0]
    c = x.shape[channel_axis]
    if channel_axis == 1:
        xg = jnp.reshape(x, (n, num_groups, c // num_groups, *x.shape[2:]))
        axes = tuple(range(2, xg.ndim))
    else:
        xg = jnp.reshape(x, (*x.shape[:-1], num_groups, c // num_groups))
        axes = tuple(range(1, xg.ndim - 2)) + (xg.ndim - 1,)
    xf = xg.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    out = ((xf - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape)
    shape = [1] * x.ndim
    shape[channel_axis] = -1
    out = out * weight.astype(jnp.float32).reshape(shape) + bias.astype(jnp.float32).reshape(shape)
    return out.astype(x.dtype)


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5, data_format="NCHW", name=None):
    ch_axis = 1 if data_format.startswith("NC") else unwrap(x).ndim - 1
    c = unwrap(x).shape[ch_axis]
    if weight is None:
        weight = Tensor(jnp.ones((c,), unwrap(x).dtype), _internal=True)
    if bias is None:
        bias = Tensor(jnp.zeros((c,), unwrap(x).dtype), _internal=True)
    return apply("group_norm", x, weight, bias, num_groups=int(num_groups),
                 epsilon=float(epsilon), channel_axis=ch_axis)


@register("instance_norm")
def _instance_norm(x, weight, bias, *, epsilon):
    axes = tuple(range(2, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + epsilon)
    shape = [1, -1] + [1] * (x.ndim - 2)
    out = out * weight.astype(jnp.float32).reshape(shape) + bias.astype(jnp.float32).reshape(shape)
    return out.astype(x.dtype)


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW", name=None):
    c = unwrap(x).shape[1]
    if weight is None:
        weight = Tensor(jnp.ones((c,), unwrap(x).dtype), _internal=True)
    if bias is None:
        bias = Tensor(jnp.zeros((c,), unwrap(x).dtype), _internal=True)
    return apply("instance_norm", x, weight, bias, epsilon=float(eps))


@register("l2_normalize")
def _l2_normalize(x, *, axis, epsilon):
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    return x / jnp.maximum(norm, epsilon)


def l2_normalize(x, axis=-1, epsilon=1e-12, name=None):
    return apply("l2_normalize", x, axis=axis, epsilon=float(epsilon))


@register("p_normalize")
def _p_normalize(x, *, p, axis, epsilon):
    n = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=True) ** (1.0 / p)
    return x / jnp.maximum(n, epsilon)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return apply("p_normalize", x, p=float(p), axis=axis, epsilon=float(epsilon))


@register("local_response_norm")
def _lrn(x, *, size, alpha, beta, k):
    # NCHW cross-channel LRN (ref: lrn_op.cc)
    sq = jnp.square(x)
    half = size // 2
    pad = jnp.pad(sq, ((0, 0), (half, size - half - 1), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(size))
    return x / jnp.power(k + alpha * acc, beta)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    return apply("local_response_norm", x, size=int(size), alpha=float(alpha),
                 beta=float(beta), k=float(k))


lrn = local_response_norm


@register("spectral_norm_op")
def _spectral_norm(w, *, dim, power_iters, eps):
    # ref: nn.py spectral_norm (spectral_norm_op.cc): normalize a weight
    # by its largest singular value, estimated with power iteration.
    perm = (dim,) + tuple(i for i in range(w.ndim) if i != dim)
    wm = jnp.transpose(w, perm).reshape(w.shape[dim], -1)
    u = jnp.ones((wm.shape[0],), jnp.float32)
    v = jnp.ones((wm.shape[1],), jnp.float32)

    def it(_, uv):
        u, v = uv
        v = wm.T @ u
        v = v / (jnp.linalg.norm(v) + eps)
        u = wm @ v
        u = u / (jnp.linalg.norm(u) + eps)
        return u, v

    u, v = jax.lax.fori_loop(0, power_iters, it, (u, v))
    sigma = u @ wm @ v
    return w / sigma


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    return apply("spectral_norm_op", weight, dim=int(dim),
                 power_iters=int(power_iters), eps=float(eps))


@register("data_norm_op")
def _data_norm(x, batch_size, batch_sum, batch_square_sum, *, epsilon):
    # ref: nn.py data_norm (data_norm_op.cc): normalize with accumulated
    # batch statistics (a CTR-model staple; stats updated by the caller).
    # Stats are per-channel (C,); broadcast along axis 1 for NC* layouts.
    shape = (1, -1) + (1,) * (x.ndim - 2)
    mean = (batch_sum / batch_size).reshape(shape)
    var = (batch_square_sum / batch_size).reshape(shape) - mean * mean
    return (x - mean) / jnp.sqrt(var + epsilon)


def data_norm(input, act=None, epsilon=1e-5, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False, slot_dim=-1,
              summary_decay_rate=0.9999999, stats=None):
    """Accumulated-stats normalization (ref: nn.py data_norm). Pass
    ``stats=(batch_size, batch_sum, batch_square_sum)`` (each (C,)); when
    omitted, per-feature batch statistics of ``input`` are used."""
    if stats is None:
        xv = unwrap(input)
        n = float(np.prod([s for i, s in enumerate(xv.shape) if i != 1]))
        axes = tuple(i for i in range(xv.ndim) if i != 1)
        bsize = Tensor(jnp.full((xv.shape[1],), n, jnp.float32), _internal=True)
        bsum = apply("_dn_sum", input, axes=axes)
        bsq = apply("_dn_sqsum", input, axes=axes)
        stats = (bsize, bsum, bsq)
    out = apply("data_norm_op", input, *stats, epsilon=float(epsilon))
    if act is not None:
        from ..nn import functional as F

        out = getattr(F, act)(out)
    return out


@register("_dn_sum")
def _dn_sum(x, *, axes):
    return jnp.sum(x.astype(jnp.float32), axis=axes)


@register("_dn_sqsum")
def _dn_sqsum(x, *, axes):
    return jnp.sum(x.astype(jnp.float32) ** 2, axis=axes)
