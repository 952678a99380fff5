"""Fused LayerNorm as a pallas TPU kernel (fwd + custom_vjp bwd).

TPU-native analog of the reference's hand-fused CUDA layer_norm kernel
(paddle/fluid/operators/layer_norm_op.cu): one VMEM pass computes the
moments, normalizes, and applies scale/shift; the backward kernel fuses
the three-term gradient in a single pass. Stats are f32 even for bf16
activations.

Layout: (N, D) rows; leading dims are flattened here.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    y = xhat * g_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean      # (bn, 1): 2-D so the block is TPU-tileable
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref, dx_ref, dg_ref,
                db_ref):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    xhat = (x - mean) * rstd
    wdy = dy * g
    c1 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    c2 = jnp.mean(wdy, axis=-1, keepdims=True)
    dx = (wdy - xhat * c1 - c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # dgamma/dbeta: accumulate into one (D,) block revisited across the
    # sequential TPU grid (a (1, D) partial-per-block output would violate
    # the (8, 128) min-tile rule)
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[:] = jnp.zeros_like(dg_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    dg_ref[:] += jnp.sum(dy * xhat, axis=0)
    db_ref[:] += jnp.sum(dy, axis=0)


def _pick_rows(N, want=256):
    b = min(want, N)
    while N % b:
        b //= 2
    return max(b, 1)


def layer_norm_route(x_shape, begin_norm_axis, scale_ndim, shift_ndim):
    """``(in_specs, out_specs)`` for ``ops.pallas.run`` where these kernels
    take a layer-norm call, else ``None`` (the caller's dense path): a TPU
    backend, the last axis with a 1D scale and shift, ``D % 128 == 0`` and a
    multiple of 8 rows a device. Under a mesh each device normalizes its own
    batch rows."""
    from . import BATCH, P, enabled, shard_spec

    if not (enabled() and begin_norm_axis == len(x_shape) - 1 and
            scale_ndim == 1 and shift_ndim == 1 and x_shape[-1] % 128 == 0):
        return None
    spec, local = shard_spec(x_shape, {0: BATCH})
    return ((spec, P(), P()), spec) if math.prod(local[:-1]) % 8 == 0 else None


def fused_layer_norm(x, gamma, beta, eps=1e-5, interpret=False):
    """x: (..., D), normalized over D as ``(N, D)`` rows; gamma/beta: (D,)."""
    return _rows_layer_norm(x.reshape(-1, x.shape[-1]), gamma, beta, eps,
                            interpret).reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rows_layer_norm(x, gamma, beta, eps, interpret):
    y, _, _ = _ln_call(x, gamma, beta, eps, interpret)
    return y


def _ln_call(x, gamma, beta, eps, interpret):
    N, D = x.shape
    bn = _pick_rows(N)
    kern = functools.partial(_fwd_kernel, eps=float(eps))
    y, mean, rstd = pl.pallas_call(
        kern,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), x.dtype),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        interpret=interpret,
        name="layer_norm_fwd",
    )(x, gamma, beta)
    return y, mean, rstd


def _ln_fwd(x, gamma, beta, eps, interpret):
    y, mean, rstd = _ln_call(x, gamma, beta, eps, interpret)
    return y, (x, gamma, mean, rstd)


def _ln_bwd(eps, interpret, res, dy):
    x, gamma, mean, rstd = res
    N, D = x.shape
    bn = _pick_rows(N)
    nblocks = N // bn
    dx, dg, db = pl.pallas_call(
        _bwd_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), x.dtype),
            jax.ShapeDtypeStruct((D,), jnp.float32),
            jax.ShapeDtypeStruct((D,), jnp.float32),
        ],
        interpret=interpret,
        name="layer_norm_bwd",
    )(x, gamma, mean, rstd, dy)
    return dx, dg.astype(gamma.dtype), db.astype(gamma.dtype)


_rows_layer_norm.defvjp(_ln_fwd, _ln_bwd)
