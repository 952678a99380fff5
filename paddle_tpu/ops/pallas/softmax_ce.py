"""Fused softmax + cross-entropy as a pallas TPU kernel.

TPU-native analog of the reference's fused CUDA kernel
(paddle/fluid/operators/softmax_with_cross_entropy_op.cu): the (N, V)
logits never materialize a softmax — a blocked pass over the vocab axis
keeps a running (max, sumexp, label-logit) triple in VMEM scratch, so
memory is O(N) and each grid step touches one (bn, bv) logits tile (a
full (bn, V) row block at V ≈ 50k would blow the ~16 MB VMEM budget).
Backward fuses softmax-minus-onehot.

ignore_index rows contribute 0 loss and 0 gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(x_ref, lab_ref, loss_ref, lse_ref, m_ref, s_ref, t_ref, *,
                nv, block_v, ignore_index):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        s_ref[:] = jnp.zeros_like(s_ref)
        t_ref[:] = jnp.zeros_like(t_ref)

    blk = x_ref[:].astype(jnp.float32)            # (bn, bv)
    lab = lab_ref[:]                              # (bn, 1) int32
    bn, bv = blk.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1) + vi * block_v
    m = m_ref[:]
    m_new = jnp.maximum(m, jnp.max(blk, axis=-1, keepdims=True))
    s_ref[:] = s_ref[:] * jnp.exp(m - m_new) + \
        jnp.sum(jnp.exp(blk - m_new), axis=-1, keepdims=True)
    hit = (cols == lab).astype(jnp.float32)
    t_ref[:] += jnp.sum(blk * hit, axis=-1, keepdims=True)
    m_ref[:] = m_new

    @pl.when(vi == nv - 1)
    def _finish():
        lse = m_ref[:] + jnp.log(jnp.maximum(s_ref[:], 1e-30))
        valid = (lab != ignore_index).astype(jnp.float32)
        loss_ref[:] = (lse - t_ref[:]) * valid
        lse_ref[:] = lse


def _bwd_kernel(x_ref, lab_ref, lse_ref, g_ref, dx_ref, *, ignore_index):
    x = x_ref[:].astype(jnp.float32)              # (bn, bv)
    lab = lab_ref[:]                              # (bn, 1)
    lse = lse_ref[:]                              # (bn, 1)
    g = g_ref[:]                                  # (bn, 1)
    bn, bv = x.shape
    vi = pl.program_id(1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1) + vi * bv
    p = jnp.exp(x - lse)
    onehot = (cols == lab).astype(jnp.float32)
    valid = (lab != ignore_index).astype(jnp.float32)
    dx_ref[:] = ((p - onehot) * g * valid).astype(dx_ref.dtype)


def _pick(n, want):
    b = min(want, n)
    while n % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def softmax_cross_entropy(logits, labels, ignore_index=-100,
                          interpret=False):
    """logits: (N, V), labels: (N,) int32 -> per-row loss (N,) f32."""
    loss, _ = _ce_fwd(logits, labels, ignore_index, interpret)
    return loss


def _ce_call(logits, labels, ignore_index, interpret):
    N, V = logits.shape
    bn = _pick(N, 256)
    bv = _pick(V, 2048)
    nv = V // bv
    lab2 = labels.astype(jnp.int32).reshape(N, 1)
    kern = functools.partial(_fwd_kernel, nv=nv, block_v=bv,
                             ignore_index=ignore_index)
    loss, lse = pl.pallas_call(
        kern,
        grid=(N // bn, nv),        # vocab axis iterates fastest (sequential)
        in_specs=[
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
        ],
        interpret=interpret,
        name="softmax_ce_fwd",
    )(logits, lab2)
    return loss[:, 0], lse


def _ce_fwd(logits, labels, ignore_index, interpret):
    loss, lse = _ce_call(logits, labels, ignore_index, interpret)
    return loss, (logits, labels, lse)


def _ce_bwd(ignore_index, interpret, res, g):
    logits, labels, lse = res
    N, V = logits.shape
    bn = _pick(N, 256)
    bv = _pick(V, 2048)
    lab2 = labels.astype(jnp.int32).reshape(N, 1)
    kern = functools.partial(_bwd_kernel, ignore_index=ignore_index)
    dx = pl.pallas_call(
        kern,
        grid=(N // bn, V // bv),
        in_specs=[
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, V), logits.dtype),
        interpret=interpret,
        name="softmax_ce_bwd",
    )(logits, lab2, lse, g.astype(jnp.float32).reshape(N, 1))
    return dx, None


softmax_cross_entropy.defvjp(_ce_fwd, _ce_bwd)
