"""Fused softmax + cross-entropy as a pallas TPU kernel.

TPU-native analog of the reference's fused CUDA kernel
(paddle/fluid/operators/softmax_with_cross_entropy_op.cu): the (N, V)
logits never materialize a softmax — a blocked pass over the vocab axis
keeps a running (max, sumexp, label-logit) triple in VMEM scratch, so
memory is O(N) and each grid step touches one (bn, bv) logits tile (a
full (bn, V) row block at V ≈ 50k would blow the ~16 MB VMEM budget).
Backward fuses softmax-minus-onehot.

Block sizes come from the shapes by one rule, :func:`block_sizes`: the row
block divides N, the vocabulary block is ``min(target, V)`` and NEED NOT
divide V. The grid has ``cdiv(V, bv)`` vocabulary blocks and the last one
may hang over the array's edge: what a kernel reads there is unspecified
(NaN in the interpreter), so the forward sets those columns to ``NEG_INF``
with a select before the max and the exponent, in the last block only; the
backward's writes there are dropped by Pallas and nothing accumulates. A
block that must divide V degenerates where V has few factors of two: GPT-2's
50,304 = 128 x 3 x 131 columns got 128-wide blocks and 25,152 grid steps a
call, at about 0.35 us a step whatever it does (PERF.md, PR 28).

ignore_index rows contribute 0 loss and 0 gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
VMEM_BUDGET = 12 * 2 ** 20        # flash_attention's: of the 16 MiB a kernel gets

# (bn, bv) to aim for; the chip's sweep found it (PERF.md, PR 28)
_TARGET = (256, 2048)


def vmem_bytes(bn, bv, itemsize):
    """What one grid step of the larger kernel (backward) holds in VMEM: the
    double-buffered logits block in and gradient block out, and the tile's
    float32 temporaries (the logits, ``p``, the gradient before its cast)
    counted as whole tiles. That is the safe side: Mosaic keeps fewer, and
    pairs this count puts at 20 MiB ran inside its 16 MiB in PR 28's sweep."""
    return bn * bv * (2 * 2 * itemsize + 3 * 4)


def block_sizes(N, V, itemsize):
    """(bn, bv) for both kernels, from the shapes alone. ``bn`` divides N
    (the target halved until it does). ``bv`` is the target or the whole of a
    narrower V; it need not divide V. Float32 logits double the tile's bytes:
    ``bv`` is halved, in multiples of 128 lanes, until the tile and its
    float32 temporaries fit the VMEM budget."""
    want_n, want_v = _TARGET
    bn = min(want_n, N)
    while N % bn:
        bn //= 2
    bv = min(want_v, V)
    while vmem_bytes(bn, bv, itemsize) > VMEM_BUDGET and bv > LANES:
        bv = max(bv // 2 // LANES * LANES, LANES)
    return bn, bv


def softmax_ce_route(logits_shape, label_shape, weighted, axis, use_softmax,
                     label_smoothing):
    """``(in_specs, out_specs)`` for ``ops.pallas.run`` (labels squeezed to
    ``(N,)``) where these kernels take a hard-label call, else ``None`` (the
    caller's dense path): a TPU backend and the LM-head case of 2D ``(N, V)``
    logits through a softmax over their last axis, no class weights, no
    smoothing, a device's ``N % 8 == 0`` and ``V % 128 == 0``. Under a mesh
    the rows split over the data AND model axes (each device needs its rows'
    whole vocabulary, so vocab-sharded logits are resharded by rows at the
    ``mesh_call`` boundary)."""
    from . import ROWS, P, enabled, shard_spec

    if not (enabled() and not weighted and use_softmax and
            label_smoothing == 0.0 and len(logits_shape) == 2 and
            axis in (-1, 1) and len(label_shape) in (1, 2)):
        return None
    spec, (n, v) = shard_spec(logits_shape, {0: ROWS})
    rows = P(spec[0])
    return ((spec, rows), rows) if n % 8 == 0 and v % 128 == 0 else None


def _fwd_kernel(x_ref, lab_ref, loss_ref, lse_ref, m_ref, s_ref, t_ref, *,
                nv, tail, ignore_index):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        s_ref[:] = jnp.zeros_like(s_ref)
        t_ref[:] = jnp.zeros_like(t_ref)

    bn, bv = x_ref.shape
    lab = lab_ref[:]                              # (bn, 1) int32

    def update(width):
        """One block into the running statistics; ``width`` of its columns
        lie inside the array."""
        blk = x_ref[:].astype(jnp.float32)        # (bn, bv)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
        if width < bv:
            blk = jnp.where(cols < width, blk, NEG_INF)
        m = m_ref[:]
        m_new = jnp.maximum(m, jnp.max(blk, axis=-1, keepdims=True))
        s_ref[:] = s_ref[:] * jnp.exp(m - m_new) + \
            jnp.sum(jnp.exp(blk - m_new), axis=-1, keepdims=True)
        hit = jnp.where(cols == lab - vi * bv, blk, 0.0)
        t_ref[:] += jnp.sum(hit, axis=-1, keepdims=True)
        m_ref[:] = m_new

    if tail == bv:
        update(bv)
    else:               # only the last block pays for the compare
        pl.when(vi < nv - 1)(lambda: update(bv))
        pl.when(vi == nv - 1)(lambda: update(tail))

    @pl.when(vi == nv - 1)
    def _finish():
        lse = m_ref[:] + jnp.log(jnp.maximum(s_ref[:], 1e-30))
        loss_ref[:] = jnp.where(lab != ignore_index, lse - t_ref[:], 0.0)
        lse_ref[:] = lse


def _bwd_kernel(x_ref, lab_ref, lse_ref, g_ref, dx_ref, *, ignore_index):
    # columns of the last block beyond V hold whatever was read there; they
    # feed no other column and Pallas drops their writes
    bn, bv = x_ref.shape
    lab = lab_ref[:]                              # (bn, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    p = jnp.exp(x_ref[:].astype(jnp.float32) - lse_ref[:])
    g = jnp.where(lab != ignore_index, g_ref[:], 0.0)
    hit = cols == lab - pl.program_id(1) * bv
    dx_ref[:] = (jnp.where(hit, p - 1.0, p) * g).astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def softmax_cross_entropy(logits, labels, ignore_index=-100,
                          interpret=False):
    """logits: (N, V), labels: (N,) int32 -> per-row loss (N,) f32."""
    loss, _ = _ce_fwd(logits, labels, ignore_index, interpret)
    return loss


def _ce_call(logits, labels, ignore_index, interpret):
    N, V = logits.shape
    bn, bv = block_sizes(N, V, logits.dtype.itemsize)
    nv = pl.cdiv(V, bv)
    lab2 = labels.astype(jnp.int32).reshape(N, 1)
    kern = functools.partial(_fwd_kernel, nv=nv, tail=V - (nv - 1) * bv,
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
    bn, bv = block_sizes(N, V, logits.dtype.itemsize)
    lab2 = labels.astype(jnp.int32).reshape(N, 1)
    kern = functools.partial(_bwd_kernel, ignore_index=ignore_index)
    dx = pl.pallas_call(
        kern,
        grid=(N // bn, pl.cdiv(V, bv)),
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
