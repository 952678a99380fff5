"""The rotary embedding as one pallas TPU kernel, forward and backward by hand
(``nn.functional.decoder``'s ``rotary``).

The heads ``x`` are (..., L, d) bfloat16, a hundred megabytes a layer; the
tables are (L, r) float32. The jnp form widens the heads to float32, builds
``rotate_half`` as a concatenation of two negated half-width slices and leaves
the backward to autodiff (pads and slices of float32): the float32 copies
cross HBM several times a pass. Here one kernel, ``rope``, reads a tile of
rows of a few heads in their own dtype once, widens it in VMEM, computes

    out = x * a + turn(x) * b

over the lane-aligned columns that hold the rotated ones (``turn`` moves each
rotated column's partner, half a rotation away, into its lane: lane rolls),
rounds once and writes the tile once; every other column is copied as it is.
``a`` and ``b`` are the tables in that window's frame, made outside the kernel
(a few megabytes of XLA code over constants):

- forward, ``a = cos`` and ``b = sign * sin`` with ``sign`` -1 over the first
  half of the rotated columns and +1 over the second, so that ``turn(x) * b``
  is ``rotate_half(x) * sin``: the same two products and one sum an element
  as the jnp form, in float32, and one rounding;
- backward, the same kernel on the cotangent with ``b = -sign * turn(sin)``:
  ``g * cos + rotate_half^T(g * sin)`` in general, and ``rotary(g, cos,
  -sin)`` for ``rotary_cos_sin``'s tables, whose halves are equal.

The tables get no gradient and are the only residuals. Which calls the kernel
takes is :func:`rotary_route`; the tile rule is :func:`tiles`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BAND = 64                      # rows in flight: the tables' band stays in
                               # registers over a block's heads
ROWS = 512                     # rows a grid step
HEADS = 8                      # heads a grid step, at most
BLOCK_BYTES = 4 * 2 ** 20      # of a grid step's heads; four such in VMEM
VMEM_LIMIT = 48 * 2 ** 20      # of v5e's 128 MiB; the default scope is 16
F32 = jnp.float32
BF16 = jnp.bfloat16


# ---- the rules ---------------------------------------------------------------
def tiles(n, length, d):
    """``(heads, rows)`` of a grid step over ``n`` heads of ``length`` rows by
    ``d`` bfloat16 columns: ``ROWS`` halved until it divides ``length``, and
    the most heads up to ``HEADS`` that divide ``n`` and keep the block in
    ``BLOCK_BYTES``; None where that leaves less than one band of rows or no
    head."""
    tl = ROWS
    while tl >= BAND and length % tl:
        tl //= 2
    row = 2 * -(-d // LANES) * LANES
    fit = [h for h in range(1, HEADS + 1)
           if n % h == 0 and h * tl * row <= BLOCK_BYTES]
    return (max(fit), tl) if tl >= BAND and fit else None


def _window(d, r, offset):
    """The lane-aligned columns ``[lo, hi)`` of a head of ``d`` that hold the
    rotated ones ``[offset, offset + r)``."""
    lo = offset // LANES * LANES
    return lo, min(d, -(-(offset + r) // LANES) * LANES)


def rotary_route(x_shape, x_dtype, r, offset=0):
    """``(in_specs, out_specs)`` for ``ops.pallas.run`` where the kernel takes
    the rotation of heads ``x`` (..., L, d) over the ``r`` columns from
    ``offset``, else ``None`` (the caller's jnp body): a TPU backend, bfloat16
    heads, rotated columns inside the head that start and end on half a lane
    row (``offset``, ``r`` and ``d`` multiples of 64: the windows Mosaic was
    seen to take), rows and heads :func:`tiles` divides, and one device's
    heads: under a mesh to wrap over the call stays dense."""
    from . import _kernel_mesh, enabled

    if not (enabled() and len(x_shape) >= 2 and x_dtype == BF16 and
            offset >= 0 and 0 < r <= x_shape[-1] - offset and
            not any(v % (LANES // 2) for v in (r, offset, x_shape[-1])) and
            tiles(math.prod(x_shape[:-2]), *x_shape[-2:])):
        return None
    return ((), None) if _kernel_mesh() is None else None


# ---- the kernel --------------------------------------------------------------
def _rope_kernel(x_ref, a_ref, b_ref, o_ref, *, lo, hi, start, r):
    # x_ref, o_ref: (heads, rows, d); a_ref, b_ref: (rows, hi - lo) float32.
    # The rotated columns are [start, start + r) of the window [lo, hi)
    hb, tl, d = x_ref.shape
    w, half = hi - lo, r // 2
    if r < w:
        lane = jax.lax.broadcasted_iota(jnp.int32, (BAND, w), 1)
        first, rotated = lane < start + half, \
            (lane >= start) & (lane < start + r)

    def band(i, carry):
        rows = pl.ds(pl.multiple_of(i * BAND, BAND), BAND)
        a, b = a_ref[rows, :], b_ref[rows, :]
        for h in range(hb):
            xf = x_ref[h, rows, lo:hi].astype(F32)
            # the partner of column j: j + half in the first half of the
            # rotated columns, j - half in the second; neither wraps
            turned = pltpu.roll(xf, half, 1)
            if r < w:
                turned = jnp.where(first, pltpu.roll(xf, w - half, 1), turned)
            out = xf * a + turned * b
            if r < w:
                out = jnp.where(rotated, out, xf)
            o_ref[h, rows, lo:hi] = out.astype(o_ref.dtype)
            if lo:
                o_ref[h, rows, :lo] = x_ref[h, rows, :lo]
            if hi < d:
                o_ref[h, rows, hi:] = x_ref[h, rows, hi:]
        return carry

    jax.lax.fori_loop(0, tl // BAND, band, 0)


@functools.partial(jax.jit, static_argnames=("lo", "start", "r", "interpret"))
def _rope_call(x, a, b, *, lo, start, r, interpret):
    """``x`` (n, L, d) with ``x * a + turn(x) * b`` over the columns ``[lo, lo
    + w)``, ``w`` the tables' width, of which ``[start, start + r)`` rotate
    among themselves."""
    n, length, d = x.shape
    w = a.shape[1]
    hb, tl = tiles(n, length, d)
    heads = pl.BlockSpec((hb, tl, d), lambda t, h: (h, t, 0))
    table = pl.BlockSpec((tl, w), lambda t, h: (t, 0))
    # heads are the minor grid axis: a row tile's tables are fetched once
    return pl.pallas_call(
        functools.partial(_rope_kernel, lo=lo, hi=lo + w, start=start, r=r),
        grid=(length // tl, n // hb),
        in_specs=[heads, table, table], out_specs=heads,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=f"rope_r{r}")(x, a, b)


def _apply(x, cos, sin, offset, transposed, interpret):
    d, r = x.shape[-1], cos.shape[-1]
    lo, hi = _window(d, r, offset)
    start = offset - lo
    sign = jnp.where(jnp.arange(r) < r // 2, -1.0, 1.0).astype(F32)
    cos, sin = cos.astype(F32), sin.astype(F32)
    b = -sign * jnp.roll(sin, r // 2, axis=-1) if transposed else sign * sin
    pad = [(0, 0), (start, hi - lo - start - r)]
    out = _rope_call(x.reshape(-1, *x.shape[-2:]), jnp.pad(cos, pad),
                     jnp.pad(b, pad), lo=lo, start=start, r=r,
                     interpret=interpret)
    return out.reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def rope(x, cos, sin, offset=0, interpret=False):
    """``x`` (..., L, d) with its columns ``[offset, offset + r)`` rotated by
    position (rotate-half among themselves); ``cos`` and ``sin`` (L, r)."""
    return _rope_fwd(x, cos, sin, offset, interpret)[0]


def _rope_fwd(x, cos, sin, offset, interpret):
    return _apply(x, cos, sin, offset, False, interpret), (cos, sin)


def _rope_bwd(offset, interpret, saved, g):
    cos, sin = saved
    return (_apply(g, cos, sin, offset, True, interpret),
            jnp.zeros_like(cos), jnp.zeros_like(sin))


rope.defvjp(_rope_fwd, _rope_bwd)
