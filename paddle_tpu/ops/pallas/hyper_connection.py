"""The multi-stream residual's passes over its streams as pallas TPU kernels,
forward and backward by hand (``nn.functional.decoder``'s ``hc_maps``,
``hc_read`` and ``hc_mix``; manifold-constrained hyper-connections).

The streams ``x`` are (n, T, C) bfloat16, tens of megabytes each; the maps are
a few floats a token. The jnp forms of the three ops are sums of products over
float32 copies of the streams, and autodiff makes those copies again, stacks
and transposes them: twenty fusions of 2 ms a step where the bytes want 0.5.
Here every pass reads a token tile of the bf16 streams from HBM once, whole
rows of C channels, keeps its float32 working values in registers (a band of
``BAND`` rows by ``CHUNK`` channels at a time) and writes bf16 once:

- ``hc_norm_proj`` (under ``hc_maps``): the sum of squares over a token's n C
  values and the maps' raw projection ``sum_j x[j] @ phi[j]``. The streams are
  bf16, so ``x`` is exact in one bf16 limb and the float32 product at
  ``Precision.HIGHEST`` is ``x`` against the (up to) three bf16 limbs of
  ``phi``, laid side by side as columns of one MXU pass and summed in float32
  outside. Backward: ``dx[j] = 2 d_ss x[j] + d_dyn @ phi[j]^T`` (the limbs of
  ``d_dyn`` against those of ``phi``, every pair down to 2^-24, as rows of one
  contraction) and ``d phi[j] = x[j]^T @ d_dyn`` accumulated in float32 over
  the token tiles, in one pass. The sigmoids, the clip and the Sinkhorn rounds
  stay the caller's lane-dense XLA code, with autodiff.
- ``hc_read``: forward is the jnp form (XLA fuses it into the norm that reads
  it: no pass of its own); backward one pass gives ``pre[j] dh`` and
  ``d pre[j] = sum_c dh x[j]``.
- ``hc_mix``: ``x'[i] = sum_j res[i, j] x[j] + post[i] y``; backward one pass
  over ``x``, ``dx'`` and ``y`` gives ``sum_i res[i, j] dx'[i]``,
  ``dy = sum_i post[i] dx'[i]``, ``d res[i, j] = sum_c dx'[i] x[j]`` and
  ``d post[i] = sum_c dx'[i] y``.

The maps reach a kernel token-major, ``(T, k)`` float32 with a token's
coefficients along the lanes (made once from the (k, T) the Sinkhorn rounds
want, in XLA: 0.4 MB), and the per-token sums leave as ``(T, 128)`` with
coefficient ``k`` in lane ``k``. Every sum over streams, channels or tokens is
float32. Which calls the kernels take is :func:`hc_route`; the tile rule is
:func:`token_tile`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BAND = 16                      # rows in flight: one packed bf16 tile
CHUNK = 1792                   # channels in flight
VMEM_LIMIT = 64 * 2 ** 20      # of v5e's 128 MiB; the default scope is 16
VMEM_BUDGET = 40 * 2 ** 20     # a call's blocks, double-buffered

# token rows a grid step, a pass; the chip's sweep found them (PERF.md, PR 41)
_WANT = {"maps_fwd": 128, "maps_bwd": 128, "read_bwd": 64, "mix_fwd": 64,
         "mix_bwd": 64}
F32 = jnp.float32
BF16 = jnp.bfloat16


# ---- the rules ---------------------------------------------------------------
def _pass_bytes(n, c):
    """{pass: (bytes a token row, bytes whatever the tile)} of each call's
    VMEM blocks, before double buffering."""
    row, k = 2 * c, 2 * n + n * n
    kp, r = _round_up(6 * k, LANES), _round_up(3 * k, BAND)
    return {"maps_fwd": (n * row, n * c * LANES * 2),
            "maps_bwd": (2 * n * row, n * c * (kp * 2 + r * 4)),
            "read_bwd": ((2 * n + 1) * row, 0),
            "mix_fwd": ((2 * n + 1) * row, 0),
            "mix_bwd": ((3 * n + 2) * row, 0)}


def token_tile(which, n, tokens, c):
    """Token rows a grid step of pass ``which``: the pass's target halved
    until it divides ``tokens`` and the call's blocks fit the VMEM budget;
    None where that leaves less than one packed bf16 tile. ``maps_bwd``'s
    tile is also the lane dimension of one operand (the cotangent's limbs,
    token-minor), so it is whole lanes or all of the tokens."""
    row, fixed = _pass_bytes(n, c)[which]
    whole = LANES if which == "maps_bwd" else BAND
    tt = _WANT[which]
    while tt >= BAND and (tokens % tt or (tt % whole and tt != tokens) or
                          2 * (fixed + tt * row) > VMEM_BUDGET):
        tt //= 2
    return tt if tt >= BAND else None


def hc_route(x_shape, x_dtype):
    """``(in_specs, out_specs)`` for ``ops.pallas.run`` where these kernels
    take the ``hc_*`` ops of streams ``x`` (n, ..., C), else ``None`` (the
    caller's dense path): a TPU backend, bfloat16 streams (the projection's
    one-limb product is exact for them alone), 2 to 5 of them (three limbs of
    a token's ``2 n + n^2`` coefficients and its sum of squares share 128
    lanes), ``C % 128 == 0``, a token count every pass's tile divides, and
    one device's streams: under a mesh to wrap over the call stays dense
    (``d phi`` sums over every token, which a row-parallel body cannot)."""
    from . import _kernel_mesh, enabled

    if not (enabled() and len(x_shape) >= 3 and x_dtype == BF16 and
            2 <= x_shape[0] <= 5 and x_shape[-1] % LANES == 0):
        return None
    n, c = x_shape[0], x_shape[-1]
    tokens = math.prod(x_shape[1:-1])
    if not all(token_tile(p, n, tokens, c) for p in _WANT):
        return None
    return ((), None) if _kernel_mesh() is None else None


# ---- pieces ------------------------------------------------------------------
def _round_up(a, b):
    return -(-a // b) * b


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _dot(a, b, widen):
    """bf16 operands, one MXU pass, float32 out: every product is exact.
    ``widen``: the interpreter's case, where the host's dot has no bf16 x
    bf16 -> float32: the operands go up first, and the products are the
    same."""
    if widen:
        return jnp.dot(a.astype(F32), b.astype(F32),
                       precision=jax.lax.Precision.HIGHEST)
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=F32,
                               precision=jax.lax.Precision.DEFAULT)


def _chunks(c):
    return [(lo, min(CHUNK, c - lo)) for lo in range(0, c, CHUNK)]


def _bands(rows, body):
    """``body(rows)`` for each band of ``BAND`` rows of a tile of ``rows``."""
    def step(b, carry):
        body(pl.ds(pl.multiple_of(b * BAND, BAND), BAND))
        return carry

    jax.lax.fori_loop(0, rows // BAND, step, 0)


def _fold(p):
    """(rows, w) -> (rows, 128): the lane groups added, no cross-lane work."""
    return sum(p[:, g:g + LANES] for g in range(0, p.shape[1], LANES))


def _place(sums):
    """(rows, 128) float32 with the row sum of ``sums[k]`` in lane ``k``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, sums[0].shape, 1)
    out = jnp.zeros(sums[0].shape, F32)
    for k, s in enumerate(sums):
        out = jnp.where(lane == k, jnp.sum(s, axis=-1, keepdims=True), out)
    return out


def _limbs(a, count):
    """``a`` float32 as ``count`` bf16 arrays whose sum is ``a`` to 2^-8 count
    (three: to float32's own 2^-24)."""
    out, rest = [], a.astype(F32)
    for _ in range(count):
        out.append(rest.astype(BF16))
        rest = rest - out[-1].astype(F32)
    return out


def _pad_last(a, width):
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, width - a.shape[-1])])


def _token_major(*maps):
    """Maps (k, tokens...) float32, token-minor as the Sinkhorn rounds hold
    them, as one (T, sum of k) table with a token's coefficients on the
    lanes."""
    rows = [m.astype(F32).reshape(m.shape[0], -1) for m in maps]
    return jnp.concatenate(rows, axis=0).T


def _streams(n, tt, c):
    """BlockSpec of a token tile of the streams (n, T, C)."""
    return pl.BlockSpec((n, tt, c), lambda t: (0, t, 0))


def _tokens(tt, width):
    """BlockSpec of a token tile of a (T, width) array, its rows whole."""
    return pl.BlockSpec((tt, width), lambda t: (t, 0))


# ---- hc_mix ------------------------------------------------------------------
def _mix_fwd_kernel(x_ref, y_ref, m_ref, o_ref):
    n, tt, c = x_ref.shape

    def band(rows):
        m = m_ref[rows, :]                        # post | res, (BAND, n + n n)
        col = [m[:, k:k + 1] for k in range(n + n * n)]
        for lo, w in _chunks(c):
            cs = slice(lo, lo + w)
            xs = [x_ref[j, rows, cs].astype(F32) for j in range(n)]
            y = y_ref[rows, cs].astype(F32)
            for i in range(n):
                acc = col[n + i * n] * xs[0]
                for j in range(1, n):
                    acc = acc + col[n + i * n + j] * xs[j]
                o_ref[i, rows, cs] = (acc + col[i] * y).astype(o_ref.dtype)

    _bands(tt, band)


def _mix_bwd_kernel(x_ref, y_ref, g_ref, m_ref, dx_ref, dy_ref, dm_ref):
    n, tt, c = x_ref.shape

    def band(rows):
        m = m_ref[rows, :]
        col = [m[:, k:k + 1] for k in range(n + n * n)]
        sums = [jnp.zeros((BAND, LANES), F32)] * (n + n * n)
        for lo, w in _chunks(c):
            cs = slice(lo, lo + w)
            xs = [x_ref[j, rows, cs].astype(F32) for j in range(n)]
            gs = [g_ref[i, rows, cs].astype(F32) for i in range(n)]
            y = y_ref[rows, cs].astype(F32)
            for j in range(n):
                acc = col[n + j] * gs[0]
                for i in range(1, n):
                    acc = acc + col[n + i * n + j] * gs[i]
                dx_ref[j, rows, cs] = acc.astype(dx_ref.dtype)
            acc = col[0] * gs[0]
            for i in range(1, n):
                acc = acc + col[i] * gs[i]
            dy_ref[rows, cs] = acc.astype(dy_ref.dtype)
            for i in range(n):
                sums[i] = sums[i] + _fold(gs[i] * y)
                for j in range(n):
                    k = n + i * n + j
                    sums[k] = sums[k] + _fold(gs[i] * xs[j])
        dm_ref[rows, :] = _place(sums)

    _bands(tt, band)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mix_fwd_call(x, y, m, *, interpret):
    n, tokens, c = x.shape
    tt = token_tile("mix_fwd", n, tokens, c)
    return pl.pallas_call(
        _mix_fwd_kernel, grid=(tokens // tt,),
        in_specs=[_streams(n, tt, c), _tokens(tt, c), _tokens(tt, m.shape[1])],
        out_specs=_streams(n, tt, c),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params("parallel"), interpret=interpret,
        name="hc_mix_fwd")(x, y, m)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mix_bwd_call(x, y, g, m, *, interpret):
    n, tokens, c = x.shape
    tt = token_tile("mix_bwd", n, tokens, c)
    return pl.pallas_call(
        _mix_bwd_kernel, grid=(tokens // tt,),
        in_specs=[_streams(n, tt, c), _tokens(tt, c), _streams(n, tt, c),
                  _tokens(tt, m.shape[1])],
        out_specs=[_streams(n, tt, c), _tokens(tt, c), _tokens(tt, LANES)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct((tokens, LANES), F32)],
        compiler_params=_params("parallel"), interpret=interpret,
        name="hc_mix_bwd")(x, y, g, m)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def hc_mix(x, y, post, res, interpret=False):
    """The streams (n, ..., C) after a layer whose output is ``y`` (..., C);
    ``post`` (n, ...) and ``res`` (n, n, ...) float32."""
    return _mix_fwd(x, y, post, res, interpret)[0]


def _mix_fwd(x, y, post, res, interpret):
    n, c = x.shape[0], x.shape[-1]
    m = _token_major(post, res.reshape((n * n,) + res.shape[2:]))
    out = _mix_fwd_call(x.reshape(n, -1, c), y.reshape(-1, c), m,
                        interpret=interpret)
    return out.reshape(x.shape), (x, y, m)


def _mix_bwd(interpret, saved, g):
    x, y, m = saved
    n, c = x.shape[0], x.shape[-1]
    dx, dy, dm = _mix_bwd_call(x.reshape(n, -1, c), y.reshape(-1, c),
                               g.reshape(n, -1, c), m, interpret=interpret)
    dm = dm[:, :n + n * n].T
    return (dx.reshape(x.shape), dy.reshape(y.shape),
            dm[:n].reshape(x.shape[:-1]),
            dm[n:].reshape((n,) + x.shape[:-1]))


hc_mix.defvjp(_mix_fwd, _mix_bwd)


# ---- hc_read -----------------------------------------------------------------
def _read_bwd_kernel(x_ref, g_ref, p_ref, dx_ref, dp_ref):
    n, tt, c = x_ref.shape

    def band(rows):
        p = p_ref[rows, :]                                   # (BAND, n)
        col = [p[:, j:j + 1] for j in range(n)]
        sums = [jnp.zeros((BAND, LANES), F32)] * n
        for lo, w in _chunks(c):
            cs = slice(lo, lo + w)
            g = g_ref[rows, cs].astype(F32)
            for j in range(n):
                dx_ref[j, rows, cs] = (col[j] * g).astype(dx_ref.dtype)
                sums[j] = sums[j] + _fold(
                    g * x_ref[j, rows, cs].astype(F32))
        dp_ref[rows, :] = _place(sums)

    _bands(tt, band)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _read_bwd_call(x, g, p, *, interpret):
    n, tokens, c = x.shape
    tt = token_tile("read_bwd", n, tokens, c)
    return pl.pallas_call(
        _read_bwd_kernel, grid=(tokens // tt,),
        in_specs=[_streams(n, tt, c), _tokens(tt, c), _tokens(tt, n)],
        out_specs=[_streams(n, tt, c), _tokens(tt, LANES)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((tokens, LANES), F32)],
        compiler_params=_params("parallel"), interpret=interpret,
        name="hc_read_bwd")(x, g, p)


def _read_dense(x, pre):
    h = sum(pre[j][..., None] * x[j].astype(F32) for j in range(x.shape[0]))
    return h.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def hc_read(x, pre, interpret=False):
    """The layer's input (..., C) from the streams ``x`` (n, ..., C) and
    ``pre`` (n, ...) float32. Forward is the jnp form, which XLA fuses into
    what reads it; the rule below is the backward."""
    return _read_dense(x, pre)


def _read_fwd(x, pre, interpret):
    return _read_dense(x, pre), (x, pre)


def _read_bwd(interpret, saved, g):
    x, pre = saved
    n, c = x.shape[0], x.shape[-1]
    dx, dp = _read_bwd_call(x.reshape(n, -1, c), g.reshape(-1, c),
                            _token_major(pre), interpret=interpret)
    return dx.reshape(x.shape), dp[:, :n].T.reshape(pre.shape)


hc_read.defvjp(_read_fwd, _read_bwd)


# ---- hc_maps: the norm and the projection ------------------------------------
def _maps_fwd_kernel(x_ref, w_ref, o_ref, *, ss_lane, widen):
    n, tt, c = x_ref.shape
    acc = _dot(x_ref[0], w_ref[0], widen)
    for j in range(1, n):
        acc = acc + _dot(x_ref[j], w_ref[j], widen)
    o_ref[...] = acc
    lane = jax.lax.broadcasted_iota(jnp.int32, (BAND, LANES), 1)

    def band(rows):
        ss = jnp.zeros((BAND, LANES), F32)
        for lo, w in _chunks(c):
            for j in range(n):
                xf = x_ref[j, rows, slice(lo, lo + w)].astype(F32)
                ss = ss + _fold(xf * xf)
        o_ref[rows, :] = jnp.where(
            lane == ss_lane, jnp.sum(ss, axis=-1, keepdims=True),
            o_ref[rows, :])

    _bands(tt, band)


def _maps_bwd_kernel(x_ref, g_ref, gt_ref, s_ref, wt_ref, dx_ref, dw_ref, *,
                     widen):
    n, tt, c = x_ref.shape

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    g, gt, s = g_ref[...], gt_ref[...], s_ref[...]
    for j in range(n):
        for lo, w in _chunks(c):
            cs = slice(lo, lo + w)
            d = _dot(g, wt_ref[j, :, cs], widen) + \
                s * x_ref[j, :, cs].astype(F32)
            dx_ref[j, :, cs] = d.astype(dx_ref.dtype)
        dw_ref[j] += _dot(gt, x_ref[j], widen)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _maps_fwd_call(x, w, *, interpret):
    """(T, 128) float32: ``sum_j x[j] @ w[j]`` and, in lane ``ss_lane``, the
    sum of a token's squares. ``w`` (n, C, 128) bf16, zero from ``ss_lane``."""
    n, tokens, c = x.shape
    tt = token_tile("maps_fwd", n, tokens, c)
    return pl.pallas_call(
        functools.partial(_maps_fwd_kernel, ss_lane=LANES - 1,
                          widen=interpret),
        grid=(tokens // tt,),
        in_specs=[_streams(n, tt, c),
                  pl.BlockSpec((n, c, LANES), lambda t: (0, 0, 0))],
        out_specs=_tokens(tt, LANES),
        out_shape=jax.ShapeDtypeStruct((tokens, LANES), F32),
        compiler_params=_params("parallel"), interpret=interpret,
        name="hc_maps_fwd")(x, w)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _maps_bwd_call(x, g, gt, s, wt, *, interpret):
    """``dx[j] = g @ wt[j] + s x[j]`` (n, T, C) and ``gt @ x[j]`` (n, R, C)
    float32, summed over the token tiles. ``g`` (T, KP) and ``gt`` (R, T)
    bf16, ``s`` (T, 1) float32, ``wt`` (n, KP, C) bf16."""
    n, tokens, c = x.shape
    tt = token_tile("maps_bwd", n, tokens, c)
    kp, r = g.shape[1], gt.shape[0]
    return pl.pallas_call(
        functools.partial(_maps_bwd_kernel, widen=interpret),
        grid=(tokens // tt,),
        in_specs=[_streams(n, tt, c), _tokens(tt, kp),
                  pl.BlockSpec((r, tt), lambda t: (0, t)), _tokens(tt, 1),
                  pl.BlockSpec((n, kp, c), lambda t: (0, 0, 0))],
        out_specs=[_streams(n, tt, c),
                   pl.BlockSpec((n, r, c), lambda t: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, r, c), F32)],
        compiler_params=_params("arbitrary"), interpret=interpret,
        name="hc_maps_bwd")(x, g, gt, s, wt)


def _phi_limbs(phi, n, c):
    """``phi`` (n C, k) as its bf16 limbs, each (n, C, k): one if it is bf16
    already, else three."""
    w = phi.reshape(n, c, -1)
    return [w] if w.dtype == BF16 else _limbs(w, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def hc_norm_proj(x, phi, interpret=False):
    """``(ss, dyn)`` of the streams ``x`` (n, T, C) bf16: ``ss`` (T,) the sum
    of a token's n C squares, ``dyn`` (T, k) ``= sum_j x[j] @ phi[j]`` with
    ``phi`` (n C, k) rows stream by stream; both float32."""
    return _norm_proj_fwd(x, phi, interpret)[0]


def _norm_proj_fwd(x, phi, interpret):
    n, _, c = x.shape
    k = phi.shape[1]
    limbs = _phi_limbs(phi, n, c)
    w = _pad_last(jnp.concatenate(limbs, axis=-1), LANES)
    out = _maps_fwd_call(x, w, interpret=interpret)
    dyn = sum(out[:, a * k:(a + 1) * k] for a in range(len(limbs)))
    return (out[:, LANES - 1], dyn), (x, phi)


def _norm_proj_bwd(interpret, saved, cot):
    x, phi = saved
    d_ss, d_dyn = cot
    n, tokens, c = x.shape
    k = phi.shape[1]
    g, w = _limbs(d_dyn, 3), _phi_limbs(phi, n, c)
    # every pair of limbs whose product is over 2^-24 of the whole
    pairs = [(a, b) for a in range(3) for b in range(len(w)) if a + b < 3]
    kp = _round_up(len(pairs) * k, LANES)
    rows = _pad_last(jnp.concatenate([g[a] for a, _ in pairs], axis=1), kp)
    wt = _pad_last(jnp.concatenate([w[b] for _, b in pairs], axis=-1), kp)
    gt = _pad_last(jnp.concatenate(g, axis=1), _round_up(3 * k, BAND)).T
    dx, dw = _maps_bwd_call(
        x, rows, gt, (2.0 * d_ss.astype(F32))[:, None],
        jnp.swapaxes(wt, 1, 2), interpret=interpret)
    dw = sum(dw[:, a * k:(a + 1) * k] for a in range(3))          # (n, k, C)
    return dx, jnp.swapaxes(dw, 1, 2).reshape(phi.shape).astype(phi.dtype)


hc_norm_proj.defvjp(_norm_proj_fwd, _norm_proj_bwd)
