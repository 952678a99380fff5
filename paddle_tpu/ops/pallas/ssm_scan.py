"""The chunked state-space recurrence as two pallas TPU kernels, forward and
backward by hand (``nn.functional.state_space``'s ``ssm_chunk``; Mamba-2,
arXiv:2405.21060).

A head h of width P over a state of N columns, ``G_i`` the log-decay from its
chunk's start through token i (``A cumsum(Delta)``, made by the caller in XLA:
it and everything else of size (B, L, H) is a 2 MB array), a chunk of Q tokens
that starts at state S:

    y_i = sum_{j <= i} exp(G_i - G_j) (C_i . B_j) Delta_j x_j
          + exp(G_i) S C_i + D x_i
    S'  = exp(G_Q) S + sum_j exp(G_Q - G_j) Delta_j x_j B_j^T

The jnp form is one ``lax.scan`` over the chunks: it makes a chunk's (H, Q, Q)
decays in HBM on every evaluation, carries S through HBM, writes its outputs
into a stacked array by copying it, and autodiff does that again with
residuals. Here a grid step is one chunk of one row with every head: the
chunk's ``x`` (Q, H P) arrives once in its own dtype, the carried state of all
heads is a (N, H P) float32 VMEM scratch over the sequential chunk axis, a
head's (Q, Q) decay lives in VMEM only, and ``y`` leaves through its
``BlockSpec``. The heads are walked a 128-lane slab of ``H P`` at a time
(``128 // P`` heads); ``B`` and ``C`` are shared by all heads, so ``C B^T`` is
made once a chunk and the state's two products are one (Q, N) x (N, 128)
product a slab.

- ``ssm_scan_fwd`` also writes the state at each chunk's start, (B, L / Q, N,
  H P) float32: the only residual beside the operands.
- ``ssm_scan_bwd`` walks the chunks in reverse carrying ``dS`` in the same
  scratch, reads the chunk's start state, makes the decays and ``B C^T`` again
  (transposed: rows j, lanes i, so that ``W^T dy`` is a plain product) and
  writes ``dx``, ``dDelta``, ``dB``, ``dC`` once; ``dB`` and ``dC`` sum over
  the heads inside the grid step. ``dG`` leaves in two parts the caller
  adds: a head's sums over j of ``dW . W`` as rows of a (H, L) array, and a
  (L, H) array that takes its sums over i off again (both sums from the one
  array: they all but cancel in ``dA_log``, a sum weighted by G), adds the
  state's terms ``R(exp(G) (C S) . dy)``, takes ``exp(G_Q - G_j)``'s terms
  off ``G_j`` and gives them, with ``exp(G_Q) <S, dS'>``, to the chunk's
  last row, which is ``G_Q``: products with decays <= 1 only, no division.

**Arithmetic.** Every exponential is of a difference ``G_i - G_j <= 0`` inside
one chunk, masked with ``-inf`` above the diagonal. Every product is float32
operands with float32 accumulation at ``Precision.HIGHEST``'s accuracy: a
float32 operand goes to the MXU as its three bfloat16 limbs and the passes
are the six HIGHEST makes (``_mm``); an operand that is bfloat16 already
(``x``, ``B``, ``C``, ``dy`` of a bfloat16 model) is one limb, exactly, and
its products need three passes or one. ``Delta_j`` is folded into the decay
matrix so that the per-head product has ``x`` itself on one side. ``y`` is
rounded once, after the skip ``D x``.

Which calls the kernels take is :func:`ssm_scan_route`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import hyper_connection as hc

LANES = 128
VMEM_LIMIT = 96 * 2 ** 20      # of v5e's 128 MiB; the default scope is 16
VMEM_BUDGET = 56 * 2 ** 20     # a call's blocks, double-buffered, and scratch
F32 = jnp.float32
BF16 = jnp.bfloat16
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


# ---- the rule ----------------------------------------------------------------
def _block_bytes(q, hp, n, itemsize):
    """VMEM of the backward call (the larger): ``x``, ``dy`` and ``dx`` blocks
    and the saved state double-buffered, ``dS``, and a dozen (Q, Q) and
    (Q, 128) float32 values in flight."""
    return (6 * q * hp * itemsize + 3 * n * hp * 4 + 12 * q * q * 4 +
            16 * q * LANES * 4)


def ssm_scan_route(x_shape, x_dtype, n_state, chunk):
    """``(in_specs, out_specs)`` for ``ops.pallas.run`` where the kernels take
    the recurrence over heads ``x`` (B, L, H, P) with a state of ``n_state``
    columns in chunks of ``chunk``, else ``None`` (the caller's ``lax.scan``):
    a TPU backend, bfloat16 or float32 heads, a chunk and a state of whole
    128-lane columns, heads that fill 128-lane slabs (``P`` divides 128 and
    ``H P`` is a multiple of it), a chunk whose blocks fit the VMEM budget,
    and one device's rows: under a mesh to wrap over the call stays dense."""
    from . import _kernel_mesh, enabled

    if not (enabled() and len(x_shape) == 4 and x_dtype in (BF16, F32) and
            chunk > 0 and chunk % LANES == 0 and n_state > 0 and
            n_state % LANES == 0):
        return None
    h, p = x_shape[2:]
    if LANES % p or (h * p) % LANES or _block_bytes(
            chunk, h * p, n_state, jnp.dtype(x_dtype).itemsize) > VMEM_BUDGET:
        return None
    return ((), None) if _kernel_mesh() is None else None


# ---- pieces ------------------------------------------------------------------
def _limbs(a):
    """``a`` as bfloat16 limbs that sum to it: itself if it is bfloat16, else
    the three of a float32 (8 + 8 + 8 bits of its mantissa)."""
    return [a] if a.dtype == BF16 else hc._limbs(a, 3)


def _mm(a, b, dims, widen):
    """``dot_general(a, b, dims)`` in float32 at ``Precision.HIGHEST``'s
    accuracy: the passes of limbs whose product is above 2^-24 of the result
    (six for two float32 operands, three where one is bfloat16, one where
    both are), each bf16 x bf16 exact into a float32 sum. ``widen``: the
    interpreter's case, where the host's dot takes float32 operands."""
    if widen:
        return jax.lax.dot_general(a.astype(F32), b.astype(F32), dims,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=F32)
    out = None
    for i, la in enumerate(_limbs(a)):
        for j, lb in enumerate(_limbs(b)):
            if i + j <= 2:
                term = jax.lax.dot_general(
                    la, lb, dims, preferred_element_type=F32,
                    precision=jax.lax.Precision.DEFAULT)
                out = term if out is None else out + term
    return out


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _slab(k):
    return pl.ds(pl.multiple_of(k * LANES, LANES), LANES)


def _of_head(value, t, p):
    """``value`` (rows, 128) with the lanes of the slab's other heads zero."""
    if p == LANES:
        return value
    lane = jax.lax.broadcasted_iota(jnp.int32, value.shape, 1)
    return jnp.where((lane >= t * p) & (lane < (t + 1) * p), value,
                     jnp.zeros_like(value))


# ---- forward -----------------------------------------------------------------
def _fwd_kernel(x_ref, dt_ref, g_ref, gt_ref, dtt_ref, b_ref, c_ref, d_ref,
                e_ref, y_ref, s_ref, state, *, p, widen):
    # x, y: (1, Q, HP); dt, g: (1, Q, H); gt, dtt: (1, H, Q); b, c: (1, Q, N)
    # d: (1, HP) float32, a head's D over its P lanes; e: (H, HP) 0/1,
    # e[h] one over head h's lanes; s: (1, 1, N, HP); state: (N, HP)
    q, hp = x_ref.shape[1:]
    mm = functools.partial(_mm, widen=widen)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    b, c = b_ref[0], c_ref[0]
    g, dt = g_ref[0], dt_ref[0]
    cb = mm(c, b, _NT)                                    # [i, j] = C_i . B_j
    keep = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)    # j <= i

    def slab(k, carry):
        sl = _slab(k)
        e = e_ref[:, sl]
        gx, dtx = mm(g, e, _NN), mm(dt, e, _NN)           # (Q, 128), exact
        xs = x_ref[0, :, sl]
        xf = xs.astype(F32)
        yw = jnp.zeros((q, LANES), F32)
        for t in range(LANES // p):
            head = pl.ds(k * (LANES // p) + t, 1)
            diff = gx[:, t * p:t * p + 1] - gt_ref[0, head, :]    # G_i - G_j
            w = cb * jnp.exp(jnp.where(keep, diff, -jnp.inf)) * \
                dtt_ref[0, head, :]
            yw = yw + mm(w, _of_head(xs, t, p), _NN)
        sk = state[:, sl]
        s_ref[0, 0, :, sl] = sk
        y = yw + jnp.exp(gx) * mm(c, sk, _NN) + d_ref[:, sl] * xf
        y_ref[0, :, sl] = y.astype(y_ref.dtype)
        gq = gx[q - 1:q, :]                               # G_Q
        u = (dtx * jnp.exp(gq - gx)) * xf
        state[:, sl] = jnp.exp(gq) * sk + mm(b, u, _TN)
        return carry

    jax.lax.fori_loop(0, hp // LANES, slab, 0)


def _expand(h, p):
    head = jax.lax.broadcasted_iota(jnp.int32, (h, h * p), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (h, h * p), 1)
    return (lane // p == head).astype(BF16)


def _specs(q, h, hp, n, at):
    """The block specs of one chunk's operands, the chunk of grid step ``(r,
    s)`` being ``at(s)``."""
    def rows(width):
        return pl.BlockSpec((1, q, width), lambda r, s: (r, at(s), 0))

    return dict(
        wide=rows(hp), head=rows(h), bc=rows(n),
        head_t=pl.BlockSpec((1, h, q), lambda r, s: (r, 0, at(s))),
        d=pl.BlockSpec((1, hp), lambda r, s: (0, 0)),
        e=pl.BlockSpec((h, hp), lambda r, s: (0, 0)),
        state=pl.BlockSpec((1, 1, n, hp), lambda r, s: (r, at(s), 0, 0)))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _fwd_call(x, dt, g, b, c, d, *, chunk, interpret):
    rows, length, h, p = x.shape
    hp, n, steps = h * p, b.shape[-1], length // chunk
    sp = _specs(chunk, h, hp, n, lambda s: s)
    y, states = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, widen=interpret),
        grid=(rows, steps),
        in_specs=[sp["wide"], sp["head"], sp["head"], sp["head_t"],
                  sp["head_t"], sp["bc"], sp["bc"], sp["d"], sp["e"]],
        out_specs=[sp["wide"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct((rows, length, hp), x.dtype),
                   jax.ShapeDtypeStruct((rows, steps, n, hp), F32)],
        scratch_shapes=[pltpu.VMEM((n, hp), F32)],
        compiler_params=_params(), interpret=interpret,
        name="ssm_scan_fwd")(
            x.reshape(rows, length, hp), dt, g, jnp.swapaxes(g, 1, 2),
            jnp.swapaxes(dt, 1, 2), b, c,
            jnp.repeat(d.astype(F32), p)[None], _expand(h, p))
    return y.reshape(x.shape), states


# ---- backward ----------------------------------------------------------------
def _bwd_kernel(x_ref, dy_ref, dt_ref, g_ref, gt_ref, b_ref, c_ref, d_ref,
                e_ref, s_ref, dx_ref, ddt_ref, dgs_ref, dgt_ref, db_ref,
                dc_ref, dd_ref, dstate, dcbt, dbacc, dcacc, *, p, widen):
    # as the forward's, and dy, dx: (1, Q, HP); ddt, dgs: (1, Q, H) float32;
    # dgt: (1, H, Q) float32; db, dc: (1, Q, N); dd: (1, 1, HP) float32,
    # summed over a row's chunks; dstate: (N, HP); dcbt: (Q, Q); dbacc,
    # dcacc: (Q, N) float32
    q, hp = x_ref.shape[1:]
    mm = functools.partial(_mm, widen=widen)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    b, c = b_ref[0], c_ref[0]
    g, dt = g_ref[0], dt_ref[0]
    cbt = mm(b, c, _NT)                                   # [j, i] = B_j . C_i
    keep = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) <= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)    # j <= i
    last = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 0) == q - 1
    heads = jax.lax.broadcasted_iota(jnp.int32, (q, g.shape[1]), 1)
    for ref in (ddt_ref, dgs_ref, dcbt, dbacc, dcacc):
        ref[...] = jnp.zeros_like(ref)

    def slab(k, carry):
        sl = _slab(k)
        e = e_ref[:, sl]
        gx, dtx = mm(g, e, _NN), mm(dt, e, _NN)
        xs, dys = x_ref[0, :, sl], dy_ref[0, :, sl]
        xf, dyf = xs.astype(F32), dys.astype(F32)
        sk, dsk = s_ref[0, 0, :, sl], dstate[:, sl]
        gq = gx[q - 1:q, :]
        ex, tx, eq = jnp.exp(gx), jnp.exp(gq - gx), jnp.exp(gq)
        v = tx * mm(b, dsk, _NN)                          # T_j (B_j dS')
        w = jnp.zeros((q, LANES), F32)
        for t in range(LANES // p):
            head = pl.ds(k * (LANES // p) + t, 1)
            diff = gt_ref[0, head, :] - gx[:, t * p:t * p + 1]    # G_i - G_j
            lt = jnp.exp(jnp.where(keep, diff, -jnp.inf))
            w = w + mm(cbt * lt, _of_head(dys, t, p), _NN)        # W^T dy
            # d(C B^T)[j, i] = decay Delta_j (x_j . dy_i); with C B^T its
            # column sums are the row sums of dW . W
            kt = lt * mm(_of_head(xs, t, p), dys, _NT) * \
                dtx[:, t * p:t * p + 1]
            dcbt[...] += kt
            # dW . W, [j, i]: its sums over j go to G_i, those over i come
            # off G_j, both from the one array (they all but cancel in
            # dA_log, a sum weighted by G)
            zt = kt * cbt
            dgt_ref[0, head, :] = jnp.sum(zt, axis=0, keepdims=True)
            dgs_ref[0] -= jnp.where(
                heads == k * (LANES // p) + t,
                jnp.sum(zt, axis=1, keepdims=True), 0.0)
        du = w + v
        dx_ref[0, :, sl] = (dtx * du + d_ref[:, sl] * dyf).astype(
            dx_ref.dtype)
        ddt_ref[0] += mm(du * xf, e, _NT)
        # exp(G_Q - G_j)'s terms come off G_j and go to G_Q, which also
        # takes exp(G_Q) <S, dS'>
        ends = dtx * v * xf
        dgq = jnp.sum(ends, axis=0, keepdims=True) + \
            eq * jnp.sum(sk * dsk, axis=0, keepdims=True)
        dgs_ref[0] += mm(ex * mm(c, sk, _NN) * dyf - ends +
                         jnp.where(last, dgq, 0.0), e, _NT)
        dd_ref[0, :, sl] += jnp.sum(dyf * xf, axis=0, keepdims=True)
        edy = ex * dyf
        dstate[:, sl] = eq * dsk + mm(c, edy, _TN)
        dcacc[...] += mm(edy, sk, _NT)
        dbacc[...] += mm(dtx * tx * xf, dsk, _NT)
        return carry

    jax.lax.fori_loop(0, hp // LANES, slab, 0)
    db_ref[0] = (dbacc[...] + mm(dcbt[...], c, _NN)).astype(db_ref.dtype)
    dc_ref[0] = (dcacc[...] + mm(dcbt[...], b, _TN)).astype(dc_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _bwd_call(x, dy, dt, g, b, c, d, states, *, chunk, interpret):
    rows, length, h, p = x.shape
    hp, n, steps = h * p, b.shape[-1], length // chunk
    sp = _specs(chunk, h, hp, n, lambda s: steps - 1 - s)
    dx, ddt, dgs, dgt, db, dc, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, widen=interpret),
        grid=(rows, steps),
        in_specs=[sp["wide"], sp["wide"], sp["head"], sp["head"],
                  sp["head_t"], sp["bc"], sp["bc"], sp["d"], sp["e"],
                  sp["state"]],
        out_specs=[sp["wide"], sp["head"], sp["head"], sp["head_t"],
                   sp["bc"], sp["bc"],
                   pl.BlockSpec((1, 1, hp), lambda r, s: (r, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, length, hp), x.dtype),
                   jax.ShapeDtypeStruct((rows, length, h), F32),
                   jax.ShapeDtypeStruct((rows, length, h), F32),
                   jax.ShapeDtypeStruct((rows, h, length), F32),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct((rows, 1, hp), F32)],
        scratch_shapes=[pltpu.VMEM((n, hp), F32), pltpu.VMEM((chunk, chunk),
                                                             F32),
                        pltpu.VMEM((chunk, n), F32),
                        pltpu.VMEM((chunk, n), F32)],
        compiler_params=_params(), interpret=interpret,
        name="ssm_scan_bwd")(
            x.reshape(rows, length, hp), dy.reshape(rows, length, hp), dt, g,
            jnp.swapaxes(g, 1, 2), b, c, jnp.repeat(d.astype(F32), p)[None],
            _expand(h, p), states)
    return (dx.reshape(x.shape), ddt, dgs + jnp.swapaxes(dgt, 1, 2), db, dc,
            jnp.sum(dd.reshape(rows, h, p), axis=(0, 2)))


# ---- the op ------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssm_scan(x, dt, g, b, c, d, chunk, interpret=False):
    """``y`` (B, L, H, P) in ``x``'s dtype: the recurrence over rows ``x``
    whose length ``chunk`` divides, each row from ``S = 0``; ``dt`` and ``g``
    (B, L, H) float32, the step size and the log-decay from a token's chunk's
    start through it; ``b``, ``c`` (B, L, N); ``d`` (H,)."""
    return _fwd_call(x, dt, g, b, c, d, chunk=chunk, interpret=interpret)[0]


def _ssm_scan_fwd(x, dt, g, b, c, d, chunk, interpret):
    y, states = _fwd_call(x, dt, g, b, c, d, chunk=chunk, interpret=interpret)
    return y, (x, dt, g, b, c, d, states)


def _ssm_scan_bwd(chunk, interpret, saved, dy):
    x, dt, g, b, c, d, states = saved
    dx, ddt, dg, db, dc, dd = _bwd_call(x, dy, dt, g, b, c, d, states,
                                        chunk=chunk, interpret=interpret)
    return dx, ddt.astype(dt.dtype), dg.astype(g.dtype), db, dc, \
        dd.astype(d.dtype)


ssm_scan.defvjp(_ssm_scan_fwd, _ssm_scan_bwd)
