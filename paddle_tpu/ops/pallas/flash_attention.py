"""Flash attention: blocked online-softmax attention as a pallas TPU kernel.

TPU-native replacement for the reference's dense-score attention graphs
(the reference has no fused attention kernel — its transformers build
softmax(QK^T)V from primitive CUDA ops; this kernel is the TPU design
point the hand-fused CUDA kernels in paddle/fluid/operators aspire to).

Design:
- O(L) memory: scores never materialize. Three kernels (forward; backward
  dQ over q-blocks, which also emits ``delta = rowsum(dO * O)``; backward
  dK/dV over k-blocks) share one tiling: the grid is (heads, resident
  block, streamed block), the streamed K/V (or Q/dO) blocks arrive through
  the innermost grid dimension so their DMA overlaps the previous block's
  compute, and the accumulators live in VMEM scratch that is initialised
  on the first streamed block and written out on the last. VMEM use does
  not grow with L.
- Inside a grid step the resident block is swept in bands of ``sub`` rows
  (a static loop): one matmul and one softmax update a band, so the f32
  score-sized temporaries are ``sub`` rows whatever the block.
- MXU operands keep the input's dtype (bf16 in, bf16 matmuls with f32
  accumulation; float32 in, float32 matmuls); ``p`` and ``ds`` are cast to
  it for their matmuls as the dense path does. Softmax statistics, ``exp``
  and every accumulator are float32. The scale is folded into the resident
  operand when it is a power of two (exact), else applied to the f32 scores.
- No in-kernel transposes of score blocks: the forward and dQ kernels hold
  scores as (q, k) with row statistics replicated across the 128 lanes;
  the dK/dV kernel holds them as (k, q), so ``p^T`` and ``ds^T`` are what it
  computes and ``lse``/``delta`` are lane-dense rows. In HBM both are
  compact ``[BH, 1, L]`` float32.
- Causal: a block wholly above the diagonal is neither computed nor
  fetched (its index map is clamped to the last block needed), a block
  wholly under it takes the unmasked body, and only a block the diagonal
  crosses builds a mask. Where the blocks are square and aligned (Lq == Lk
  always is) a band of such a block stops at the diagonal, so how much of
  the score matrix is visited is set by ``sub`` and costs no grid steps.
- A padding mask enters as a key bias: float32 ``[B or 1, 1, Lk]``, the same
  for every head and query of a batch row, an operand after q, k, v blocked
  along the keys with its row taken from the grid's ``BH`` index. It is added
  to the float32 scores where the dense path adds its mask (after the scale,
  before the causal ``where`` and the running maximum): a lane-dense row in
  the forward and dQ kernels, a column made once a resident block in the
  dK/dV kernel. Same arithmetic, so a row whose keys are all masked is the
  mean of the values on both paths; its ``lse = m + log(l)`` is ``m`` in
  float32, so the forward also returns ``fix``, the factor by which
  ``exp(s - lse)`` overstates a row's probabilities, and the backward scales
  dO by it (both backward kernels are linear in a row's p): the gradients are
  the dense path's too. No block is skipped for padding. Without a bias the
  kernels compile without the operand and without ``fix`` (a static case).
- A sliding window (:func:`window_attention`: causal, a query sees its last
  ``window`` keys) runs the same three bodies on grids whose streamed
  dimension holds only the blocks that meet the band, ``ceil((window - 1) /
  block) + 1`` of them a resident block, oldest first for resident queries
  and nearest first for resident keys; the blocks are square, so how far a
  step's streamed block lies from the resident one is static, and each band
  of ``sub`` resident rows takes just the streamed rows it can see and
  masks only the edges that cross it (``_band_sweep``). The calls are named
  ``swa_<call>_w<window>``: not ``flash_*_causal``, whose readers count half
  of ``L^2``.
- Block sizes come from the shapes by one rule, :func:`block_sizes`; which
  calls the kernels take by one more, :func:`flash_route` (a grid step must
  hold enough scores to pay for itself: ``MIN_STEP_SCORES``).
- Inside a recomputed region (``framework.recompute``) the region keeps the
  forward call's outputs: the forward rules name ``o``, ``lse`` and ``fix``
  ``RECOMPUTE_KEEP`` (:func:`_kept`), and the region's backward pass runs
  the two backward kernels alone, on q, k, v made again. Outside a region
  the name does nothing.
- ``interpret=True`` runs the same kernels on CPU for tests.

Layout: (B, H, L, D) — collapsed to (BH, L, D) for the grid. Queries and keys
share one head width ``D``; values, the output and their gradients may have
another, ``Dv`` (the kernels' first three operands stay q, k, v).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
_NT = (((1,), (1,)), ((), ()))    # a @ b.T, contracted without a transpose
VMEM_BUDGET = 12 * 2 ** 20        # of the 16 MiB a kernel gets by default


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """An MXU matmul in the operands' dtype, accumulated in float32. Operands
    narrower than float32 have one pass to make, whatever
    ``jax_default_matmul_precision`` asks of float32 ones (Mosaic refuses a
    multi-pass precision on bf16 operands)."""
    narrow = a.dtype.itemsize < 4
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT if narrow else None)


def _lanes(x, n):
    """A lane-replicated (rows, 128) statistic widened to n columns."""
    reps = -(-n // LANES)
    return x if n == LANES else jnp.tile(x, (1, reps))[:, :n]


def _col_to_row(x):
    """(rows, 128) lane-replicated -> the same values as one (1, rows) row."""
    return x.T[:1]


def _row_to_col(x):
    """(1, rows) -> (rows, 128) lane-replicated."""
    return jnp.broadcast_to(x, (LANES, x.shape[1])).T


def _scores(resident, streamed, scale, q_axis, thresh, bias=None, top=None):
    """The f32 score block ``resident @ streamed.T`` (times ``scale`` unless
    it was folded into an operand: None), plus the keys' ``bias`` where the
    call has one (a row or a column that broadcasts over the queries: added
    where the dense path adds its mask, to the scaled float32 scores), with
    what a causal query cannot see at NEG_INF. Visible is q position >= k
    position, which in the block's own indices (q along ``q_axis``) reads
    ``q - k >= thresh``: the k rows' start minus the q rows' aligned start.
    ``thresh`` None means no causal mask. Under a window a query also sees
    no key further back than the window reaches: ``q - k <= top`` in the same
    indices; ``top`` None means the block lies wholly inside that edge."""
    s = _dot(resident, streamed, _NT)
    if scale is not None:
        s = s * scale
    if bias is not None:
        s = s + bias
    if thresh is None and top is None:
        return s
    q = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    if top is None:
        return jnp.where(q - k >= thresh, s, NEG_INF)
    seen = q - k <= top
    if thresh is not None:
        seen = jnp.logical_and(q - k >= thresh, seen)
    return jnp.where(seen, s, NEG_INF)


def _band_sweep(update, window, block, sub, at, step, steps, blocks,
                q_resident):
    """A windowed call's grid step ``step`` of ``steps`` on the resident block
    ``at`` (of ``blocks`` square blocks of ``block`` rows; queries and keys
    have one length). The steps walk the blocks that meet the band ``0 <= q -
    k < window``: under resident queries the key blocks from ``steps - 1``
    back up to the queries' own, under resident keys the query blocks from the
    keys' own on. How far the streamed block lies from the resident one is
    static a step, so each band of ``sub`` resident rows takes just the
    streamed rows it can see (in whole lane tiles) and builds only the edges
    of the mask that cross it. A step that falls off either end of the
    sequence does nothing (its index map names the nearest block)."""
    tile = LANES if block % LANES == 0 else sub

    def block_at(away):
        gap = away * block      # first query row's position - first key row's
        for r0 in range(0, block, sub):
            if q_resident:      # q - k = gap + r - c
                lo, hi = gap + r0 - window + 1, gap + r0 + sub - 1
            else:               # q - k = gap + c - r
                lo, hi = r0 - gap, r0 + sub - 1 - gap + window - 1
            c0 = max(0, lo // tile * tile)
            c1 = min(block, (hi // tile + 1) * tile)
            if c0 >= c1:
                continue
            if q_resident:
                least, most = gap + r0 - (c1 - 1), gap + r0 + sub - 1 - c0
                thresh = c0 - r0 - gap
            else:
                least, most = gap + c0 - (r0 + sub - 1), gap + c1 - 1 - r0
                thresh = r0 - c0 - gap
            update(r0, c0, c1, thresh if least < 0 else None,
                   thresh + window - 1 if most >= window else None)

    for j in range(steps):
        away = steps - 1 - j if q_resident else j
        inside = at - away >= 0 if q_resident else at + away <= blocks - 1
        pl.when(jnp.logical_and(step == j, inside))(
            functools.partial(block_at, away))


def _sweep(update, causal, aligned, qi, ki, bq, bk, sub, offset, q_resident,
           window=None, steps=None, blocks=None):
    """One grid step's work on the score block (qi, ki), a band of ``sub``
    rows of the resident operand at a time: ``update(r0, c0, c1, thresh)``
    takes resident rows [r0, r0 + sub) against streamed rows [c0, c1), with
    ``thresh`` for :func:`_scores` (None where no mask is needed).

    Not causal, or a block wholly under the diagonal: every band against
    the whole streamed block, unmasked. A block the diagonal crosses: each
    band masked; where the blocks are square and aligned the diagonal runs
    corner to corner, so a band also leaves out the streamed rows it cannot
    see (statically: no grid step is spent on them). A block wholly above
    the diagonal: nothing. A windowed call (``window``: its grid has
    ``steps`` streamed steps a resident block, of ``blocks``) is
    :func:`_band_sweep`'s."""
    if window is not None:      # the streamed index is the band's step
        at, step = (qi, ki) if q_resident else (ki, qi)
        return _band_sweep(update, window, bq, sub, at, step, steps, blocks,
                           q_resident)
    n_res, n_str = (bq, bk) if q_resident else (bk, bq)
    bands = range(0, n_res, sub)

    def full():
        for r0 in bands:
            update(r0, 0, n_str, None)

    if not causal:
        full()
        return
    q0 = qi * bq + offset       # k position the block's first q row sees up to
    k0 = ki * bk
    needed = k0 <= q0 + (bq - 1)
    is_full = k0 + (bk - 1) <= q0

    def crossed():
        for r0 in bands:
            if aligned and q_resident:      # here k0 == q0
                update(r0, 0, r0 + sub, -r0)
            elif aligned:
                update(r0, r0, n_str, 0)
            else:
                update(r0, 0, n_str, k0 - q0 + (-r0 if q_resident else r0))

    pl.when(is_full)(full)
    pl.when(jnp.logical_and(needed, jnp.logical_not(is_full)))(crossed)


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, fold, sub, biased,
                **where):
    if biased:
        bias_ref, o_ref, lse_ref, fix_ref, qs_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, lse_ref, qs_ref, m_ref, l_ref, acc_ref = refs
    ki, nk = pl.program_id(2), pl.num_programs(2)
    Dv = v_ref.shape[2]
    post = None if fold else scale      # what the scores still need

    @pl.when(ki == 0)
    def _init():
        qs_ref[:] = q_ref[0] * scale if fold else q_ref[0]
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def update(r0, c0, c1, thresh, top=None):
        rows = slice(r0, r0 + sub)
        v = v_ref[0, c0:c1, :]
        s = _scores(qs_ref[rows, :], k_ref[0, c0:c1, :], post, 0, thresh,
                    bias_ref[0, :, c0:c1] if biased else None, top)
        m_prev = m_ref[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, c1 - c0))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[rows, :] = alpha * l_ref[rows, :] + \
            jnp.sum(p, axis=1, keepdims=True)
        m_ref[rows, :] = m_new
        acc_ref[rows, :] = acc_ref[rows, :] * _lanes(alpha, Dv) + \
            _dot(p.astype(v.dtype), v)

    _sweep(update, qi=pl.program_id(1), ki=ki, sub=sub, q_resident=True,
           **where)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] * _lanes(1.0 / l, Dv)).astype(o_ref.dtype)
        lse = m_ref[:] + jnp.log(l)
        lse_ref[0] = _col_to_row(lse)
        if biased:
            # what the float32 sum above lost of log(l): all of it in a row
            # whose every key is masked (m = -1e30), rounding elsewhere
            fix_ref[0] = _col_to_row(jnp.exp((lse - m_ref[:]) - jnp.log(l)))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, *refs, scale, fold, sub, biased,
                   **where):
    bias_ref, refs = (refs[0], refs[1:]) if biased else (None, refs)
    (do_ref, o_ref, lse_ref, dq_ref, delta_ref, qs_ref, lse_c, delta_c,
     acc_ref) = refs
    ki, nk = pl.program_id(2), pl.num_programs(2)
    post = None if fold else scale

    @pl.when(ki == 0)
    def _init():
        qs_ref[:] = q_ref[0] * scale if fold else q_ref[0]
        lse_c[:] = _row_to_col(lse_ref[0])
        # delta_i = rowsum(dO * O) — the softmax-jacobian diagonal term
        delta = jnp.sum(do_ref[0].astype(jnp.float32) *
                        o_ref[0].astype(jnp.float32), axis=1, keepdims=True)
        delta_c[:] = jnp.broadcast_to(delta, delta_c.shape)
        delta_ref[0] = _col_to_row(delta_c[:])
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def update(r0, c0, c1, thresh, top=None):
        rows = slice(r0, r0 + sub)
        k = k_ref[0, c0:c1, :]
        s = _scores(qs_ref[rows, :], k, post, 0, thresh,      # (sub, c1 - c0)
                    bias_ref[0, :, c0:c1] if biased else None, top)
        p = jnp.exp(s - _lanes(lse_c[rows, :], c1 - c0))
        dp = _dot(do_ref[0, rows, :], v_ref[0, c0:c1, :], _NT)
        ds = p * (dp - _lanes(delta_c[rows, :], c1 - c0))
        acc_ref[rows, :] += _dot(ds.astype(k.dtype), k)

    _sweep(update, qi=pl.program_id(1), ki=ki, sub=sub, q_resident=True,
           **where)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, *refs, scale, fold, sub, biased,
                    **where):
    bias_ref, refs = (refs[0], refs[1:]) if biased else (None, refs)
    (do_ref, lse_ref, delta_ref, dk_ref, dv_ref, ks_ref, dk_acc, dv_acc,
     *bias_c) = refs        # the last a scratch the biased call alone has
    qi, nq = pl.program_id(2), pl.num_programs(2)
    post = None if fold else scale

    @pl.when(qi == 0)
    def _init():
        ks_ref[:] = k_ref[0] * scale if fold else k_ref[0]
        if biased:      # scores are (k, q) here: the keys' row as a column
            bias_c[0][:] = _row_to_col(bias_ref[0])
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def update(r0, c0, c1, thresh, top=None):
        rows = slice(r0, r0 + sub)
        q, do = q_ref[0, c0:c1, :], do_ref[0, c0:c1, :]
        s = _scores(ks_ref[rows, :], q, post, 1, thresh,      # (sub, c1 - c0)
                    _lanes(bias_c[0][rows, :], c1 - c0) if biased else None,
                    top)
        p = jnp.exp(s - lse_ref[0, :, c0:c1])               # lse: a row
        dv_acc[rows, :] += _dot(p.astype(do.dtype), do)
        dp = _dot(v_ref[0, rows, :], do, _NT)
        ds = p * (dp - delta_ref[0, :, c0:c1])
        dk_acc[rows, :] += _dot(ds.astype(q.dtype), q)

    _sweep(update, qi=qi, ki=pl.program_id(1), sub=sub, q_resident=False,
           **where)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _kernel_name(base, causal, window=None):
    """The name the call carries into the HLO and the device trace. The
    ``_causal`` suffix tells a reader of the trace that the kernel skips the
    blocks beyond the diagonal (about half the work). A windowed call is
    ``swa_*_w<window>``: it visits the band alone, so a reader that counts a
    ``flash_*_causal`` call as half of L^2 must not meet it under that
    prefix, and the window it needs for its own count is in the name."""
    if window is not None:
        return f"swa_{base}_w{window}"
    return "flash_" + base + ("_causal" if causal else "")


def vmem_bytes(bq, bk, sub, D, itemsize, Dv=None):
    """What one grid step of the largest of the three kernels (dK/dV) holds
    in VMEM: its double-buffered operand and result blocks (q and k, dK are
    ``D`` wide; v, dO, dV ``Dv``, which is ``D`` where not given), its
    scratch, and a band's f32 score-sized temporaries. ``L`` is not an
    argument. A key bias adds its row and the column made of it, at most
    0.5 MiB at bk = 1024, which the budget's distance from the 16 MiB a
    kernel gets leaves room for: it is not counted."""
    Dv = D if Dv is None else Dv
    blocks = 2 * itemsize * (D + Dv) * (bq + 2 * bk) + 2 * 2 * 4 * bq
    scratch = bk * (D * itemsize + 4 * (D + Dv))
    temps = sub * max(bq, bk) * (3 * 4 + 2 * itemsize)  # s/p, dp, ds; casts
    return blocks + scratch + temps


# (bq, bk, sub) to aim for; causal or not, the chip's sweep found one best
# (PERF.md, PR 25)
_TARGET = (1024, 1024, 256)
# (block, sub) to aim for under a sliding window: square blocks, so that how
# far a streamed block lies from the resident one is static a grid step; the
# chip's sweep at 72 heads of 8,192 under a window of 512 found this one best
# (PERF.md, PR 42: large blocks for the grid steps, small bands for the edges)
_BAND_TARGET = (1024, 128)
# The fewest scores (bq x bk) a grid step may hold for the kernels to take
# the call: a step costs about 0.35 us whatever it holds, and under this
# the dense path is faster, mask or no mask (the chip's sweep, PERF.md, PR 30)
MIN_STEP_SCORES = 256 * 256


def block_sizes(Lq, Lk, D, itemsize, block_q=None, Dv=None):
    """(bq, bk, sub) for the three kernels, from the shapes alone: the q and
    k blocks of a grid step, and the band of resident rows that one matmul
    and one softmax update take inside it.

    All are multiples of 128 (bq is a lane dimension of the ``lse`` row and
    of the dK/dV kernel's scores, bk of the forward's) that divide their
    length, or the whole length where it is shorter. Large blocks amortise
    what a grid step costs whatever it holds (about 0.35 us) and the
    forward's per-step statistics. Under a causal mask a square block on
    the diagonal is swept band by band, each band stopping at the diagonal,
    so the share of the score matrix visited is set by ``sub`` and not by
    the block (at L=1024: 62.5% with 256-row bands). ``block_q`` is an
    upper bound on bq; shrinking to the VMEM budget starts with ``sub``.
    ``Dv`` is the value head's width where it is not ``D`` (latent attention:
    192 against 128); it only enters the VMEM estimate."""
    want_q, want_k, want_sub = _TARGET
    if block_q is not None:
        want_q = min(want_q, block_q)
    bq, bk = _divisor(Lq, want_q), _divisor(Lk, want_k)

    def band():
        return _divisor(math.gcd(bq, bk), min(want_sub, bq, bk))

    while vmem_bytes(bq, bk, band(), D, itemsize, Dv) > VMEM_BUDGET:
        if want_sub > LANES and band() > LANES:
            want_sub = band() - LANES
        elif bk >= bq and bk > LANES:
            bk = _divisor(Lk, bk - LANES)
        elif bq > LANES:
            bq = _divisor(Lq, bq - LANES)
        else:
            break
    return bq, bk, band()


def flash_route(q_shape, k_shape, v_shape, causal, mask, dropout_p,
                window=None):
    """``(in_specs, out_specs)`` for ``ops.pallas.run`` where these kernels
    take a ``(B, H, L, D)`` call (operands q, k, v and the key bias, which is
    ``None`` for a call without a mask), else ``None`` (the caller's dense
    path): a TPU backend, no dropout (causal is handled inside), both
    lengths multiples of 128, ``Dqk`` of queries and keys and ``Dv`` of
    values (they may differ: latent attention has 192 against 128) each a
    multiple of 64 up to 256, and blocks of at least ``MIN_STEP_SCORES``
    scores a grid step (L=128 stays dense). Not a causal call with
    ``Lk < Lq``: its first queries see no key, and where the sweep skips
    their blocks the rows are not the dense path's.

    ``mask`` is ``None`` or what the call shows of its mask, ``(shape, dtype,
    wants_grad)``. The kernels take it as their key bias when it is one: an
    additive float ``(B or 1, 1, 1, Lk)``, the same for every head and
    query of a batch row (BERT's padding mask), that wants no gradient (the
    kernels return zeros for the bias) on a call that is not causal (the
    causal sweep skips blocks, and no model here pads a causal row). Any
    other mask stays dense: one with a row a query or a head, and a boolean
    one, whose dense ``where`` passes no gradient to a masked score (a row
    with every key masked then differs from the additive form).

    ``window``: a causal call in which a query sees its last ``window`` keys
    (itself among them). One that reaches the whole row (``window >= Lk``) is
    the causal call above. A narrower one goes to :func:`window_attention`'s
    kernels (``swa_*``; the caller picks by ``window < Lk``) where queries
    and keys have one length, there is no mask, and the window's square
    blocks (:func:`band_sizes`) hold ``MIN_STEP_SCORES``; else it stays
    dense, under a band mask.

    Under a mesh the batch splits over the data axis (the bias with it) and
    the heads over the model axis."""
    from . import BATCH, HEADS, enabled, shard_spec

    (B, _, Lq, D), Lk, Dv = q_shape, k_shape[-2], v_shape[-1]
    if not enabled() or dropout_p > 0.0 or (causal and Lk < Lq):
        return None
    if not (Lq % 128 == 0 and Lk % 128 == 0 and all(
            d % 64 == 0 and d <= 256 for d in (D, Dv))):
        return None
    if window is not None and window < Lk:
        if not causal or mask is not None or Lq != Lk or \
                _band_block(Lq) ** 2 < MIN_STEP_SCORES:
            return None
    elif _divisor(Lq, _TARGET[0]) * _divisor(Lk, _TARGET[1]) < \
            MIN_STEP_SCORES:
        return None
    bias_spec = None
    if mask is not None:
        shape, dtype, wants_grad = mask
        key_bias = tuple(shape) in ((B, 1, 1, Lk), (1, 1, 1, Lk)) and \
            jnp.issubdtype(dtype, jnp.floating)
        if causal or wants_grad or not key_bias:
            return None
        bias_spec = shard_spec((shape[0], 1, Lk), {0: BATCH})[0]
    spec = shard_spec(q_shape, {0: BATCH, 1: HEADS})[0]
    return (spec, spec, spec, bias_spec), spec


def _kept(outputs):
    """The forward call's outputs as a recomputed region keeps them, for the
    forward RULE to return and to hold as residuals: every output of the call
    named, so that no reader of it is left in the backward pass and the call
    is made once (one output not named would be made again, and the call
    with it). q, k, v are not named: the region makes them again from its
    input. Outside a ``jax.checkpoint`` the name is the identity and lowers
    to nothing."""
    from jax.ad_checkpoint import checkpoint_name

    from ...framework.recompute import RECOMPUTE_KEEP

    return tuple(None if x is None else checkpoint_name(x, RECOMPUTE_KEEP)
                 for x in outputs)


def _divisor(L, want):
    """The largest block of at most ``want`` that divides L in whole tiles:
    multiples of 128, or of 8 under a ``want`` below 128 (the interpreter's
    tests pin such blocks; Mosaic would refuse them). L itself if none."""
    tile = LANES if want >= LANES else 8
    for b in range(min(want, L) // tile * tile, 0, -tile):
        if L % b == 0:
            return b
    return L


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention(q, k, v, bias=None, causal=False, scale=None,
                    block_q=None, interpret=False):
    """q: (B, H, Lq, D); k: (B, H, Lk, D); v: (B, H, Lk, Dv) -> (B, H, Lq,
    Dv). The value head may be narrower or wider than the query/key head
    (latent attention without absorption: 192 against 128). ``bias``:
    float32 ``[B or 1, 1, Lk]`` added to the scaled scores of every head and
    query of a batch row before the softmax (a padding mask: 0 / -1e30), or
    ``None``, which compiles to kernels without the operand. Its gradient is
    not computed: zeros come back, and :func:`flash_route` sends a mask that
    wants one down the dense path. ``block_q`` bounds the q block from
    above; the blocks are :func:`block_sizes`'."""
    o, _ = _flash_fwd(q, k, v, bias, causal, scale, block_q, interpret)
    return o


def _static(q, k, v, bias, causal, scale, block_q, interpret):
    """The static arguments of the two jitted calls, from the shapes.
    ``group``: how many rows of the grid's ``BH`` dimension share a row of
    the bias (the heads of a batch row, or all of them for a bias of one
    row); None without a bias."""
    B, H, Lq, D = q.shape
    return dict(
        causal=bool(causal),
        scale=float(scale) if scale is not None else 1.0 / (D ** 0.5),
        blocks=block_sizes(Lq, k.shape[2], D, q.dtype.itemsize, block_q,
                           v.shape[3]),
        group=None if bias is None else H if bias.shape[0] == B else B * H,
        interpret=bool(interpret))


def _flash_fwd(q, k, v, bias, causal, scale, block_q, interpret):
    B, H, Lq, D = q.shape
    Lk, Dv = v.shape[2:]
    o, lse, fix = _kept(_forward(
        q.reshape(B * H, Lq, D), k.reshape(B * H, Lk, D),
        v.reshape(B * H, Lk, Dv), bias,
        **_static(q, k, v, bias, causal, scale, block_q, interpret)))
    o = o.reshape(B, H, Lq, Dv)
    return o, (q, k, v, bias, o, lse, fix)


def _flash_bwd(causal, scale, block_q, interpret, res, do):
    q, k, v, bias, o, lse, fix = res
    B, H, Lq, D = q.shape
    Lk, Dv = v.shape[2:]
    dq, dk, dv = _backward(
        q.reshape(B * H, Lq, D), k.reshape(B * H, Lk, D),
        v.reshape(B * H, Lk, Dv), bias, do.reshape(B * H, Lq, Dv),
        o.reshape(B * H, Lq, Dv), lse, fix,
        **_static(q, k, v, bias, causal, scale, block_q, interpret))
    return (dq.reshape(B, H, Lq, D), dk.reshape(B, H, Lk, D),
            dv.reshape(B, H, Lk, Dv),
            None if bias is None else jnp.zeros_like(bias))


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---- a sliding window ----------------------------------------------------------
def _band_block(L, block=None):
    """The windowed kernels' square block for a row of L: the rule's target,
    or ``block`` where that is smaller."""
    want = _BAND_TARGET[0]
    return _divisor(L, want if block is None else min(want, block))


def band_sizes(L, window, D, itemsize, block=None, Dv=None):
    """(b, b, sub) for the windowed kernels: one block size for queries and
    keys (at most ``block`` where given) and the band of resident rows one
    update takes. A band of ``sub`` rows computes ``window + sub - 1`` key
    columns a row, rounded out to whole lane tiles, of which ``window`` are
    seen: smaller bands waste less and pay more updates. ``window`` does not
    move the blocks: it sets how many of them a grid row visits."""
    b = _band_block(L, block)
    sub = _divisor(b, min(_BAND_TARGET[1], b))
    while vmem_bytes(b, b, sub, D, itemsize, Dv) > VMEM_BUDGET and \
            sub > LANES:
        sub = _divisor(b, sub - LANES)
    return b, b, sub


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def window_attention(q, k, v, window, scale=None, block=None,
                     interpret=False):
    """Causal attention in which query i sees the keys ``0 <= i - j <
    window`` (a sliding window; the Hugging Face convention ``kv_idx > q_idx -
    sliding_window``). q, k: (B, H, L, D); v: (B, H, L, Dv); one length for
    queries and keys, ``window < L`` (a window that reaches the whole row is
    :func:`flash_attention`'s causal call). The three kernels are
    :func:`flash_attention`'s bodies on grids that step over the blocks that
    meet the band alone (``_band_sweep``); ``block`` bounds the block from
    above, else :func:`band_sizes`."""
    o, _ = _window_fwd(q, k, v, window, scale, block, interpret)
    return o


def _window_static(q, v, window, scale, block, interpret):
    D = q.shape[3]
    return dict(causal=True, window=int(window), group=None,
                scale=float(scale) if scale is not None else 1.0 / (D ** 0.5),
                blocks=band_sizes(q.shape[2], window, D, q.dtype.itemsize,
                                  block, v.shape[3]),
                interpret=bool(interpret))


def _window_fwd(q, k, v, window, scale, block, interpret):
    B, H, L, D = q.shape
    Dv = v.shape[3]
    o, lse, _ = _kept(_forward(
        q.reshape(B * H, L, D), k.reshape(B * H, L, D),
        v.reshape(B * H, L, Dv), None,
        **_window_static(q, v, window, scale, block, interpret)))
    o = o.reshape(B, H, L, Dv)
    return o, (q, k, v, o, lse)


def _window_bwd(window, scale, block, interpret, res, do):
    q, k, v, o, lse = res
    B, H, L, D = q.shape
    Dv = v.shape[3]
    dq, dk, dv = _backward(
        q.reshape(B * H, L, D), k.reshape(B * H, L, D),
        v.reshape(B * H, L, Dv), None, do.reshape(B * H, L, Dv),
        o.reshape(B * H, L, Dv), lse, None,
        **_window_static(q, v, window, scale, block, interpret))
    return (dq.reshape(B, H, L, D), dk.reshape(B, H, L, D),
            dv.reshape(B, H, L, Dv))


window_attention.defvjp(_window_fwd, _window_bwd)


def _plan(Lq, Lk, D, Dv, causal, scale, blocks, group, window=None):
    bq, bk, sub = blocks
    offset = Lk - Lq      # aligns the last query with the last key (the
    # causal convention of cached decode)
    nq, nk = Lq // bq, Lk // bk
    # the streamed dimension of the two grids: every k block under a resident
    # q block (forward, dQ) and every q block over a resident k block (dK/dV),
    # or under a window the ``steps`` blocks that can meet the band
    steps = None if window is None else min(-(-(window - 1) // bk) + 1, nk)
    over_q, over_k = (nk, nq) if window is None else (steps, steps)

    # Index of the streamed block: a causal step that has nothing to see
    # names the nearest block that has, so the pipeline fetches nothing new.
    if window is not None:
        def kv_map(b, i, j):
            return (b, jnp.maximum(i - (steps - 1) + j, 0), 0)

        def first_q(j, i):
            return jnp.minimum(j + i, nq - 1)
    elif causal:
        def kv_map(b, i, j):
            last = jnp.clip((i * bq + bq - 1 + offset) // bk, 0, nk - 1)
            return (b, jnp.minimum(j, last), 0)

        def first_q(j, i):
            return jnp.maximum(i, jnp.clip((j * bk - offset) // bq, 0, nq - 1))
    else:
        def kv_map(b, i, j):
            return (b, j, 0)

        def first_q(j, i):
            return i

    kw = dict(scale=scale, fold=math.frexp(scale)[0] == 0.5, causal=causal,
              aligned=bq == bk and offset % bk == 0, bq=bq, bk=bk, sub=sub,
              offset=offset, biased=group is not None)
    if window is not None:
        kw.update(window=window, steps=steps, blocks=nq)
    # the forward's and dQ's grid, (heads, q block, k block): q-sized
    # blocks (q, dQ: D wide; o, dO: Dv), streamed k-sized ones (k: D; v:
    # Dv), and lse / delta rows
    specs = tuple(pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
                  for d in (D, Dv)) + \
        tuple(pl.BlockSpec((1, bk, d), kv_map) for d in (D, Dv)) + \
        (pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),)
    # the keys' bias: the batch row's, streamed with the k block it belongs to
    bias_spec = [] if group is None else [pl.BlockSpec(
        (1, 1, bk), lambda b, i, j: (b // group, 0, kv_map(b, i, j)[1]))]
    return nq, nk, (over_q, over_k), specs, bias_spec, first_q, kw


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
_STATIC = ("causal", "scale", "blocks", "group", "interpret", "window")


# Both calls are jitted on their own: a model's layers then share one trace
# and one lowering of each kernel (tracing the banded bodies costs about as
# much as the rest of a GPT-2 layer's step).
@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(q, k, v, bias, *, causal, scale, blocks, group, interpret,
             window=None):
    """q: [BH, Lq, D]; k: [BH, Lk, D]; v: [BH, Lk, Dv]; bias: [B or 1, 1, Lk]
    or None -> o [BH, Lq, Dv], lse [BH, 1, Lq] and, with a bias, ``fix``
    [BH, 1, Lq]: the factor by which ``exp(s - lse)`` overstates the
    probabilities of a row (1 but for float32's rounding of ``lse``, and
    ``1 / l`` in a row whose keys are all masked, where ``m + log(l)`` is
    ``m``); None without one."""
    (BH, Lq, D), (_, Lk, Dv) = q.shape, v.shape
    bq = blocks[0]
    nq, _, (over_q, _), (q_spec, o_spec, k_spec, v_spec, row_spec), \
        bias_spec, _, kw = _plan(Lq, Lk, D, Dv, causal, scale, blocks, group,
                                 window)
    row = jax.ShapeDtypeStruct((BH, 1, Lq), jnp.float32)
    o, lse, *fix = pl.pallas_call(
        functools.partial(_fwd_kernel, **kw),
        grid=(BH, nq, over_q),
        in_specs=[q_spec, k_spec, v_spec] + bias_spec,
        out_specs=[o_spec, row_spec] + [row_spec] * len(bias_spec),
        out_shape=[jax.ShapeDtypeStruct((BH, Lq, Dv), q.dtype), row] +
        [row] * len(bias_spec),
        scratch_shapes=[
            pltpu.VMEM((bq, D), q.dtype),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name=_kernel_name("fwd", causal, window),
    )(q, k, v, *([] if bias is None else [bias]))
    return o, lse, (fix[0] if fix else None)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(q, k, v, bias, do, o, lse, fix, *, causal, scale, blocks,
              group, interpret, window=None):
    """dq, dk, dv of :func:`_forward`'s operands, from its results."""
    (BH, Lq, D), (_, Lk, Dv) = q.shape, v.shape
    bq, bk, _ = blocks
    nq, nk, (over_q, over_k), (q_spec, o_spec, k_spec, v_spec, row_spec), \
        bias_spec, first_q, kw = _plan(Lq, Lk, D, Dv, causal, scale, blocks,
                                       group, window)
    bias = [] if bias is None else [bias]
    if fix is not None:
        # p = exp(s - lse) * fix, and both kernels are linear in p row by
        # row: dO carries the factor (delta = rowsum(dO * O) then has it too)
        do = (do * jnp.swapaxes(fix, 1, 2)).astype(do.dtype)
    dq, delta = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(BH, nq, over_q),
        in_specs=[q_spec, k_spec, v_spec] + bias_spec +
        [o_spec, o_spec, row_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Lq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), q.dtype),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name=_kernel_name("bwd_dq", causal, window),
    )(q, k, v, *bias, do, o, lse)

    q_spec, do_spec = (
        pl.BlockSpec((1, bq, d), lambda b, j, i: (b, first_q(j, i), 0))
        for d in (D, Dv))
    k_spec, v_spec = (pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
                      for d in (D, Dv))
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, first_q(j, i)))
    if bias:
        bias_spec = [pl.BlockSpec((1, 1, bk),
                                  lambda b, j, i: (b // group, 0, j))]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        grid=(BH, nk, over_k),
        in_specs=[q_spec, k_spec, v_spec] + bias_spec +
        [do_spec, row_spec, row_spec],
        out_specs=[k_spec, v_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Lk, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), k.dtype),
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, Dv), jnp.float32),
        ] + [pltpu.VMEM((bk, LANES), jnp.float32)] * len(bias),
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name=_kernel_name("bwd_dkv", causal, window),
    )(q, k, v, *bias, do, lse, delta)
    return dq, dk, dv
