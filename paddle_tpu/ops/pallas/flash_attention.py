"""Flash attention: blocked online-softmax attention as a pallas TPU kernel.

TPU-native replacement for the reference's dense-score attention graphs
(the reference has no fused attention kernel — its transformers build
softmax(QK^T)V from primitive CUDA ops; this kernel is the TPU design
point the hand-fused CUDA kernels in paddle/fluid/operators aspire to).

Design:
- O(L) memory: scores never materialize; K/V stream through VMEM blocks
  while a running (max, sumexp) pair rescales the accumulator.
- fwd saves only the logsumexp row stats; bwd recomputes probabilities
  blockwise (two kernels: dq over q-blocks, dk/dv over k-blocks).
- f32 accumulation regardless of input dtype (bf16 in, f32 softmax).
- `interpret=True` runs the same kernels on CPU for tests.

Layout: (B, H, L, D) — collapsed to (BH, L, D) for the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _causal_mask(qi, ki, block_q, block_k, offset):
    """Additive mask block (block_q, block_k) for q-block qi / k-block ki.

    offset = Lk - Lq aligns the last query with the last key (standard
    causal convention for cached decode)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    q_pos = qi * block_q + rows + offset
    k_pos = ki * block_k + cols
    return jnp.where(q_pos >= k_pos, 0.0, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k, Lk, offset):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (Bq, D)
    block_q, D = q.shape
    nk = Lk // block_k

    acc = jnp.zeros((block_q, D), jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)

    def body(ki, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            s = s + _causal_mask(qi, ki, block_q, block_k, offset)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(p, v,
                                        preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    if causal:
        # skip fully-masked k-blocks beyond the diagonal
        last = jnp.minimum(
            nk, ((qi + 1) * block_q + offset + block_k - 1) // block_k)
        acc, m, l = jax.lax.fori_loop(0, last, body, (acc, m, l))
    else:
        acc, m, l = jax.lax.fori_loop(0, nk, body, (acc, m, l))

    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)   # (bq, 1) — trailing unit dim keeps the
    # block 2-D-tileable on TPU ((1, bq) row blocks violate the min tile)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, causal, block_k, Lk, offset):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]        # (bq, 1)
    delta = delta_ref[0]    # (bq, 1)
    block_q, D = q.shape
    nk = Lk // block_k
    dq = jnp.zeros((block_q, D), jnp.float32)

    def body(ki, dq):
        k = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            s = s + _causal_mask(qi, ki, block_q, block_k, offset)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    if causal:
        last = jnp.minimum(
            nk, ((qi + 1) * block_q + offset + block_k - 1) // block_k)
        dq = jax.lax.fori_loop(0, last, body, dq)
    else:
        dq = jax.lax.fori_loop(0, nk, body, dq)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, Lq, offset):
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    block_k, D = k.shape
    nq = Lq // block_q
    dk = jnp.zeros((block_k, D), jnp.float32)
    dv = jnp.zeros((block_k, D), jnp.float32)

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32) \
            * scale
        do = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi * block_q, block_q)]      # (bq, 1)
        delta = delta_ref[0, pl.ds(qi * block_q, block_q)]  # (bq, 1)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            s = s + _causal_mask(qi, ki, block_q, block_k, offset)
        p = jnp.exp(s - lse)
        dv_new = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_new = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk_new, dv_new

    if causal:
        # q-blocks before the diagonal never attend to this k-block
        first = jnp.maximum(0, (ki * block_k - offset) // block_q)
        dk, dv = jax.lax.fori_loop(first, nq, body, (dk, dv))
    else:
        dk, dv = jax.lax.fori_loop(0, nq, body, (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _kernel_name(base, causal):
    """The name the call carries into the HLO and the device trace. The
    ``_causal`` suffix tells a reader of the trace that the kernel skips the
    blocks beyond the diagonal (about half the work)."""
    return base + "_causal" if causal else base


def _pick_block(L, want):
    b = min(want, L)
    while L % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    interpret=False):
    """q: (B, H, Lq, D); k/v: (B, H, Lk, D) -> (B, H, Lq, D)."""
    o, _ = _flash_fwd(q, k, v, causal, scale, block_q, interpret)
    return o


def _flash_call(q, k, v, causal, scale, block_q, interpret):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    bq = _pick_block(Lq, block_q)
    bk = _pick_block(Lk, max(128, bq))
    qr = q.reshape(B * H, Lq, D)
    kr = k.reshape(B * H, Lk, D)
    vr = v.reshape(B * H, Lk, D)
    grid = (B * H, Lq // bq)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_k=bk, Lk=Lk, offset=Lk - Lq)
    o, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Lq, 1), jnp.float32),
        ],
        interpret=interpret,
        name=_kernel_name("flash_fwd", causal),
    )(qr, kr, vr)
    return o.reshape(B, H, Lq, D), lse   # lse stays (BH, Lq, 1) for bwd


def _flash_fwd(q, k, v, causal, scale, block_q, interpret):
    o, lse = _flash_call(q, k, v, causal, scale, block_q, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, interpret, res, do):
    q, k, v, o, lse = res
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    bq = _pick_block(Lq, block_q)
    bk = _pick_block(Lk, max(128, bq))
    qr = q.reshape(B * H, Lq, D)
    kr = k.reshape(B * H, Lk, D)
    vr = v.reshape(B * H, Lk, D)
    dor = do.reshape(B * H, Lq, D)
    lser = lse                                   # (BH, Lq, 1)
    # delta_i = rowsum(dO * O) — the softmax-jacobian diagonal term
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(B * H, Lq, 1)

    dq_kern = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                block_k=bk, Lk=Lk, offset=Lk - Lq)
    dq = pl.pallas_call(
        dq_kern,
        grid=(B * H, Lq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Lq, D), q.dtype),
        interpret=interpret,
        name=_kernel_name("flash_bwd_dq", causal),
    )(qr, kr, vr, dor, lser, delta)

    dkv_kern = functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                                 block_q=bq, Lq=Lq, offset=Lk - Lq)
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=(B * H, Lk // bk),
        in_specs=[
            pl.BlockSpec((1, Lq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Lq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Lq, 1), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Lq, 1), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Lk, D), v.dtype),
        ],
        interpret=interpret,
        name=_kernel_name("flash_bwd_dkv", causal),
    )(qr, kr, vr, dor, lser, delta)
    return (dq.reshape(B, H, Lq, D), dk.reshape(B, H, Lk, D),
            dv.reshape(B, H, Lk, D))


flash_attention.defvjp(_flash_fwd, _flash_bwd)
