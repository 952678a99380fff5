"""Ragged paged decode attention as a pallas TPU kernel.

The serving path (``paddle_tpu.serving``) keeps every sequence's KV
history in fixed-size pages scattered across one preallocated pool
(``serving.kv_cache.PagedKVCache``), so a decode step cannot use the
dense ``flash_attention`` layout — each query must *gather* its K/V
through a per-sequence page table, and the batch is ragged (every
sequence has its own context length). This is the TPU-native kernel
shape of Ragged Paged Attention (arXiv 2604.15464): one kernel serves
the whole mixed batch, no per-sequence padding to the longest context.

Design:
- grid ``(B, max_pages)``: the page axis iterates sequentially per
  sequence, so one VMEM-resident (m, l, acc) online-softmax carry in
  scratch accumulates across a sequence's pages — O(page) memory.
- the page table and context lengths ride scalar prefetch
  (``PrefetchScalarGridSpec``): the K/V BlockSpec index map reads
  ``page_table[b, p]`` *before* the body runs, so the pool pages DMA
  straight from HBM into VMEM blocks — the gather never materializes.
- pages past a sequence's last (``p >= ceil(len/page)``) are skipped
  with ``pl.when``; inside the last live page, positions ``>= len``
  are masked to -inf, which is what makes ragged lengths exact.
- f32 softmax/accumulation regardless of pool dtype.
- ``interpret=True`` runs the identical kernel on CPU — the tier-1
  numerics gate pins it against ``dense_decode_reference`` below.

Layouts: q ``(B, H, D)`` (one decode token per sequence);
k/v pools ``(P, page_size, H, D)``; page_table ``(B, max_pages)``
int32; lengths ``(B,)`` int32 (tokens already *in* the cache that this
query attends over, query included).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "dense_decode_reference"]

NEG_INF = -1e30


def _decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, page_size, scale):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = len_ref[b]
    n_pages = (seq_len + page_size - 1) // page_size

    @pl.when(p < n_pages)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale          # (H, D)
        k = k_ref[0].astype(jnp.float32)                  # (T, H, D)
        v = v_ref[0].astype(jnp.float32)
        # per-head scores q·k over the page: (H, T). An MXU dot would
        # contract D but cross the head axes (HxH); heads are few and
        # D small for decode, so the VPU elementwise-sum is the shape
        s = jnp.sum(q[:, None, :] * jnp.swapaxes(k, 0, 1), axis=-1)
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)                 # (1, T)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)                         # (H, T)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(pexp, axis=-1,
                                              keepdims=True)
        # (H, T) @ (T, D) per head: contract T with v (T, H, D)
        pv = jnp.sum(pexp[:, :, None] * jnp.swapaxes(v, 0, 1), axis=1)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(p == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           scale=None, interpret=False):
    """Decode attention for ragged sequences through a paged KV pool.

    q: ``(B, H, D)`` — the current token's query per sequence;
    k_pages/v_pages: ``(P, page_size, H, D)`` pools;
    page_table: ``(B, max_pages)`` page ids per sequence (entries past
    a sequence's last live page are ignored — any in-range id is safe,
    the pool's null page included);
    lengths: ``(B,)`` context length per sequence (the query's own
    position is ``lengths - 1``).

    Returns ``(B, H, D)`` in q's dtype. ``interpret=True`` runs on CPU.
    """
    B, H, D = q.shape
    P, page_size = k_pages.shape[0], k_pages.shape[1]
    max_pages = page_table.shape[1]
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    # clamp so even garbage tail entries DMA a real page (masked anyway)
    page_table = jnp.clip(page_table.astype(jnp.int32), 0, P - 1)
    lengths = lengths.astype(jnp.int32)

    kern = functools.partial(_decode_kernel, page_size=page_size,
                             scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, p, pt, ln: (b, 0, 0)),
            pl.BlockSpec((1, page_size, H, D),
                         lambda b, p, pt, ln: (pt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, page_size, H, D),
                         lambda b, p, pt, ln: (pt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, p, pt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),   # acc
            pltpu.VMEM((H, 1), jnp.float32),   # running max
            pltpu.VMEM((H, 1), jnp.float32),   # running sumexp
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_table, lengths, q, k_pages, v_pages)


def dense_decode_reference(q, k, v, lengths=None):
    """The CPU oracle the kernel is pinned against: masked dense decode
    attention in f32. ``k``/``v`` are ``(B, L, H, D)`` contiguous
    histories (L >= every length); ``lengths (B,)`` masks the ragged
    tails (None = all L live)."""
    B, H, D = q.shape
    L = k.shape[1]
    qf = q.astype(jnp.float32) / (D ** 0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # (B, H, L): per-head scores against every cached position
    s = jnp.einsum("bhd,blhd->bhl", qf, kf)
    if lengths is not None:
        mask = jnp.arange(L)[None, None, :] < lengths[:, None, None]
        s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhl,blhd->bhd", w, vf)
    return out.astype(q.dtype)
