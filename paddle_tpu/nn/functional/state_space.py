"""Functionals of a Mamba-2 state-space layer (arXiv:2405.21060), each a
registered op so that the compiled step names them: the step size and the
chunked recurrence. The layer's causal convolution and its gated norm are
``linear_attention.short_conv`` (with a bias) and ``gated_rms_norm`` (with
``silu_first``).

The recurrence, a head h of width P over a state of N columns (``x_t`` in
R^P, ``B_t``, ``C_t`` in R^N shared by all heads, ``Delta_t`` the head's step
size, ``A = -exp(A_log)`` its scalar rate):

    S_t = exp(A Delta_t) S_{t-1} + Delta_t x_t B_t^T     in R^{P x N}
    y_t = S_t C_t + D x_t,   S = 0 at the start of a row

``ssm_chunk`` computes it a chunk of Q tokens at a time. With ``G_i = A
(Delta_1 + .. + Delta_i)`` the log-decay from the chunk's start through its
token i, a chunk that starts at state S gives

    y_i = sum_{j <= i} exp(G_i - G_j) (C_i . B_j) Delta_j x_j
          + exp(G_i) S C_i + D x_i
    S'  = exp(G_Q) S + sum_j exp(G_Q - G_j) Delta_j x_j B_j^T

by one of two paths, the same function and the same arithmetic. On a TPU,
for shapes ``ops.pallas.ssm_scan_route`` takes (bfloat16 or float32 heads, a
chunk and a state of whole 128-lane columns, heads that fill 128-lane slabs,
one device's rows), two Pallas kernels (``ops/pallas/ssm_scan.py``:
``ssm_scan_fwd`` and ``ssm_scan_bwd``, forward and backward by hand) that
keep a chunk's decays and the carried state in VMEM; the cumulative
log-decay ``G``, its floor and its transpose to ``Delta`` and ``A_log`` stay
XLA code here. Everywhere else (the CPU, a mesh, other shapes), and as the
kernels' oracle in the tests, the jnp body below: one ``lax.scan`` over the
chunks carrying ``S``, each step under a ``checkpoint`` so that the backward
pass keeps a state a chunk and makes a chunk's (heads, Q, Q) decays again.
There is no switch: the route reads the call's shapes. **Every exponential is of a difference
``G_i - G_j <= 0`` with ``j <= i`` inside one chunk** (``j = 0``, the chunk's
start, for ``exp(G_i)``): none can overflow, and one that underflows is a
decay that is zero in float32 too. The quotient of cumulative products
``exp(G_i) / exp(G_j)`` that the textbook form has is 0 / 0 once a chunk's
log-decay passes -88, which ``A = -1`` at ``Delta = 0.69`` does in 128 tokens.
All of it is float32, matrix products at the highest precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...ops._base import register, apply

__all__ = ["ssm_gate", "ssm_chunk"]

_HIGHEST = jax.lax.Precision.HIGHEST


@register("ssm_gate")
def _ssm_gate(raw, dt_bias):
    return jax.nn.softplus(raw.astype(jnp.float32) +
                           dt_bias.astype(jnp.float32))


def ssm_gate(raw, dt_bias):
    """The step size a head, float32: ``Delta = softplus(raw + dt_bias)``
    over (B, L, H); ``ssm_chunk`` makes the decay ``exp(-exp(A_log) Delta)``
    of it."""
    return apply("ssm_gate", raw, dt_bias)


@register("ssm_chunk")
def _ssm_chunk(x, dt, a_log, b, c, d, *, chunk):
    # x: (B, L, H, P); dt: (B, L, H); a_log, d: (H,); b, c: (B, L, N)
    from ...ops import pallas as pk

    batch, length, heads, width = x.shape
    pad = -length % chunk
    n = (length + pad) // chunk
    rate = -jnp.exp(a_log.astype(jnp.float32))            # A: (H,)

    specs = pk.ssm_scan_route(x.shape, x.dtype, b.shape[-1], chunk)
    if specs is not None:
        # the kernels take the chunk's log-decays as an operand: the
        # cumulative sum, its transpose and the decay's floor stay XLA code
        xp, dtp, bp, cp = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                                   (t.ndim - 2))
                           for t in (x, dt.astype(jnp.float32), b, c))
        cum = jnp.cumsum((dtp * rate).reshape(batch, n, chunk, heads), axis=2)
        y = pk.run(pk.ssm_scan, specs,
                   (xp, dtp, cum.reshape(dtp.shape), bp, cp, d), chunk)
        return y[:, :length], jax.lax.stop_gradient(jnp.min(cum[:, :, -1]))

    def chunks(t):
        # (B, L, ...) -> (n, B, Q, ...): zeros after the row's end are tokens
        # that write nothing and decay nothing (Delta = 0)
        t = jnp.pad(t.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((batch, n, chunk) + t.shape[2:]), 1, 0)

    mm = functools.partial(jnp.einsum, precision=_HIGHEST)
    i, j = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]

    @jax.checkpoint
    def one(state, inputs):
        xc, dtc, bc, cc = inputs    # (B, Q, H, P), (B, Q, H), (B, Q, N) twice
        cum = jnp.cumsum(dtc * rate, axis=1)              # G: (B, Q, H)
        total = cum[:, -1]                                # G_Q: (B, H)
        by_head = jnp.moveaxis(cum, 2, 1)                 # (B, H, Q)
        decay = jnp.exp(jnp.where(
            j <= i, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
        u = dtc[..., None] * xc                           # Delta_j x_j
        y = mm("bhij,bjhp->bihp",
               mm("bin,bjn->bij", cc, bc)[:, None] * decay, u)
        y = y + mm("bin,bhpn->bihp", cc, state) * jnp.exp(cum)[..., None]
        to_end = jnp.exp(total[:, None] - cum)            # exp(G_Q - G_j)
        state = state * jnp.exp(total)[..., None, None] + \
            mm("bjhp,bjn->bhpn", u * to_end[..., None], bc)
        return state, (y, total)

    _, (y, totals) = jax.lax.scan(
        one, jnp.zeros((batch, heads, width, b.shape[-1]), jnp.float32),
        tuple(chunks(t) for t in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(batch, n * chunk, heads, width)
    y = y[:, :length] + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), jax.lax.stop_gradient(jnp.min(totals))


def ssm_chunk(x, dt, a_log, B, C, D, *, chunk=256):
    """The state-space recurrence over rows ``x`` (B, L, H, P) in chunks of
    ``chunk`` tokens, each row from ``S = 0``: ``(y, decay_min)``, the outputs
    in ``x``'s shape and type and the most negative log-decay a head ran up
    over one chunk (float32, no gradient: past -88 a chunk's first tokens
    reach its end as zero). ``dt`` (B, L, H) is ``ssm_gate``'s, ``a_log`` and
    ``D`` are (H,), ``B`` and ``C`` (B, L, N) are shared by all heads (one
    group)."""
    return apply("ssm_chunk", x, dt, a_log, B, C, D, chunk=int(chunk))
