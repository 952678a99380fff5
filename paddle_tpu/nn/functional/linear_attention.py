"""Functionals of a gated-delta-rule linear-attention layer (Kimi Linear's
KDA, arXiv:2510.26692: the delta rule with a decay a channel), each a
registered op so that the compiled step names them: the causal depthwise
short convolution, the gates, the chunked rule itself, and the RMS norm with
a sigmoid gate on a head's output.

The rule, a head of width d (``q``, ``k`` unit vectors, ``q`` scaled):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,   S_0 = 0,   alpha_t = exp(g_t) in (0, 1]^d

``kda_chunk`` computes it a chunk of C tokens at a time. With ``G_i = g_1 +
.. + g_i`` the log-decay from the chunk's start to its token i (``G_0 = 0``),
``u_i = beta_i (v_i - S_{i-1}^T (alpha_i k_i))`` turns the rule into ``S_i =
Diag(alpha_i) S_{i-1} + k_i u_i^T``, and over a chunk that starts at state S:

    A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)   (j <  i)
    B_ij = sum_c q_ic k_jc exp(G_ic - G_jc)   (j <= i)
    (I + Diag(beta) A) [U~, W] = Diag(beta) [V, exp(G) K]      (triangular)
    U = U~ - W S
    O = (exp(G) Q) S + B U
    S' = Diag(exp(G_C)) S + (exp(G_C - G) K)^T U  =  P S + R

Everything but ``S`` is known a chunk at a time without it, so the chunks'
``A, B, U~, W, P, R`` are made side by side, one ``lax.scan`` over chunks
carries ``S`` (``S' = P S + R``: one product a step) and the outputs follow
side by side again. **Every exponential is of a difference ``G_i - G_j <= 0``
with ``j <= i`` inside one chunk** (``j = 0``, the chunk's start, for
``exp(G)``): none can overflow, and one that underflows is a decay that is
zero in float32 too. The split ``exp(G_i) exp(-G_j)`` that would turn ``A``
and ``B`` into matrix products overflows at a decay of 88 a chunk, which a
published ``A_log`` reaches; ``A`` and ``B`` are elementwise sums over the
channels instead. All of it is float32, matrix products at the highest
precision (they are 3% of the layer's projections' FLOPs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...ops._base import register, apply

__all__ = ["short_conv", "kda_gate", "kda_chunk", "gated_rms_norm"]

_HIGHEST = jax.lax.Precision.HIGHEST


@register("short_conv")
def _short_conv(x, w, bias=None):
    # x: (B, L, C); w: (K, C), w[K - 1] on the token itself; bias: (C,).
    # Depthwise, causal (K - 1 zeros in front of the row), then SiLU; float32
    # inside.
    taps, length = w.shape[0], x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    y = sum(xf[:, j:j + length] * wf[j] for j in range(taps))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y).astype(x.dtype)


def short_conv(x, weight, bias=None):
    """``y_t = SiLU(sum_j w[j] x_{t - (K - 1) + j} + bias)`` a channel of
    ``x`` (B, L, C), ``weight`` (K, C), ``bias`` (C,) or none: a causal
    depthwise convolution over the row, **with the SiLU after it** (the
    delta-rule and state-space layers'). ``gated_conv.gated_short_conv`` is
    the one without an activation, gated on both sides instead."""
    return apply("short_conv", x, weight, *(() if bias is None else (bias,)))


@register("kda_gate")
def _kda_gate(raw, a_log, dt_bias, beta_logits, *, head_dim, neg_eigval):
    # raw: (B, L, H d); a_log: (H,); dt_bias: (H d,); beta_logits: (B, L, H)
    b, l, h = beta_logits.shape
    f = raw.astype(jnp.float32) + dt_bias.astype(jnp.float32)
    g = -jnp.exp(a_log.astype(jnp.float32))[:, None] * \
        jax.nn.softplus(f).reshape(b, l, h, head_dim)
    beta = jax.nn.sigmoid(beta_logits.astype(jnp.float32))
    return g, (2.0 * beta if neg_eigval else beta)


def kda_gate(raw, a_log, dt_bias, beta_logits, *, head_dim, neg_eigval=True):
    """The rule's two gates, float32: the log-decay a channel ``g = -exp(A_log)
    softplus(raw + dt_bias)`` (B, L, H, d), at most 0, and the step ``beta =
    sigmoid(beta_logits)`` (B, L, H), doubled to (0, 2) where the transition
    may have negative eigenvalues."""
    return apply("kda_gate", raw, a_log, dt_bias, beta_logits,
                 head_dim=int(head_dim), neg_eigval=bool(neg_eigval))


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


@jax.checkpoint
def _pairs(q, k, cum):
    """(A, B) of one chunk, (..., C, C) each, from (..., C, d) operands: the
    (C, C, d) decays are made again in the backward pass, never kept."""
    size = q.shape[-2]
    i, j = jnp.arange(size)[:, None], jnp.arange(size)[None, :]
    decay = jnp.exp(jnp.where(
        (j <= i)[..., None], cum[..., :, None, :] - cum[..., None, :, :],
        -jnp.inf))
    kd = k[..., None, :, :] * decay
    a = jnp.sum(k[..., :, None, :] * kd, axis=-1)
    b = jnp.sum(q[..., :, None, :] * kd, axis=-1)
    return jnp.where(j < i, a, 0.0), b


@register("kda_chunk")
def _kda_chunk(q, k, v, g, beta, *, chunk):
    # q, k: (B, L, H, d); v: (B, L, H, dv); g: (B, L, H, d); beta: (B, L, H)
    batch, length, heads, d = q.shape
    out_dtype = v.dtype
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)
    pad = -length % chunk
    n = (length + pad) // chunk

    def chunks(x):
        # (B, L, H, ...) -> (n, B, H, C, ...): zeros after the row's end are
        # tokens that write nothing (k = 0) and decay nothing (g = 0)
        x = jnp.pad(x.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((batch, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    q, k = _unit(q) * d ** -0.5, _unit(k)
    cum = jnp.cumsum(g, axis=-2)                          # G: (n, B, H, C, d)
    total = cum[..., -1:, :]                              # G_C
    a, b = jax.lax.map(lambda x: _pairs(*x), (q, k, cum))
    from_start = jnp.exp(cum)                             # exp(G_i - G_0)
    solved = jax.lax.linalg.triangular_solve(
        jnp.eye(chunk, dtype=jnp.float32) + beta[..., None] * a,
        beta[..., None] * jnp.concatenate([v, from_start * k], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    u0, w = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    to_end = jnp.exp(total - cum) * k                     # exp(G_C - G_j) k_j
    p = -mm("...cd,...ce->...de", to_end, w)
    p = p + jnp.exp(total)[..., 0, :, None] * jnp.eye(d, dtype=jnp.float32)
    r = mm("...cd,...ce->...de", to_end, u0)

    def carry(s, pr):
        return mm("...de,...ef->...df", pr[0], s) + pr[1], s

    _, starts = jax.lax.scan(
        carry, jnp.zeros((batch, heads, d, v.shape[-1]), jnp.float32), (p, r))
    u = u0 - mm("...cd,...de->...ce", w, starts)
    o = mm("...cd,...de->...ce", from_start * q, starts) + \
        mm("...ij,...je->...ie", b, u)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)         # (B, n, C, H, dv)
    o = o.reshape(batch, n * chunk, heads, -1)[:, :length]
    return o.astype(out_dtype), jax.lax.stop_gradient(jnp.min(total))


def kda_chunk(q, k, v, g, beta, *, chunk=64):
    """The gated delta rule over rows (B, L, H, d) in chunks of ``chunk``
    tokens, each row from ``S = 0``: ``(o, decay_min)``, the outputs (B, L,
    H, dv) in ``v``'s type and the most negative log-decay a channel ran up
    over one chunk (float32, no gradient: how near float32's exp(-88) the
    gates run). ``q`` and ``k`` are made unit vectors a head first (``x /
    sqrt(sum x^2 + 1e-6)``) and ``q`` is scaled by ``d^-1/2``; ``g`` and
    ``beta`` are ``kda_gate``'s."""
    return apply("kda_chunk", q, k, v, g, beta, chunk=int(chunk))


@register("gated_rms_norm")
def _gated_rms_norm(x, gate, weight, *, epsilon, silu_first=False):
    # x, gate: (..., d); statistics and the gate in float32
    xf = x.astype(jnp.float32)
    if silu_first:
        xf = xf * jax.nn.silu(gate.astype(jnp.float32))
    out = xf * jax.lax.rsqrt(
        jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + epsilon)
    out = out * weight.astype(jnp.float32)
    if not silu_first:
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32))
    return out.astype(x.dtype)


def gated_rms_norm(x, gate, weight, epsilon=1e-6, silu_first=False):
    """``RMS_w(x) * sigmoid(gate)`` over the last axis: the norm of a linear-
    attention head's output under its output gate. With ``silu_first`` the
    gate comes before the norm, ``RMS_w(x * SiLU(gate))``, as a Mamba-2 layer
    norms all its heads' channels together."""
    # the attribute only where it is set: a call without it is the op it was
    mode = {"silu_first": True} if silu_first else {}
    return apply("gated_rms_norm", x, gate, weight, epsilon=float(epsilon),
                 **mode)
