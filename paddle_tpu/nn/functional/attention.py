"""Attention primitives.

Dense reference implementation of scaled-dot-product attention; the pallas
flash-attention kernels (ops/pallas/flash_attention.py) are substituted on TPU
for the calls their ``flash_route`` takes: no mask or a padding mask over the
keys, or a causal sliding window, no dropout, blocks large enough to pay for
a grid step. Ref: the
reference builds attention from primitive ops in its transformer models (book
ch8 / ERNIE); there is no fused kernel to port — this is the TPU-native design
point.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import random as prandom
from ...core.tensor import Tensor
from ...ops._base import register, apply

__all__ = ["scaled_dot_product_attention", "sdpa_bhld"]


@register("sdpa")
def _sdpa(q, k, v, mask, key, *, scale, is_causal, dropout_p,
          mask_grad=True, window=None):
    # q: (B, H, L, Dqk); k: (B, Hkv, L, Dqk); v: (B, Hkv, L, Dv), H a multiple
    # of Hkv. Softmax in f32 for bf16 inputs.
    # ``mask_grad``: whether the caller wants the mask's gradient, which only
    # this dense path computes (a direct caller that does not say gets it).
    # ``window``: a causal query sees its last ``window`` keys, itself
    # among them (``0 <= q - k < window``); None or one that reaches the
    # whole row is plain causal attention.
    from ...ops import pallas as pk

    if window is not None and window >= k.shape[2]:
        window = None

    if k.shape[1] != q.shape[1]:
        # grouped-query heads: key/value head j serves the query heads j g ..
        # j g + g - 1; expanded here, so the flash kernels and the dense
        # path see one head count (the group's gradients add up in the vjp)
        group = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    specs = pk.flash_route(
        q.shape, k.shape, v.shape, is_causal,
        None if mask is None else (mask.shape, mask.dtype, mask_grad),
        dropout_p, window)
    if specs is not None and window is not None:
        return pk.run(pk.window_attention, (specs[0][:3], specs[1]),
                      (q, k, v), int(window), float(scale), None)
    if specs is not None:
        # a mask the route took is a bias on the keys: (B or 1, 1, 1, Lk)
        bias = None if mask is None else mask[:, 0].astype(jnp.float32)
        return pk.run(pk.flash_attention, specs, (q, k, v, bias),
                      bool(is_causal), float(scale), None)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if is_causal:
        Lq, Lk = scores.shape[-2], scores.shape[-1]
        causal = jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq)
        if window is not None:      # the band: not further back than this
            causal &= jnp.triu(jnp.ones((Lq, Lk), bool),
                               k=Lk - Lq - window + 1)
        scores = jnp.where(causal, scores, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -1e30)
        else:
            scores = scores + mask.astype(jnp.float32)
    p = jax.nn.softmax(scores, axis=-1)
    if dropout_p > 0.0 and key is not None:
        # dropout on the attention *weights* (reference semantics), before
        # the V matmul, with upscale-in-train normalization
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def sdpa_bhld(query, key, value, attn_mask=None, scale=None, is_causal=False,
              dropout_p=0.0, training=True, window=None):
    """(B, H, L, D) layout — internal form used by nn layers. ``value`` may
    have a head width of its own, ``Dv`` != ``Dqk``; the result is ``(B, H,
    Lq, Dv)``. ``key`` and ``value`` may have fewer heads, ``H % H_kv == 0``
    (grouped-query attention: each serves ``H / H_kv`` consecutive query
    heads). Which calls the flash kernels take is theirs to say
    (``ops.pallas.flash_route``); every other call takes the dense path.
    A masked call is theirs when ``attn_mask`` is an additive key mask, ``(B
    or 1, 1, 1, Lk)`` float, with ``stop_gradient`` set (anything made from
    integer input has it): it enters the kernels as a bias on the keys. A
    mask that wants a gradient, has a row a query or a head, or is boolean
    meets the dense scores as before. ``window`` (with ``is_causal``): a
    query sees its last ``window`` keys, itself among them, ``0 <= q - k <
    window``; the windowed kernels take it where the route says, else the
    dense path masks the band."""
    if window is not None and (not is_causal or window < 1):
        raise ValueError(f"window={window} wants is_causal=True and at least "
                         f"the query's own key")
    d = query.shape[-1] if not hasattr(query, "_data") else query._data.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    use_drop = dropout_p > 0.0 and training
    rng = Tensor(prandom.next_key(), _internal=True) if use_drop else None
    # a call without a window carries no such attribute, as before there
    # was one
    band = {} if window is None else {"window": int(window)}
    return apply("sdpa", query, key, value, attn_mask, rng,
                 scale=float(scale), is_causal=bool(is_causal),
                 dropout_p=float(dropout_p) if use_drop else 0.0,
                 mask_grad=not getattr(attn_mask, "stop_gradient", True),
                 **band)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None, window=None):
    """Paddle 2.x layout (B, L, H, D). ``window``: see :func:`sdpa_bhld`."""
    from ...ops.manipulation import transpose

    q = transpose(query, [0, 2, 1, 3])
    k = transpose(key, [0, 2, 1, 3])
    v = transpose(value, [0, 2, 1, 3])
    out = sdpa_bhld(q, k, v, attn_mask=attn_mask, is_causal=is_causal,
                    dropout_p=dropout_p, training=training, window=window)
    return transpose(out, [0, 2, 1, 3])
