"""Recompute (gradient checkpointing) — SURVEY §2.12.

Ref: the reference's forward-recomputation machinery
(python/paddle/fluid/incubate/fleet RecomputeOptimizer / recompute
segments). TPU-native: ``jax.checkpoint`` on the sub-graph — the forward
runs normally, residuals inside the segment are dropped, and the backward
pass rematerializes them from the segment inputs. Trades FLOPs for HBM,
the standard lever for deep transformer stacks on TPU.

Works in eager mode and (the real use) inside the fused TrainStep trace:
the whole recompute region becomes one tape node whose vjp is the
jax.checkpoint'd vjp.

Limitation: the segment must be functionally pure w.r.t. its parameters —
buffer mutations inside (e.g. BatchNorm running stats) do not propagate
out of the recompute region. Transformer blocks (LayerNorm) are fine.
"""
from __future__ import annotations

import jax

from ..core import dispatch
from ..core.tensor import Tensor, Parameter

__all__ = ["recompute", "Recompute", "RECOMPUTE_KEEP"]

# An op may mark an intermediate that is small to keep and dear to make again
# (``jax.ad_checkpoint.checkpoint_name(x, RECOMPUTE_KEEP)``): a recomputed
# region keeps those and makes everything else again. Nothing marked, nothing
# kept: the region is then plain ``jax.checkpoint``. Who marks what:
# - the expert layer (``dist/moe.py:_keep``): the router's scores, the plan
#   made from them and the two products into the experts' width, together;
# - the flash kernels (``ops/pallas/flash_attention.py:_kept``): every output
#   of the forward call, ``o``, ``lse`` and with a key bias ``fix``, in the
#   forward rule of their ``custom_vjp``, windowed calls too: the block's
#   backward pass then holds no forward call. q, k, v are made again.
# A mark belongs in a ``custom_vjp``'s forward RULE, on every output of the
# call that is to go: only the rule's jaxpr is in the region that is
# differentiated, and an output without the name is made again, its call
# with it.
RECOMPUTE_KEEP = "recompute_keep"


def _segment_params(function, models):
    from ..nn.layer import Layer

    layers = []
    if isinstance(function, Layer):
        layers.append(function)
    for m in models or ():
        layers.append(m)
    params, seen = [], set()
    for layer in layers:
        for _, p in layer.named_parameters():
            if id(p) not in seen:
                seen.add(id(p))
                params.append(p)
        for _, b in layer.named_buffers():
            if b is not None and id(b) not in seen:
                seen.add(id(b))
                params.append(b)
    return params


def _region(function, params, kwargs=None):
    """``pure(*param_arrays, *input_arrays)``: ``function`` over arrays, its
    parameters explicit, for one taped op (``dispatch.apply``) to hold."""
    from .jit import _rebind

    n = len(params)
    kwargs = kwargs or {}

    def pure(*arrays):
        p_arr, x_arr = list(arrays[:n]), arrays[n:]
        # ops inside run untaped (no per-op ``jax.vjp``): the region is one
        # pure function that the caller's ``jax.vjp`` differentiates whole, so
        # a ``custom_vjp`` inside (the Pallas kernels') keeps its own
        # backward rule; taped, the outer pass would have to differentiate
        # the kernels' forward calls themselves
        with _rebind(params, p_arr), dispatch.fresh_tape(), \
                dispatch.no_grad():
            ts = [Tensor(a, _internal=True) for a in x_arr]
            out = function(*ts, **kwargs)
            if isinstance(out, (tuple, list)):
                return tuple(o._data if isinstance(o, Tensor) else o
                             for o in out)
            return out._data if isinstance(out, Tensor) else out

    return pure


def recompute(function, *args, models=None, **kwargs):
    """Run ``function(*args)`` under gradient checkpointing.

    function: a Layer (its parameters are discovered automatically) or any
    callable over Tensors (pass the Layers it closes over via ``models``).
    """
    params = _segment_params(function, models)
    wrapped = jax.checkpoint(
        _region(function, params, kwargs),
        policy=jax.checkpoint_policies.save_only_these_names(RECOMPUTE_KEEP))
    return dispatch.apply("recompute", wrapped, *params, *args)


class Recompute:
    """Layer wrapper: ``Recompute(block)(x)`` == block(x) with segment
    checkpointing (ref: RecomputeOptimizer's segment list)."""

    def __init__(self, layer):
        self._layer = layer

    def __call__(self, *args, **kwargs):
        return recompute(self._layer, *args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.__dict__["_layer"], name)
