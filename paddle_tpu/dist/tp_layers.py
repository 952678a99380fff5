"""Tensor-parallel layers.

TPU-native analog of the reference's model-parallel layers (ERNIE-era
c_allgather/c_reducescatter column/row parallel FC, ParallelCrossEntropy —
operators/collective/*): instead of explicit collectives around sharded
weights, each layer declares a PartitionSpec on its weight and constrains
its activations; XLA's SPMD partitioner materializes the same
all-gather/reduce-scatter pattern on ICI, fused into surrounding matmuls.

Mesh axis convention: 'model' is the TP axis (override via mp_axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.layer import Layer
from ..nn import initializer as I
from ..ops._base import register, apply

__all__ = [
    "ColumnParallelLinear", "RowParallelLinear", "VocabParallelEmbedding",
    "ParallelCrossEntropy", "mark_sharding",
]


def mark_sharding(param, spec):
    """Attach a PartitionSpec to a Parameter; honored by
    DistributedTrainStep placement and with_sharding_constraint."""
    param.sharding_spec = spec
    return param


@register("sharding_constraint")
def _sharding_constraint(x, *, spec):
    # spec covers the TRAILING dims; the leading ones (the batch) stay
    # with the partitioner. Spelling them None would replicate the
    # global batch onto every device of the data axis.
    lead = (P.UNCONSTRAINED,) * (x.ndim - len(spec))
    try:
        return jax.lax.with_sharding_constraint(x, P(*lead, *spec))
    except (ValueError, RuntimeError):
        return x  # outside a mesh context: no-op


def _constrain(x, spec):
    """Constrain the trailing dims of an activation: a mesh axis name
    shards that dim, None replicates it; dims ``spec`` does not reach
    are left unconstrained. Axis names the active mesh does not have
    count as None."""
    from .env import get_mesh

    mesh = get_mesh()
    if mesh is None:
        return x
    return apply("sharding_constraint", x,
                 spec=tuple(a if a in mesh.shape else None for a in spec))


class ColumnParallelLinear(Layer):
    """Weight (in, out) sharded on out: y = x @ W is column-sliced; with
    gather_output the result is re-replicated (ref: c_allgather after the
    partial matmul)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, mp_axis="model",
                 name=None):
        super().__init__()
        self.gather_output = gather_output
        self.mp_axis = mp_axis
        self.weight = self.create_parameter((in_features, out_features),
                                            attr=weight_attr)
        mark_sharding(self.weight, P(None, mp_axis))
        self.bias = self.create_parameter((out_features,), attr=has_bias if
                                          has_bias is not True else None,
                                          is_bias=True) if has_bias else None
        if self.bias is not None:
            mark_sharding(self.bias, P(mp_axis))

    def forward(self, x):
        y = F.linear(x, self.weight, self.bias)
        return _constrain(
            y, (None if self.gather_output else self.mp_axis,))


class RowParallelLinear(Layer):
    """Weight (in, out) sharded on in: partial products psum into the full
    output (ref: c_allreduce after row-parallel matmul)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False, mp_axis="model",
                 name=None):
        super().__init__()
        self.input_is_parallel = input_is_parallel
        self.mp_axis = mp_axis
        self.weight = self.create_parameter((in_features, out_features),
                                            attr=weight_attr)
        mark_sharding(self.weight, P(mp_axis, None))
        self.bias = self.create_parameter((out_features,), is_bias=True) \
            if has_bias else None
        if self.bias is not None:
            mark_sharding(self.bias, P())

    def forward(self, x):
        if self.input_is_parallel:
            x = _constrain(x, (self.mp_axis,))
        y = F.linear(x, self.weight, self.bias)
        return _constrain(y, (None,))


class VocabParallelEmbedding(Layer):
    """Embedding table sharded over vocab (ref: c_embedding +
    c_allreduce_sum)."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_axis="model", name=None):
        super().__init__()
        self.mp_axis = mp_axis
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=I.Normal(0.0, 0.02))
        mark_sharding(self.weight, P(mp_axis, None))

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return _constrain(out, (None,))


class ParallelCrossEntropy(Layer):
    """CE over class-sharded logits (ref: c_softmax_with_cross_entropy):
    constrain logits to the class sharding and let GSPMD turn the softmax
    reductions into psums over the model axis."""

    def __init__(self, mp_axis="model", ignore_index=-100, name=None):
        super().__init__()
        self.mp_axis = mp_axis
        self.ignore_index = ignore_index

    def forward(self, logits, label):
        logits = _constrain(logits, (self.mp_axis,))
        return F.cross_entropy(logits, label, reduction="none",
                               ignore_index=self.ignore_index)
