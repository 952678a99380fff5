"""Normalization layers (ref: python/paddle/nn/layer/norm.py,
fluid/dygraph/nn.py BatchNorm; kernels: batch_norm_op.cc, layer_norm_op.cc).

BatchNorm running stats are Buffers updated functionally each train step —
no in-place device mutation, so the layer stays jit-compatible. SyncBatchNorm
degenerates to BatchNorm on a single device; under a data-parallel Mesh the
batch axis is sharded and XLA's reduction over it IS the cross-replica sync.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ...core.tensor import Tensor
from .. import functional as F
from ..layer import Layer
from .. import initializer as I

__all__ = [
    "BatchNorm", "BatchNorm1D", "BatchNorm2D", "BatchNorm3D", "SyncBatchNorm",
    "LayerNorm", "RMSNorm", "GroupNorm", "InstanceNorm1D", "InstanceNorm2D",
    "InstanceNorm3D", "LocalResponseNorm", "SpectralNorm",
]


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        self.weight = self.create_parameter(
            (num_features,), attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter((num_features,), attr=bias_attr,
                                          is_bias=True)
        self.register_buffer("_mean", Tensor(jnp.zeros((num_features,), jnp.float32), _internal=True))
        self.register_buffer("_variance", Tensor(jnp.ones((num_features,), jnp.float32), _internal=True))

    def forward(self, x):
        return F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self._momentum,
            epsilon=self._epsilon, data_format=self._data_format,
            use_global_stats=self._use_global_stats)


class BatchNorm(_BatchNormBase):
    """fluid.dygraph.BatchNorm-compatible (act fused on top)."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-5,
                 param_attr=None, bias_attr=None, dtype="float32",
                 data_layout="NCHW", in_place=False, use_global_stats=False,
                 **kw):
        super().__init__(num_channels, momentum=momentum, epsilon=epsilon,
                         weight_attr=param_attr, bias_attr=bias_attr,
                         data_format=data_layout,
                         use_global_stats=use_global_stats or None)
        self._act = act

    def forward(self, x):
        out = super().forward(x)
        if self._act:
            out = getattr(F, self._act)(out)
        return out


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCL", **kw):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, "NCL", **kw)


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCDHW", **kw):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, "NCDHW", **kw)


class SyncBatchNorm(_BatchNormBase):
    """Under dp sharding the batch-axis reduction is a cross-replica psum
    inserted by XLA — no NCCL sync op needed (ref: sync_batch_norm_op.cu)."""

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        out = layer
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, SyncBatchNorm):
            out = SyncBatchNorm(layer._num_features, layer._momentum,
                                layer._epsilon, data_format=layer._data_format)
            out.weight, out.bias = layer.weight, layer.bias
            out.register_buffer("_mean", layer._mean)
            out.register_buffer("_variance", layer._variance)
        for name, sub in list(layer._sub_layers.items()):
            layer._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return out


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                self._normalized_shape, attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(self._normalized_shape,
                                              attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.layer_norm(x, self._normalized_shape, self.weight, self.bias,
                            self._epsilon)


class RMSNorm(Layer):
    """Root-mean-square norm over the last axis with a learned weight (no
    shift), statistics in float32."""

    def __init__(self, hidden_size, epsilon=1e-6, weight_attr=None, name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            [hidden_size], attr=weight_attr,
            default_initializer=I.Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self._epsilon)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self._data_format = data_format
        self.weight = None if weight_attr is False else self.create_parameter(
            (num_channels,), attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            (num_channels,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self._num_groups, self.weight, self.bias,
                            self._epsilon, self._data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._epsilon = epsilon
        if weight_attr is False:
            self.weight = self.bias = None
        else:
            self.weight = self.create_parameter(
                (num_features,), attr=weight_attr,
                default_initializer=I.Constant(1.0))
            self.bias = self.create_parameter((num_features,), attr=bias_attr,
                                              is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias,
                               eps=self._epsilon)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0,
                 data_format="NCHW", name=None):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta, self.k)


class SpectralNorm(Layer):
    """Power-iteration spectral norm of a weight (ref: spectral_norm_op.cc)."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12,
                 dtype="float32"):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._eps = eps
        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        self.register_buffer("weight_u", Tensor(np.random.randn(h).astype(np.float32), _internal=False))
        self.register_buffer("weight_v", Tensor(np.random.randn(w).astype(np.float32), _internal=False))

    def forward(self, weight):
        from ...ops._base import apply, register

        @register("spectral_norm")
        def _sn(w, u, v, *, dim, power_iters, eps):
            mat = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
            for _ in range(power_iters):
                v = mat.T @ u
                v = v / (jnp.linalg.norm(v) + eps)
                u = mat @ v
                u = u / (jnp.linalg.norm(u) + eps)
            sigma = u @ mat @ v
            return w / sigma

        return apply("spectral_norm", weight, self.weight_u, self.weight_v,
                     dim=self._dim, power_iters=self._power_iters,
                     eps=self._eps)
