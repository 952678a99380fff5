"""Common layers (ref: python/paddle/nn/layer/common.py and
fluid/layers/nn.py fc/embedding/dropout/pad/...).
"""
from __future__ import annotations

from ... import ops
from ...ops.manipulation import pad as _pad_op, flatten as _flatten
from .. import functional as F
from ..layer import Layer
from ..param_attr import ParamAttr
from .. import initializer as I

__all__ = [
    "Identity", "Linear", "SwiGLU", "Dropout", "Dropout2D", "Dropout3D", "AlphaDropout",
    "Embedding", "Flatten", "Pad1D", "Pad2D", "Pad3D", "ZeroPad2D",
    "Upsample", "UpsamplingNearest2D", "UpsamplingBilinear2D",
    "CosineSimilarity", "PairwiseDistance", "Bilinear", "Unfold",
    "PixelShuffle", "ChannelShuffle",
]


class Identity(Layer):
    def forward(self, x):
        return x


class Linear(Layer):
    """ref: fc / mul_op; weight stored (in, out) so x@W hits the MXU directly."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr)
        self.bias = self.create_parameter(
            (out_features,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.linear(x, self.weight, self.bias)

    def extra_repr(self):
        return f"in={self.weight.shape[0]}, out={self.weight.shape[1]}"


class SwiGLU(Layer):
    """Gated MLP (Shazeer 2020): ``(silu(x W_gate) * (x W_up)) W_down``,
    no biases."""

    def __init__(self, hidden_size, intermediate_size, weight_attr=None,
                 down_attr=None, name=None):
        super().__init__()
        self.gate = Linear(hidden_size, intermediate_size,
                           weight_attr=weight_attr, bias_attr=False)
        self.up = Linear(hidden_size, intermediate_size,
                         weight_attr=weight_attr, bias_attr=False)
        self.down = Linear(intermediate_size, hidden_size,
                           weight_attr=down_attr or weight_attr,
                           bias_attr=False)

    def forward(self, x):
        return self.down(F.swiglu(self.gate(x), self.up(x)))


class Dropout(Layer):
    def __init__(self, p=0.5, axis=None, mode="upscale_in_train", name=None):
        super().__init__()
        self.p, self.axis, self.mode = p, axis, mode

    def forward(self, x):
        return F.dropout(x, p=self.p, axis=self.axis, training=self.training,
                         mode=self.mode)


class Dropout2D(Layer):
    def __init__(self, p=0.5, data_format="NCHW", name=None):
        super().__init__()
        self.p, self.data_format = p, data_format

    def forward(self, x):
        return F.dropout2d(x, p=self.p, training=self.training,
                           data_format=self.data_format)


class Dropout3D(Layer):
    def __init__(self, p=0.5, data_format="NCDHW", name=None):
        super().__init__()
        self.p, self.data_format = p, data_format

    def forward(self, x):
        return F.dropout3d(x, p=self.p, training=self.training,
                           data_format=self.data_format)


class AlphaDropout(Layer):
    def __init__(self, p=0.5, name=None):
        super().__init__()
        self.p = p

    def forward(self, x):
        return F.alpha_dropout(x, p=self.p, training=self.training)


class Embedding(Layer):
    """ref: lookup_table_op."""

    def __init__(self, num_embeddings, embedding_dim, padding_idx=None,
                 sparse=False, weight_attr=None, name=None):
        super().__init__()
        self._padding_idx = padding_idx if padding_idx is None or padding_idx >= 0 \
            else num_embeddings + padding_idx
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=I.Normal(0.0, 1.0))
        if self._padding_idx is not None:
            import jax.numpy as jnp

            self.weight._replace(self.weight._data.at[self._padding_idx].set(0.0))

    def forward(self, x):
        return F.embedding(x, self.weight, padding_idx=self._padding_idx)


class Flatten(Layer):
    def __init__(self, start_axis=1, stop_axis=-1):
        super().__init__()
        self.start_axis, self.stop_axis = start_axis, stop_axis

    def forward(self, x):
        return _flatten(x, self.start_axis, self.stop_axis)


class _PadNd(Layer):
    nsp = 2

    def __init__(self, padding, mode="constant", value=0.0,
                 data_format=None, name=None):
        super().__init__()
        self.padding = padding
        self.mode = mode
        self.value = value
        self.data_format = data_format

    def forward(self, x):
        return _pad_op(x, self.padding, mode=self.mode, value=self.value,
                       data_format=self.data_format or "NC" + "DHW"[-self.nsp:])


class Pad1D(_PadNd):
    nsp = 1


class Pad2D(_PadNd):
    nsp = 2


class Pad3D(_PadNd):
    nsp = 3


class ZeroPad2D(Pad2D):
    def __init__(self, padding, data_format="NCHW", name=None):
        super().__init__(padding, mode="constant", value=0.0,
                         data_format=data_format)


class Upsample(Layer):
    def __init__(self, size=None, scale_factor=None, mode="nearest",
                 align_corners=False, align_mode=0, data_format="NCHW",
                 name=None):
        super().__init__()
        self.size, self.scale_factor = size, scale_factor
        self.mode, self.align_corners = mode, align_corners
        self.data_format = data_format

    def forward(self, x):
        return F.interpolate(x, size=self.size, scale_factor=self.scale_factor,
                             mode=self.mode, align_corners=self.align_corners,
                             data_format=self.data_format)


class UpsamplingNearest2D(Upsample):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW", name=None):
        super().__init__(size=size, scale_factor=scale_factor, mode="nearest",
                         data_format=data_format)


class UpsamplingBilinear2D(Upsample):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW", name=None):
        super().__init__(size=size, scale_factor=scale_factor, mode="bilinear",
                         align_corners=True, data_format=data_format)


class CosineSimilarity(Layer):
    def __init__(self, axis=1, eps=1e-8):
        super().__init__()
        self.axis, self.eps = axis, eps

    def forward(self, x1, x2):
        return F.cosine_similarity(x1, x2, axis=self.axis, eps=self.eps)


class PairwiseDistance(Layer):
    def __init__(self, p=2.0, epsilon=1e-6, keepdim=False, name=None):
        super().__init__()
        self.p, self.epsilon, self.keepdim = p, epsilon, keepdim

    def forward(self, x, y):
        return F.pairwise_distance(x, y, p=self.p, epsilon=self.epsilon,
                                   keepdim=self.keepdim)


class Bilinear(Layer):
    def __init__(self, in1_features, in2_features, out_features,
                 weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            (out_features, in1_features, in2_features), attr=weight_attr)
        self.bias = self.create_parameter((1, out_features), attr=bias_attr,
                                          is_bias=True)

    def forward(self, x1, x2):
        return F.bilinear(x1, x2, self.weight, self.bias)


class Unfold(Layer):
    def __init__(self, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
        super().__init__()
        self.kernel_sizes, self.strides = kernel_sizes, strides
        self.paddings, self.dilations = paddings, dilations

    def forward(self, x):
        return F.unfold(x, self.kernel_sizes, self.strides, self.paddings,
                        self.dilations)


class PixelShuffle(Layer):
    def __init__(self, upscale_factor, data_format="NCHW", name=None):
        super().__init__()
        self.upscale_factor = upscale_factor

    def forward(self, x):
        return F.pixel_shuffle(x, self.upscale_factor)


class ChannelShuffle(Layer):
    def __init__(self, groups, data_format="NCHW", name=None):
        super().__init__()
        self.groups = groups

    def forward(self, x):
        return F.channel_shuffle(x, self.groups)
