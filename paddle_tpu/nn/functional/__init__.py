"""paddle_tpu.nn.functional — the functional API surface.

Mirrors ``paddle.nn.functional`` (ref: python/paddle/nn/functional/ +
fluid/layers/{nn,loss}.py), aggregating the op library plus nn-specific
functionals (linear, embedding, losses, attention).
"""
from ...ops.activation import (  # noqa: F401
    relu, relu6, sigmoid, tanh, softmax, log_softmax, gelu, leaky_relu, elu,
    celu, selu, prelu, hardtanh, hardshrink, softshrink, thresholded_relu,
    softplus, softsign, silu, swish, mish, hardswish, hardsigmoid, tanhshrink,
    log_sigmoid, gumbel_softmax, maxout, glu,
)
from ...ops.conv import (  # noqa: F401
    conv1d, conv2d, conv3d, conv1d_transpose, conv2d_transpose,
    conv3d_transpose,
    max_pool1d, max_pool2d,
    max_pool3d, avg_pool1d, avg_pool2d, avg_pool3d, adaptive_avg_pool1d,
    adaptive_avg_pool2d, adaptive_max_pool1d, adaptive_max_pool2d,
    interpolate, pixel_shuffle, unfold,
    grid_sample, affine_grid,  # 2.x paddle.nn.functional homes
)
from ...ops.norm_ops import (  # noqa: F401
    batch_norm, layer_norm, group_norm, instance_norm, normalize,
    local_response_norm, rms_norm,
)
from ...ops.random_ops import (  # noqa: F401
    dropout, dropout2d, dropout3d, alpha_dropout, channel_shuffle,
)
from ...ops.manipulation import pad  # noqa: F401
from ...ops.sequence import sequence_mask  # noqa: F401
from .common import (  # noqa: F401
    linear, embedding, one_hot, cosine_similarity, pairwise_distance,
    label_smooth, bilinear,
)
from .loss import (  # noqa: F401
    cross_entropy, softmax_with_cross_entropy, nll_loss, kl_div,
    binary_cross_entropy, binary_cross_entropy_with_logits, mse_loss, l1_loss,
    smooth_l1_loss, margin_ranking_loss, cosine_embedding_loss, ctc_loss,
    square_error_cost, log_loss, sigmoid_focal_loss, hinge_embedding_loss,
    triplet_margin_loss, npair_loss,
)
from .attention import scaled_dot_product_attention, sdpa_bhld  # noqa: F401
from .decoder import (  # noqa: F401
    rotary, rotary_cos_sin, yarn_inv_freq, yarn_mscale, swiglu, hc_maps,
    hc_read, hc_mix,
)
from .linear_attention import (  # noqa: F401
    short_conv, kda_gate, kda_chunk, gated_rms_norm,
)
from .state_space import ssm_gate, ssm_chunk  # noqa: F401
from .gated_conv import gated_short_conv  # noqa: F401

upsample = interpolate

__all__ = [n for n in dir() if not n.startswith("_")]
