"""paddle_tpu: a TPU-native deep-learning framework.

Re-designed from scratch for TPU (jax/XLA/pallas/pjit) with the API surface
and capabilities of the PaddlePaddle Fluid reference (gc1023/Paddle):
eager (dygraph) + static (Program/Executor) modes, nn layers, optimizers,
data pipeline, Mesh-based distributed training (dp/tp/pp/sp/ep), AMP,
checkpointing, inference, and a model zoo.
"""
from __future__ import annotations

import time as _time

_IMPORT_BEGAN = _time.perf_counter()  # first line to last: startup.import

__version__ = "0.1.0"
from . import version  # noqa: F401,E402

import os as _os
from .check_import_scipy import check_import_scipy  # noqa: E402

check_import_scipy(_os.name)

from .core import (
    Tensor,
    Parameter,
    no_grad,
    enable_grad,
    is_grad_enabled,
)
from .core.autograd import grad
from .core.tensor import to_tensor
from .core import dtype as _dtype_mod
from .core.dtype import (
    float16, bfloat16, float32, float64, int8, int16, int32, int64, uint8,
    bool_, complex64, complex128, set_default_dtype, get_default_dtype,
)
from .core.device import (
    set_device, get_device, device_count, is_compiled_with_tpu,
    TPUPlace, CPUPlace, CUDAPlace, Place, set_compilation_cache,
)
from .core.random import seed

# ops: import attaches Tensor methods, then re-export the functional API
from . import ops
from .ops.creation import (
    zeros, ones, full, empty, zeros_like, ones_like, full_like, empty_like,
    arange, linspace, logspace, eye, tril, triu, meshgrid, diagflat, assign,
    clone, rand, randn, randint, randperm, uniform, normal, bernoulli,
    multinomial, standard_normal, fill_constant,
)
from .ops.math import (
    add, subtract, multiply, divide, floor_divide, remainder, mod, pow,
    matmul, mm, bmm, dot, outer, inner, scale, clip, add_n, cumsum, cumprod,
    lerp, einsum, kron, trace, diag, diagonal, nan_to_num, stanh, exp, expm1,
    log, log2, log10, log1p, sqrt, rsqrt, abs, neg, floor, ceil, round, trunc,
    sin, cos, tan, asin, acos, atan, sinh, cosh, asinh, acosh, atanh, erf,
    erfinv, sign, reciprocal, square, digamma, lgamma, isnan, isinf, isfinite,
    maximum, minimum, atan2, logaddexp, increment, mul,
)
from .ops.reduction import (
    sum, mean, max, min, prod, all, any, logsumexp, argmax, argmin, std, var,
    median, quantile, kthvalue, mode as mode_op, count_nonzero, nansum,
    nanmean, amax, amin,
)
from .ops.manipulation import (
    reshape, transpose, t, flatten, squeeze, unsqueeze, concat, stack, split,
    chunk, unbind, slice, strided_slice, gather, gather_nd, take_along_axis,
    index_select, index_sample, scatter, scatter_nd, scatter_nd_add,
    put_along_axis, tile, expand, broadcast_to, expand_as, repeat_interleave,
    flip, roll, pad, where, topk, sort, argsort, one_hot, cast, nonzero,
    masked_select, unique, masked_fill, bincount, moveaxis, swapaxes, rot90,
    shard_index, as_real, as_complex,
)
from .ops.compare import (
    equal, not_equal, less_than, less_equal, greater_than, greater_equal,
    logical_and, logical_or, logical_xor, logical_not, bitwise_and,
    bitwise_or, bitwise_xor, bitwise_not, isclose, allclose, equal_all,
    is_empty, is_tensor,
)
from .ops.activation import tanh  # noqa: F401  (others live in nn.functional)
from .ops.linalg import (
    norm, dist, cholesky, inverse, matrix_power, pinv, svd, qr, eig, eigh,
    eigvals, eigvalsh, matrix_rank, det, slogdet, cross, triangular_solve,
    cholesky_solve, solve, lstsq, histogram, mv, multi_dot, cov, corrcoef,
)
from .ops.control_flow import cond, while_loop, case, switch_case, scan

from . import nn
from . import optim
from . import amp
from . import metrics
from . import distribution
from . import static_
from . import framework
from . import resilience
from . import obs
from . import runtime
from . import inference
from . import serving
from . import quant
from . import slim
from . import hapi
from . import dataset
from . import vision
from . import fluid
from .hapi import Model
from .io_.dataloader import DataLoader  # noqa: F401  (paddle.DataLoader)
# NB: ``paddle_tpu.dist`` is the p-norm distance op (paddle parity);
# the distributed package binds as ``paddle_tpu.distributed`` — that
# alias, and the rest of the 2.x module surface (paddle.tensor, .io,
# .metric, .optimizer, .static, .device, .fleet, .imperative,
# .regularizer), are bound by modules_compat.install() at the bottom
# of this file so the alias table lives in ONE place.
from . import sysconfig  # noqa: E402


def summary(net, input_size, dtypes="float32"):
    """Per-layer param/FLOP table (2.x ``paddle.summary`` shape; built
    on utils.stats.summary — forward hooks over a sample run)."""
    from .utils.stats import summary as _s

    return _s(net, input_size, dtypes=dtypes)


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Total forward FLOPs (2.x ``paddle.flops``); ``custom_ops`` maps
    LayerClass -> fn(layer, in_shape, out_shape) for user layers."""
    from .utils.stats import summary as _s

    return _s(net, input_size, print_table=print_detail,
              custom_ops=custom_ops)["total_flops"]
# the submodule import rebinds the package attr 'dist' to the module;
# restore the function for paddle.dist parity
from .ops.linalg import dist  # noqa: E402,F811
from .framework import jit as _jit_mod
from .framework.jit import jit, to_static, TrainStep
from .framework.recompute import recompute, Recompute
from .framework.io import save, load
from .static_ import enable_static, disable_static
from .static_.program import program_guard, global_scope


def in_dynamic_mode():
    return not static.in_static_mode()
from .optim import regularizer
from .nn.param_attr import ParamAttr
from .utils import unique_name

bool = bool_  # paddle.bool

__all__ = [n for n in dir() if not n.startswith("_")]

# reader-creator combinators + batching (ref: paddle/reader, batch.py)
from . import reader  # noqa: E402
from . import compat  # noqa: E402
from .reader import batch  # noqa: E402

# 1.x tensor-API aliases (ref: python/paddle/tensor/math.py __all__)
div = ops.divide
elementwise_equal = ops.equal
elementwise_sum = ops.add_n


def create_tensor(dtype, name=None, persistable=False):
    """ref: tensor/creation.py create_tensor."""
    return ops.zeros([1], dtype=dtype)


__all__ += ["reader", "compat", "batch", "div", "elementwise_equal",
            "elementwise_sum", "create_tensor"]

# 2.x module surface (paddle.tensor/io/metric/optimizer/distributed/
# fleet/imperative/static/device/regularizer): attribute binds + the
# module-import spellings (import paddle_tpu.tensor, python -m
# paddle_tpu.distributed.launch, ...) — registered last so every
# implementation module they alias already exists.
from . import modules_compat as _modules_compat  # noqa: E402

_modules_compat.install(__name__)

# the package's own import as a phase record (obs.trace: written after the
# fact, since the module that holds the ring is imported in between)
obs.trace.record("startup.import", _IMPORT_BEGAN, _time.perf_counter())
