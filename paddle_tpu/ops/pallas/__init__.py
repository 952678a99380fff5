"""Pallas TPU kernels (SURVEY §2.39).

The reference ships ~500 hand-written CUDA kernels under
paddle/fluid/operators; on TPU, XLA fusion covers most of them, and these
pallas kernels cover the rest — the memory-bound fusions XLA can't do:

- flash_attention: O(L)-memory blocked attention (fwd + custom_vjp bwd);
  window_attention: the same bodies over a causal sliding window's band;
  a recomputed block keeps the forward's outputs and runs the forward once
- fused_layer_norm: one-pass moments+normalize (+ fused bwd)
- softmax_cross_entropy: LM-head CE without materializing softmax
- hyper_connection: the multi-stream residual's passes over its streams
  (``hc_norm_proj``, ``hc_read``, ``hc_mix``), forward and backward by
  hand: each reads the bf16 streams once and keeps float32 in registers
- rotary: the rotary embedding (``rope``), one pass over the heads forward
  and the same pass over the cotangent backward, float32 in registers
- ssm_scan: the chunked state-space recurrence (``ssm_chunk``), a chunk of
  every head a grid step with the carried state in VMEM, forward and
  backward by hand, float32 products as bfloat16 limbs
- paged_decode_attention: ragged paged decode attention for the
  serving path (K/V gathered through per-sequence page tables via
  scalar prefetch — see paddle_tpu.serving)

Which calls a training kernel takes is its own module's to say
(``flash_route``, ``softmax_ce_route``, ``layer_norm_route``, ``hc_route``,
``rotary_route``, ``ssm_scan_route``, beside the block rules that impose
them): the operands' specs, or ``None`` for the dense path of the op that asked (the dense jnp paths remain the reference
implementations and the CPU test oracle). The routes read :func:`enabled`
(a TPU backend, or a test's ``set_enabled``); :func:`run` makes the call,
in the interpreter where the backend is the host CPU.

Under a device mesh (``dist.env.get_mesh()``) the first three training
kernels run through :func:`mesh_call` (the residual's, the rotation's and
the scan's stay dense there): Mosaic kernels cannot be
partitioned by GSPMD, so each call is wrapped in one full-manual
``jax.shard_map`` whose specs :func:`shard_spec` derives from the
kernel's parallel dims — rows/batch over the ``data`` axis, heads over
the ``model`` axis. All three are row- or head-parallel, so the body
needs no collective; GSPMD reshards at the boundary.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from .flash_attention import (flash_attention, flash_route,
                              window_attention)
from .layernorm import fused_layer_norm, layer_norm_route
from .softmax_ce import softmax_ce_route, softmax_cross_entropy
from .hyper_connection import hc_mix, hc_norm_proj, hc_read, hc_route
from .rotary import rope, rotary_route
from .ssm_scan import ssm_scan, ssm_scan_route
from .paged_attention import dense_decode_reference, paged_decode_attention

__all__ = ["flash_attention", "window_attention", "fused_layer_norm", "softmax_cross_entropy",
           "paged_decode_attention", "dense_decode_reference",
           "hc_norm_proj", "hc_read", "hc_mix", "rope", "ssm_scan",
           "flash_route", "layer_norm_route", "softmax_ce_route", "hc_route",
           "rotary_route", "ssm_scan_route",
           "run",
           "enabled", "set_enabled", "auto_interpret", "shard_spec",
           "mesh_call", "BATCH", "HEADS", "ROWS"]

_FORCED = None  # None: by backend (TPU only); True/False: forced by a test


def set_enabled(value):
    """Force pallas kernels on/off (None restores platform auto-detect)."""
    global _FORCED
    _FORCED = value


def enabled():
    if _FORCED is not None:
        return _FORCED
    return jax.default_backend() == "tpu"


def auto_interpret():
    """Interpret mode only where the backend IS the host CPU (the test
    oracle for the wired call sites). Any accelerator compiles the
    kernels through Mosaic; a backend that cannot be found raises."""
    return jax.default_backend() == "cpu"


# -- kernels under a mesh ------------------------------------------------------
# Candidate mesh axes per parallel dim, by the repo's axis convention
# (dist/parallel.py batch_axis="data", dist/tp_layers.py mp_axis="model").
BATCH = ("data",)           # leading batch dim of an activation
HEADS = ("model",)          # attention heads under tensor parallelism
ROWS = ("data", "model")    # independent rows with no model-sharded dim


def _kernel_mesh():
    """The mesh a kernel call must be wrapped over, or ``None`` when the
    call already sees per-device shapes (no mesh, one device, or the
    body of a full-manual ``shard_map``)."""
    from ...dist.env import get_mesh

    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return None
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    if not manual:
        return mesh
    if manual == set(mesh.axis_names):
        return None
    raise NotImplementedError(
        "pallas kernel inside a partial-manual shard_map (manual axes "
        f"{sorted(manual)} of {mesh.axis_names}): Mosaic needs every "
        "mesh axis manual. Turn the kernels off for this program with "
        "ops.pallas.set_enabled(False).")


def shard_spec(shape, roles):
    """``(spec, local_shape)`` for one kernel operand under the active
    mesh: ``roles`` maps a dim to its candidate axes (``BATCH`` /
    ``HEADS`` / ``ROWS``); each dim takes the candidates that exist
    with size > 1 and divide it. With no mesh to wrap over every entry
    is None and the shape is returned as is. Call sites test kernel
    eligibility on ``local_shape``, the block a device actually sees."""
    mesh = _kernel_mesh()
    if mesh is None:
        return P(*(None,) * len(shape)), tuple(shape)
    spec, local = [], list(shape)
    for d in range(len(shape)):
        axes = []
        for a in roles.get(d, ()):
            size = mesh.shape.get(a, 1)
            if size > 1 and local[d] % size == 0:
                axes.append(a)
                local[d] //= size
        spec.append(tuple(axes) if len(axes) > 1
                    else (axes[0] if axes else None))
    return P(*spec), tuple(local)


def run(kernel, specs, args, *static):
    """``kernel(*args, *static, interpret)`` under :func:`mesh_call`, for a
    call its module's route took (``specs`` is what the route returned). The
    one place that chooses the interpreter."""
    interpret = auto_interpret()
    return mesh_call(lambda *a: kernel(*a, *static, interpret), args, *specs)


def mesh_call(fn, args, in_specs, out_specs):
    """``fn(*args)`` as one full-manual ``shard_map`` over the active
    mesh (specs from :func:`shard_spec`); a plain call when there is no
    mesh to wrap over. ``check_vma`` is off because ``pallas_call``
    outputs carry no varying-axes type."""
    mesh = _kernel_mesh()
    if mesh is None:
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=out_specs, check_vma=False)(*args)
