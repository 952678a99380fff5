"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

TPU-native analog of the reference's section/pipeline training in Fleet
(pipeline_optimizer): stage parameters live stacked on a leading axis
sharded over the 'pipe' mesh axis; one shard_map program runs the whole
schedule, rotating activations ring-wise with ppermute each tick. The
schedule (M microbatches, S stages → M+S-1 ticks) is a lax.scan, so
forward AND the autodiff'd backward compile into a single XLA while-loop —
no per-stage host orchestration like the reference's section executor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from .env import get_mesh

__all__ = ["pipeline_forward", "PipelineStage", "gpipe_inner"]

# jitted partial-manual schedules, keyed on (stage_fn, mesh, axes,
# microbatches, param tree/shapes, input aval) — see pipeline_forward.
# Bounded LRU: entries strongly reference stage_fn (usually a bound
# method pinning a whole model) plus its executables, so evict oldest.
from collections import OrderedDict

_partial_manual_cache: OrderedDict = OrderedDict()
_PARTIAL_MANUAL_CACHE_MAX = 16


def gpipe_inner(stage_fn, stage_params, x_mb, axis_name):
    """Per-shard GPipe loop. Call inside shard_map over ``axis_name``.

    stage_fn(params, x) -> y: one stage's computation (same structure for
    every stage — the usual homogeneous-transformer-block case).
    stage_params: this shard's stage parameters (pytree; leading stage axis
    already stripped by shard_map).
    x_mb: (M, ...) microbatches — only stage 0's copy is consumed.
    Returns (M, ...) outputs — meaningful on the LAST stage (replicated out
    by the caller if needed).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = x_mb.shape[0]
    total = M + n - 1
    perm = [(i, (i + 1) % n) for i in range(n)]

    y0 = jax.eval_shape(lambda p, x: stage_fn(p, x), stage_params,
                        jax.eval_shape(lambda a: a[0], x_mb))
    out_buf = jnp.zeros((M,) + y0.shape, y0.dtype)
    carry_act = jnp.zeros(y0.shape, y0.dtype)  # activation arriving from left

    def tick(state, t):
        carry, outs = state
        # stage 0 injects microbatch t; other stages consume the carry
        mb_idx = jnp.clip(t - idx, 0, M - 1)
        x_in = jnp.where(idx == 0,
                         jax.lax.dynamic_index_in_dim(x_mb, mb_idx, 0,
                                                      keepdims=False),
                         carry)
        y = stage_fn(stage_params, x_in)
        # last stage writes result for microbatch (t - n + 1)
        out_idx = jnp.clip(t - (n - 1), 0, M - 1)
        valid = (idx == n - 1) & (t >= n - 1) & (t - (n - 1) < M)
        outs = jnp.where(
            valid,
            jax.lax.dynamic_update_index_in_dim(outs, y, out_idx, 0),
            outs)
        carry_next = jax.lax.ppermute(y, axis_name, perm)
        return (carry_next, outs), None

    (carry, outs), _ = jax.lax.scan(tick, (carry_act, out_buf),
                                    jnp.arange(total))
    # replicate the last stage's results to every shard so the caller can
    # use out_specs=P() (grads of the loss then flow back through the ring)
    outs = jax.lax.psum(
        jnp.where(idx == n - 1, outs, jnp.zeros_like(outs)), axis_name)
    return outs


def pipeline_forward(stage_fn, stacked_params, x, num_microbatches,
                     axis_name="pipe", mesh=None, batch_axis=None):
    """Run x (batch-major) through the pipeline; returns last-stage output.

    stacked_params: pytree whose leaves have leading dim = n_layers, a
    multiple of the ``axis_name`` mesh size (each stage applies its
    n_layers/n_stages resident layers in order — the usual
    layers-per-stage grouping). x: (B, ...) split into M microbatches.
    ``batch_axis``: optional dp mesh axis; microbatches are then sharded
    over it so dp x pp runs in one shard_map.
    """
    mesh = mesh or get_mesh()
    n = mesh.shape[axis_name]
    B = x.shape[0]
    M = num_microbatches
    assert B % M == 0, "batch must divide into microbatches"
    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    assert n_layers % n == 0, \
        f"{n_layers} stacked layers not divisible by {n} pipeline stages"
    if batch_axis:
        dp = mesh.shape[batch_axis]
        assert (B // M) % dp == 0, \
            f"microbatch size {B // M} not divisible by " \
            f"{batch_axis} mesh size {dp}"

    arr = x._data if isinstance(x, Tensor) else jnp.asarray(x)
    mb = arr.reshape((M, B // M) + arr.shape[1:])

    def local_stage(params, x):
        # apply this shard's resident layers (leading dim n_layers/n)
        for i in range(n_layers // n):
            p_i = jax.tree_util.tree_map(lambda a: a[i], params)
            x = stage_fn(p_i, x)
        return x

    def shard_fn(params, xs):
        return gpipe_inner(local_stage, params, xs, axis_name)

    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    xspec = P(None, batch_axis) if batch_axis else P()
    # manual only over the pipe (+ dp batch) axes: any OTHER mesh axis
    # (e.g. the 'model' tensor-parallel axis) stays automatic, so GSPMD
    # keeps honoring the TP layers' sharding constraints INSIDE each
    # stage — this is what composes dp x tp x pp into one executable
    manual = frozenset({axis_name} | ({batch_axis} if batch_axis else set()))
    if manual != frozenset(mesh.axis_names):
        # partial-manual + check_vma=False hits a jax-0.9 bug in the
        # EAGER dispatch path (_unmatch builds a dst spec over ALL mesh
        # axes); under jit the rearrangement never runs, so compile the
        # call — inside an outer trace this just inlines. Cached so
        # repeated eager calls (e.g. batched eval) don't retrace.
        leaves, treedef = jax.tree_util.tree_flatten(stacked_params)
        key = (stage_fn, mesh, axis_name, batch_axis, M, treedef,
               tuple((l.shape, str(l.dtype)) for l in leaves),
               mb.shape, str(mb.dtype))
        sm_fn = _partial_manual_cache.get(key)
        if sm_fn is None:
            sm_fn = jax.jit(jax.shard_map(
                shard_fn, mesh=mesh,
                in_specs=(pspec, xspec), out_specs=xspec,
                axis_names=manual, check_vma=False))
            _partial_manual_cache[key] = sm_fn
            while len(_partial_manual_cache) > _PARTIAL_MANUAL_CACHE_MAX:
                _partial_manual_cache.popitem(last=False)
        else:
            _partial_manual_cache.move_to_end(key)
    else:
        sm_fn = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(pspec, xspec), out_specs=xspec,
            axis_names=manual, check_vma=False)
    out = sm_fn(stacked_params, mb)
    out = out.reshape((B,) + out.shape[2:])
    return Tensor(out, _internal=True) if isinstance(x, Tensor) else out


class PipelineStage:
    """Helper bundling a stage callable + stacked params for the schedule."""

    def __init__(self, stage_fn, stacked_params, num_microbatches=4,
                 axis_name="pipe"):
        self.stage_fn = stage_fn
        self.stacked_params = stacked_params
        self.num_microbatches = num_microbatches
        self.axis_name = axis_name

    def __call__(self, x):
        return pipeline_forward(self.stage_fn, self.stacked_params, x,
                                self.num_microbatches, self.axis_name)
