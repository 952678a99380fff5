"""The flash kernels through the real XLA:TPU + Mosaic compile, at the
benchmark's widths, for a ``v5e`` that is described and not attached: what
interpret mode cannot refuse (a block Mosaic cannot tile, more VMEM than a
kernel gets). Nothing runs; a compile that passes is not a chip run.

The topology is described inside a fixture, never at import (one process at
a time may load libtpu; see the on-chip-measurement guide), and every such
test lives in this one file.
"""
import importlib
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    # a compile for a described device is written to the persistent cache
    # and cannot be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("BH,Lq,Lk,D,dtype,causal", [
    (192, 1024, 1024, 64, jnp.bfloat16, True),    # the GPT cells, a chip
    (288, 512, 512, 64, jnp.bfloat16, False),     # BERT's shape (ROADMAP S3)
    (16, 4096, 4096, 128, jnp.bfloat16, True),    # blocks under the diagonal
    (8, 2048, 2048, 256, jnp.float32, False),     # the widest routed head
    (16, 128, 1152, 64, jnp.bfloat16, True),      # an offset, unequal blocks
], ids=["gpt2s", "bert", "L4096_D128", "f32_D256", "offset"])
def test_forward_and_backward_lower_for_v5e(one_chip, no_compile_cache, BH,
                                            Lq, Lk, D, dtype, causal):
    def x(L):
        return jax.ShapeDtypeStruct((1, BH, L, D), dtype, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal, None, None,
                                          False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        x(Lq), x(Lk), x(Lk)).compile().as_text()
    suffix = "_causal" if causal else ""
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        # the benchmark's reader takes [BH, L, D] of q and k from the first
        # two operands of the call's own line
        line = next(ln for ln in text.splitlines()
                    if re.search(rf"%\S*{name}{suffix}\S* = .*custom-call", ln))
        operands = line[line.index("operand_layout_constraints={"):]
        shapes = re.findall(r"\[([\d,]+)\]", operands)[:2]
        assert shapes == [f"{BH},{Lq},{D}", f"{BH},{Lk},{D}"], shapes
    # lse and delta cross HBM as compact rows
    assert f"f32[{BH},1,{Lq}]" in text
    assert f"f32[{BH},{Lq},1]" not in text


@pytest.mark.parametrize("BH,L,Dqk,Dv", [
    (32, 4096, 192, 128),     # latent attention without absorption, a chip
    (16, 1024, 64, 128),      # a value head wider than the query's
], ids=["mla_192_128", "64_128"])
def test_unequal_head_widths_lower_for_v5e(one_chip, no_compile_cache, BH, L,
                                           Dqk, Dv):
    def x(d):
        return jax.ShapeDtypeStruct((1, BH, L, d), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True, 0.1447, None,
                                          False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        x(Dqk), x(Dqk), x(Dv)).compile().as_text()
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        # the benchmark's reader takes q, k and v, in that order, from the
        # call's own line: the value head's width is the third operand's
        line = next(ln for ln in text.splitlines()
                    if re.search(rf"%\S*{name}_causal\S* = .*custom-call", ln))
        operands = line[line.index("operand_layout_constraints={"):]
        shapes = re.findall(r"\[([\d,]+)\]", operands)[:3]
        assert shapes == [f"{BH},{L},{Dqk}", f"{BH},{L},{Dqk}",
                          f"{BH},{L},{Dv}"], shapes
    assert f"bf16[{BH},{L},{Dv}]" in text      # o, dO, dV are Dv wide
    bq, bk, sub = fa.block_sizes(L, L, Dqk, 2, None, Dv)
    assert fa.vmem_bytes(bq, bk, sub, Dqk, 2, Dv) <= fa.VMEM_BUDGET


def test_grouped_expert_products_lower_for_v5e(one_chip, no_compile_cache):
    """``dist.moe``'s routed experts at the benchmark's widths: 16,384 slots
    sorted over 64 experts, the 8 held here computed by megablox's grouped
    products (a dynamic grid), forward and backward."""
    from paddle_tpu.dist import moe

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(xs, wg, wu, wd, sizes):
        out = moe._grouped_swiglu(xs, sizes, wg, wu, wd, 0, False)
        return jnp.sum(out.astype(jnp.float32))

    # Mosaic takes bf16 operands at one pass only; the tests' global
    # "highest" is for float32 references
    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.grad(loss, (0, 1, 2, 3))).lower(
            s((16384, 3584)), s((8, 3584, 1024)), s((8, 3584, 1024)),
            s((8, 1024, 3584)), s((64,), jnp.int32)).compile().as_text()
    calls = text.count('custom_call_target="tpu_custom_call"')
    assert calls >= 8, calls   # 3 forward, 3 to the rows, 3 to the weights
