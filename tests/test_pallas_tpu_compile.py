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
        return jnp.sum(fa.flash_attention(q, k, v, None, causal, None, None,
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


@pytest.mark.parametrize("BH,L,D,window", [
    (72, 8192, 128, 512),     # a sliding layer of the laguna cell, a chip
    (8, 1024, 64, 384),       # a window that is no multiple of the block
], ids=["laguna_w512", "w384"])
def test_windowed_kernels_lower_for_v5e(one_chip, no_compile_cache, BH, L, D,
                                        window):
    x = jax.ShapeDtypeStruct((1, BH, L, D), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.window_attention(q, k, v, window, None, None,
                                           False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).compile() \
        .as_text()
    for name in ("swa_fwd", "swa_bwd_dq", "swa_bwd_dkv"):
        # ``benchmark/window_costs.py`` takes the window from the name and
        # [BH, L, D] of q from the call's own line
        line = next(ln for ln in text.splitlines() if re.search(
            rf"%{name}_w{window}\S* = .*custom-call", ln))
        operands = line[line.index("operand_layout_constraints={"):]
        assert re.findall(r"\[([\d,]+)\]", operands)[:3] == \
            [f"{BH},{L},{D}"] * 3
    assert not re.search(r"%flash_\w+ = ", text)   # no causal kernel beside


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
        return jnp.sum(fa.flash_attention(q, k, v, None, True, 0.1447, None,
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


@pytest.mark.parametrize("B,H,L,rows", [
    (24, 12, 512, 24),      # bert_base_mlm_512's call: a row a batch row
    (24, 12, 512, 1),       # one row for every batch row
    (2, 4, 2048, 2),        # several k blocks stream their part of the row
], ids=["bert512", "one_row", "L2048"])
def test_key_bias_lowers_for_v5e(one_chip, no_compile_cache, B, H, L, rows):
    """The three kernels with the padding mask as their key bias: the
    operand comes after q, k, v (the benchmark's reader takes those by
    position) under the names the unbiased calls have."""
    def x(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, bias):
        return jnp.sum(fa.flash_attention(q, k, v, bias, False, None, None,
                                          False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        *[x((B, H, L, 64))] * 3, x((rows, 1, L), jnp.float32)
    ).compile().as_text()
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        line = next(ln for ln in text.splitlines()
                    if re.search(rf"%\S*{name}\.?\d* = .*custom-call", ln))
        operands = line[line.index("operand_layout_constraints={"):]
        shapes = re.findall(r"(\w+)\[([\d,]+)\]", operands)[:4]
        assert shapes == [("bf16", f"{B * H},{L},64")] * 3 + \
            [("f32", f"{rows},1,{L}")], shapes


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


@pytest.mark.parametrize("rows,hidden,width,held", [
    (2048, 4096, 1280, 8),      # solar2_pretrain_tp8_ep40's window
    (8192, 2048, 768, 16),      # joyai_pretrain_mtp_ep16's
    (4096, 3584, 1024, 8),      # xing4_pretrain_ep8's
], ids=["solar2", "joyai", "xing4"])
def test_a_windows_products_lower_for_v5e(one_chip, no_compile_cache,
                                          monkeypatch, rows, hidden, width,
                                          held):
    """One pass of ``dist.moe``'s window loop at the three cells' shapes: a
    grouped product into the experts' width, one back with the weights
    transposed, and the weights' gradient added into what earlier passes
    gave (megablox's ``existing_out``: one more block in fast memory)."""
    from paddle_tpu.dist import moe
    from paddle_tpu.ops import pallas as pk

    monkeypatch.setattr(pk, "enabled", lambda: True)
    monkeypatch.setattr(pk, "auto_interpret", lambda: False)

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def one_pass(xs, w, sizes, into):
        product, product_t = moe._grouped_products(rows, xs.dtype)
        up = product(xs, w, sizes)
        return product(up, w, sizes, True), product_t(xs, up, sizes, into)

    with jax.default_matmul_precision("default"):
        text = jax.jit(one_pass, donate_argnums=3).lower(
            s((rows, hidden)), s((held, hidden, width)),
            s((held,), jnp.int32), s((held, hidden, width))
        ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert moe._gmm_tiling(rows, hidden, width)[0] == moe.ROW_TILE


ce = importlib.import_module("paddle_tpu.ops.pallas.softmax_ce")


@pytest.mark.parametrize("N,V,dtype,kind", [
    (16384, 50304, jnp.bfloat16, "bf16"),   # the GPT cells, a chip: a tail
    (4096, 16384, jnp.bfloat16, "bf16"),    # xing4's slice: bv divides V
    (2048, 50304, jnp.float32, "f32"),      # float32 logits halve the block
], ids=["gpt2s", "xing4", "f32"])
def test_softmax_ce_lowers_for_v5e(one_chip, no_compile_cache, N, V, dtype,
                                   kind):
    x = jax.ShapeDtypeStruct((N, V), dtype, sharding=one_chip)
    y = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)

    def loss(x, y):
        return jnp.sum(ce.softmax_cross_entropy(x, y, -100, False))

    text = jax.jit(jax.grad(loss)).lower(x, y).compile().as_text()
    for name, result in (("softmax_ce_fwd", f"f32[{N},1]"),
                         ("softmax_ce_bwd", f"{kind}[{N},{V}]")):
        # the benchmark's reader counts the bytes of the operands and results
        # on the call's own line: the logits come first and are not padded,
        # and the backward's result has their shape and dtype
        line = next(ln for ln in text.splitlines()
                    if re.search(rf"%\S*{name}\S* = .*custom-call", ln))
        operands = line[line.index("operand_layout_constraints={"):]
        assert operands.startswith(
            f"operand_layout_constraints={{{kind}[{N},{V}]"), operands[:80]
        assert result in line.partition(" custom-call(")[0], line[:200]
    bn, bv = ce.block_sizes(N, V, jnp.dtype(dtype).itemsize)
    assert ce.vmem_bytes(bn, bv, jnp.dtype(dtype).itemsize) <= ce.VMEM_BUDGET


def test_softmax_ce_block_rule_counts_grid_steps():
    """The rule as a count, not a speed: a vocabulary block need not divide
    V, so GPT-2's 50,304 columns take 2,000 grid steps a call at most (25,152
    when the block had to divide), and where 2048 divided V already the blocks
    are what they were."""
    bn, bv = ce.block_sizes(16384, 50304, 2)
    assert 16384 % bn == 0 and bv % 128 == 0
    assert (16384 // bn) * -(-50304 // bv) <= 2000
    assert ce.block_sizes(4096, 16384, 2) == (256, 2048)
    # float32 logits: a narrower block, still whole lanes, inside the budget
    bn, bv = ce.block_sizes(2048, 50304, 4)
    assert bv % 128 == 0 and ce.vmem_bytes(bn, bv, 4) <= ce.VMEM_BUDGET
    # a vocabulary narrower than the target is one block; rows keep dividing
    assert ce.block_sizes(24, 384, 2) == (24, 384)


@pytest.mark.parametrize("n,tokens,c,phi_dtype", [
    (4, 4096, 3584, jnp.bfloat16),      # xing4_pretrain_ep8's streams
    (2, 512, 256, jnp.float32),         # float32 phi: three limbs a side
], ids=["xing4", "n2_f32_phi"])
def test_hyper_connection_kernels_lower_for_v5e(one_chip, no_compile_cache, n,
                                                tokens, c, phi_dtype):
    """The multi-stream residual's five kernels, forward and backward of a
    sublayer's three ops, at the tiles the rule gives them."""
    hc = importlib.import_module("paddle_tpu.ops.pallas.hyper_connection")
    k = 2 * n + n * n

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, phi, pre, post, res):
        ss, dyn = hc.hc_norm_proj(x, phi, False)
        y = hc.hc_read(x, pre, False)
        out = hc.hc_mix(x, y, post, res, False)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(ss) + jnp.sum(dyn)

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4))).lower(
        s((n, tokens, c)), s((n * c, k), phi_dtype),
        s((n, tokens), jnp.float32), s((n, tokens), jnp.float32),
        s((n, n, tokens), jnp.float32)).compile().as_text()
    for name in ("hc_maps_fwd", "hc_maps_bwd", "hc_read_bwd", "hc_mix_fwd",
                 "hc_mix_bwd"):
        assert re.search(rf"%\S*{name}\S* = .*custom-call", text), name
    for which in ("maps_fwd", "maps_bwd", "read_bwd", "mix_fwd", "mix_bwd"):
        row, fixed = hc._pass_bytes(n, c)[which]
        tt = hc.token_tile(which, n, tokens, c)
        assert tokens % tt == 0 and \
            2 * (fixed + tt * row) <= hc.VMEM_BUDGET < hc.VMEM_LIMIT


@pytest.mark.parametrize("n,length,d,r,offset", [
    (80, 8192, 128, 128, 0),      # laguna's sliding layers: q and k, whole
    (56, 8192, 128, 64, 0),       # its full layers: a head's first half
    (32, 8192, 192, 64, 128),     # joyai's latent queries: the last 64 of 192
    (32, 4096, 192, 64, 128),     # xing4's
    (1, 8192, 64, 64, 0),         # the shared key: half a lane row, whole
    (4, 512, 256, 64, 64),        # a rotation in the middle of two lane rows
    (4, 512, 256, 256, 0),        # a partner a whole lane row away
], ids=["laguna_sliding", "laguna_full", "joyai_q", "xing4_q", "shared_k",
        "middle", "two_lane_rows"])
def test_rotary_kernel_lowers_for_v5e(one_chip, no_compile_cache, n, length,
                                      d, r, offset):
    """Forward and backward are one ``rope_*`` body a shape, at the tile the
    rule gives it and inside its VMEM."""
    ro = importlib.import_module("paddle_tpu.ops.pallas.rotary")

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(x, g, cos, sin):
        out, vjp = jax.vjp(lambda t: ro.rope(t, cos, sin, offset, False), x)
        return out, vjp(g)[0]

    text = jax.jit(both).lower(
        s((1, n, length, d)), s((1, n, length, d)),
        s((length, r), jnp.float32), s((length, r), jnp.float32)
    ).compile().as_text()
    assert len(re.findall(rf"%\S*rope_r{r}\S* = .*custom-call", text)) == 2
    # the heads cross HBM in their own dtype and nothing of them in float32
    assert f"f32[1,{n},{length}," not in text and \
        f"f32[{n},{length}," not in text
    hb, tl = ro.tiles(n, length, d)
    assert n % hb == 0 and length % tl == 0 and \
        4 * hb * tl * 2 * -(-d // 128) * 128 < ro.VMEM_LIMIT


@pytest.mark.parametrize("rows,length,h,p,n,chunk,dtype", [
    (1, 8192, 64, 64, 128, 256, jnp.bfloat16),   # the granite4h cell's scan
    (2, 1024, 8, 128, 128, 128, jnp.float32),    # six-pass products, P = 128
    (1, 512, 4, 32, 256, 128, jnp.bfloat16),     # four heads a slab, N = 256
], ids=["granite4h", "float32_p128", "p32_n256"])
def test_ssm_scan_kernels_lower_for_v5e(one_chip, no_compile_cache, rows,
                                        length, h, p, n, chunk, dtype):
    """The state-space scan, forward and backward, at the benchmark cell's
    shape: the state of every head in VMEM, dynamic 128-lane slabs of the
    (chunk, H P) blocks, bfloat16 limbs into the MXU."""
    sc = importlib.import_module("paddle_tpu.ops.pallas.ssm_scan")

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def loss(*operands):
        return jnp.sum(sc.ssm_scan(*operands, chunk, False).astype(
            jnp.float32))

    text = jax.jit(jax.grad(loss, tuple(range(6)))).lower(
        s((rows, length, h, p), dtype), s((rows, length, h)),
        s((rows, length, h)), s((rows, length, n), dtype),
        s((rows, length, n), dtype), s((h,))).compile().as_text()
    for name in ("ssm_scan_fwd", "ssm_scan_bwd"):
        assert len(re.findall(rf"%\S*{name}\S* = .*custom-call", text)) == 1
    # the states at the chunks' starts are the one residual the kernels add
    assert f"f32[{rows},{length // chunk},{n},{h * p}]" in text
