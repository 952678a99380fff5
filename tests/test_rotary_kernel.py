"""The rotary kernel (``ops/pallas/rotary.py``) against the jnp body it
replaces (``nn/functional/decoder.py:_rotary``), in the Pallas interpreter at
tiny shapes: the result, the gradient, the gradient inside a recomputed
region, and the backward's table against the transposed rotation written out.

Both forms take two float32 products and one float32 sum an element and round
once, so on tables whose products are exact (bfloat16 values held as float32)
they agree to the bit whatever the host's compiler contracts; on
``rotary_cos_sin``'s own tables XLA:CPU fuses one product of one form into the
sum and not of the other, and the roundings may then differ by one bf16 ulp.
On the chip ``tools/rope_sweep.py`` holds both to the bit (PERF.md, PR 43)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from paddle_tpu.framework.recompute import RECOMPUTE_KEEP
from paddle_tpu.nn.functional import decoder as D
from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import rotary as ro

YARN = {"factor": 4, "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
        "mscale_all_dim": 0.5, "original_max_position_embeddings": 32}

# (heads' shape, rotated width, where it starts, whether the kernel takes it)
CASES = {
    "full_128_of_128": ((2, 3, 128, 128), 128, 0, True),
    "first_64_of_128": ((1, 4, 128, 128), 64, 0, True),
    "last_64_of_192": ((1, 2, 64, 192), 64, 128, True),
    "middle_64_of_256": ((1, 2, 64, 256), 64, 64, True),
    "one_shared_head": ((2, 1, 128, 64), 64, 0, True),
    "two_lane_rows": ((1, 2, 64, 256), 256, 0, True),
    "rows_no_tile_divides": ((1, 2, 96, 128), 128, 0, False),
    "columns_off_half_a_lane_row": ((1, 2, 64, 48), 16, 32, False),
}


def _tables(length, r, exact, **kw):
    cos, sin = (jnp.asarray(t) for t in D.rotary_cos_sin(length, r, 1e4, **kw))
    if exact:
        cos, sin = (t.astype(jnp.bfloat16).astype(jnp.float32)
                    for t in (cos, sin))
    return cos, sin


def _heads(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.bfloat16)


def _both(fn, x, cot, on):
    pk.set_enabled(on)
    try:
        out, vjp = jax.vjp(fn, x)
        return out, vjp(cot)[0]
    finally:
        pk.set_enabled(None)


def _bits(a):
    return np.asarray(a.view(jnp.uint16))


def _within_an_ulp(got, want):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2.0 ** -7, atol=1e-6)


@pytest.mark.parametrize("shape,r,offset,kernel", list(CASES.values()),
                         ids=list(CASES))
def test_result_and_gradient_are_the_jnp_bodys(shape, r, offset, kernel):
    x, cot = _heads(shape, 0), _heads(shape, 1)
    pk.set_enabled(True)
    try:
        assert (pk.rotary_route(shape, x.dtype, r, offset) is not None) \
            is kernel
    finally:
        pk.set_enabled(None)
    for exact in (True, False):
        cos, sin = _tables(shape[-2], r, exact)

        def fn(t):
            return D._rotary(t, cos, sin, offset=offset)

        want, got = _both(fn, x, cot, False), _both(fn, x, cot, True)
        for g, w in zip(got, want):
            assert g.dtype == jnp.bfloat16 and g.shape == shape
            if exact or not kernel:
                np.testing.assert_array_equal(_bits(g), _bits(w))
            else:
                _within_an_ulp(g, w)
    # the columns outside the rotation pass untouched, to the bit
    keep = np.ones(shape[-1], bool)
    keep[offset:offset + r] = False
    np.testing.assert_array_equal(_bits(got[0])[..., keep],
                                  _bits(x)[..., keep])
    np.testing.assert_array_equal(_bits(got[1])[..., keep],
                                  _bits(cot)[..., keep])


def test_float32_heads_and_the_host_cpu_stay_dense():
    assert pk.rotary_route((1, 2, 128, 128), jnp.bfloat16, 128) is None
    pk.set_enabled(True)
    try:
        assert pk.rotary_route((1, 2, 128, 128), jnp.bfloat16, 128) is not None
        assert pk.rotary_route((1, 2, 128, 128), jnp.float32, 128) is None
        assert pk.rotary_route((1, 2, 128, 128), jnp.bfloat16, 96) is None
        assert pk.rotary_route((1, 2, 128, 128), jnp.bfloat16, 128, 64) is None
    finally:
        pk.set_enabled(None)


def test_tile_rule():
    assert ro.tiles(72, 8192, 128) == (8, 512)
    assert ro.tiles(32, 4096, 192) == (8, 512)
    assert ro.tiles(1, 8192, 64) == (1, 512)
    assert ro.tiles(6, 192, 128) == (6, 64)
    assert ro.tiles(7, 64, 128) == (7, 64)
    assert ro.tiles(8, 512, 1024) == (4, 512)      # the block's ceiling
    assert ro.tiles(8, 96, 128) is None            # rows: under one band
    assert ro.tiles(1, 512, 8192) is None          # one head over the ceiling


@pytest.mark.parametrize("shape,r,offset", [
    ((1, 3, 64, 128), 128, 0), ((1, 2, 64, 192), 64, 128)],
    ids=["full", "offset"])
def test_backward_inside_a_recomputed_region(shape, r, offset, capsys):
    """The rule holds inside ``jax.checkpoint`` under the repo's policy, the
    forward is made again there, and nothing of the call is kept: against
    autodiff of the jnp body in the same region."""
    x, cot = _heads(shape, 2), _heads(shape, 3)
    w = jnp.asarray(np.random.RandomState(4).randn(shape[-1], shape[-1]) *
                    shape[-1] ** -0.5, jnp.bfloat16)
    cos, sin = _tables(shape[-2], r, True)
    policy = jax.checkpoint_policies.save_only_these_names(RECOMPUTE_KEEP)

    @functools.partial(jax.checkpoint, policy=policy)
    def region(t, w):
        return jnp.tanh(D._rotary(t @ w, cos, sin, offset=offset))

    def grads(on):
        pk.set_enabled(on)
        try:
            fn = jax.jit(jax.grad(
                lambda t, w: jnp.sum((region(t, w) * cot).astype(jnp.float32)),
                argnums=(0, 1)))
            # kept: the region's arguments, the tables (constants), and
            # nothing made inside it
            print_saved_residuals(region, x, w)
            kept = capsys.readouterr().out.strip().splitlines()
            assert sum("from the argument" in line for line in kept) == 2
            assert all("from the argument" in line or
                       "from a constant" in line for line in kept), kept
            return fn(x, w)
        finally:
            pk.set_enabled(None)

    want, got = grads(False), grads(True)
    for g, wnt in zip(got, want):
        _within_an_ulp(g, wnt)
    assert float(jnp.abs(got[0].astype(jnp.float32)).max()) > 0.1


@pytest.mark.parametrize("kw", [{}, {"scaling": YARN},
                                {"scaling": YARN, "attention_factor": 1.3}],
                         ids=["plain", "yarn", "attention_factor"])
def test_backward_is_the_rotation_by_minus_sin(kw):
    """``g cos + rotate_half^T(g sin)``, written out, is what the backward
    computes for any tables, and for ``rotary_cos_sin``'s, whose halves are
    equal, it is ``rotary(g, cos, -sin)``."""
    shape, r = (1, 2, 64, 128), 64
    cos, sin = _tables(shape[-2], r, False, **kw)
    np.testing.assert_array_equal(np.asarray(sin[:, :r // 2]),
                                  np.asarray(sin[:, r // 2:]))
    g = _heads(shape, 5)

    def transposed(g, cos, sin):
        gf = g.astype(jnp.float32)[..., :r]
        t = gf * sin
        turned = jnp.concatenate([t[..., r // 2:], -t[..., :r // 2]], axis=-1)
        return jnp.concatenate([(gf * cos + turned).astype(g.dtype),
                                g[..., r:]], axis=-1)

    pk.set_enabled(True)
    try:
        back = jax.vjp(lambda t: D._rotary(t, cos, sin), g)[1](g)[0]
        by_minus_sin = D._rotary(g, cos, -sin)
        # other tables: the halves differ, and the general form still holds
        odd = sin * jnp.linspace(0.5, 1.5, r)
        back_odd = jax.vjp(lambda t: D._rotary(t, cos, odd), g)[1](g)[0]
    finally:
        pk.set_enabled(None)
    _within_an_ulp(back, transposed(g, cos, sin))
    _within_an_ulp(back, by_minus_sin)
    _within_an_ulp(back_odd, transposed(g, cos, odd))
    assert float(jnp.abs(back_odd.astype(jnp.float32) -
                         D._rotary(g, cos, -odd).astype(jnp.float32)).max()) \
        > 0.05
