"""Sliding-window attention: the ``swa_*`` kernels in the interpreter against
``sdpa``'s dense band-masked path (values and all three gradients, windows
that are and are not a multiple of the block, grouped-query heads through
``sdpa``), a window that reaches the whole row handed to the causal kernels,
what a recomputed region keeps of a windowed call, the band's blocks by
``band_sizes``; rotary over part of a head and a stated
attention factor against the formula; ``DroplessMoE`` with softmax scores
against a dense loop, and its sigmoid call lowered as before there was a
choice."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.dist import moe
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops._base import OP_REGISTRY

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _band(L, window):
    d = np.arange(L)[:, None] - np.arange(L)[None, :]
    return (d >= 0) & (d < window)


def _dense(q, k, v, window, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    s = jnp.where(_band(q.shape[2], window), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                      precision="highest")


def _qkv(shape, kv_heads=None, seed=0, dv=None):
    B, H, L, D = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    kv = (B, kv_heads or H, L, D)
    return (jax.random.normal(keys[0], shape, jnp.float32),
            jax.random.normal(keys[1], kv, jnp.float32),
            jax.random.normal(keys[2], kv[:3] + (dv or D,), jnp.float32),
            jax.random.normal(keys[3], shape[:3] + (dv or D,), jnp.float32))


@pytest.mark.parametrize("L,window,block,sub", [
    (64, 16, 16, 16),      # the window is the block: two blocks a grid row
    (64, 20, 16, 16),      # not a multiple of it: three, both edges cross
    (96, 33, 32, 8),       # bands smaller than the block
    (256, 100, 128, 128),  # whole lane tiles
], ids=["w16_b16", "w20_b16", "w33_b32_sub8", "w100_b128"])
def test_kernels_against_the_dense_band(monkeypatch, L, window, block, sub):
    monkeypatch.setattr(fa, "_BAND_TARGET", (block, sub))
    q, k, v, do = _qkv((1, 2, L, 16), dv=8)
    scale = 0.25
    assert fa.band_sizes(L, window, 16, 4, None, 8) == (block, block, sub)
    out, vjp = jax.vjp(lambda *a: fa.window_attention(
        *a, window, scale, None, True), q, k, v)
    want, vjp_dense = jax.vjp(lambda *a: _dense(*a, window, scale), q, k, v)
    for got, ref in zip((out,) + vjp(do), (want,) + vjp_dense(do)):
        np.testing.assert_allclose(got, ref, atol=3e-6, rtol=1e-5)


@pytest.fixture
def kernels_on(monkeypatch):
    pk.set_enabled(True)
    monkeypatch.setattr(fa, "MIN_STEP_SCORES", 128 * 128)
    # traced anew, so that a test sees the names its own calls carry
    fa._forward.clear_cache()
    fa._backward.clear_cache()
    yield
    pk.set_enabled(None)


def _sdpa_and_grads(q, k, v, do, window):
    tensors = [Tensor(a, stop_gradient=False, _internal=True)
               for a in (q, k, v)]
    out = F.sdpa_bhld(*tensors, is_causal=True, window=window)
    out.backward(Tensor(do, _internal=True))
    return [out._data] + [t.grad._data for t in tensors]


def test_through_sdpa_with_grouped_query_heads(kernels_on, monkeypatch):
    """Six query heads over two key/value heads, L 256, window 100: the
    kernels take the call (``sdpa`` expands the key/value heads first) and
    agree with the dense band-masked path, the group's gradients summed."""
    names = []
    real = fa._kernel_name
    monkeypatch.setattr(fa, "_kernel_name", lambda *a: names.append(
        real(*a)) or names[-1])
    q, k, v, do = _qkv((1, 6, 256, 64), kv_heads=2)
    got = _sdpa_and_grads(q, k, v, do, 100)
    assert sorted(set(names)) == ["swa_bwd_dkv_w100", "swa_bwd_dq_w100",
                                  "swa_fwd_w100"]
    pk.set_enabled(False)
    want = _sdpa_and_grads(q, k, v, do, 100)
    assert got[1].shape == q.shape and got[2].shape == k.shape
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
    # and the band is not the causal triangle
    causal = _sdpa_and_grads(q, k, v, do, None)
    assert float(jnp.abs(causal[0] - want[0]).max()) > 0.1


@pytest.mark.parametrize("window", [100, 128], ids=["window_100",
                                                    "window_is_the_block"])
def test_a_recomputed_region_keeps_a_windowed_calls_outputs(
        kernels_on, kernel_calls, window):
    """``_window_fwd`` names ``o`` and ``lse`` as ``_flash_fwd`` does: under
    the repo's policy the region keeps them and its backward pass holds no
    ``swa_fwd``; under a policy that keeps nothing the forward runs twice.
    The gradients are the same to the bit."""
    from paddle_tpu.framework.recompute import RECOMPUTE_KEEP
    from paddle_tpu.nn.functional.attention import _sdpa

    q, k, v, do = _qkv((1, 4, 256, 64), kv_heads=2, seed=7)

    def read(policy):
        def region(q, k, v):
            return _sdpa(jnp.tanh(q), k, v, None, None, scale=0.125,
                         is_causal=True, dropout_p=0.0, window=window)

        fn = jax.grad(lambda *a: jnp.sum(jax.checkpoint(
            region, policy=policy)(*a) * do), argnums=(0, 1, 2))
        return kernel_calls(fn, q, k, v), jax.jit(fn)(q, k, v)

    calls_bare, want = read(jax.checkpoint_policies.nothing_saveable)
    calls, got = read(
        jax.checkpoint_policies.save_only_these_names(RECOMPUTE_KEEP))
    assert calls == {f"swa_fwd_w{window}": 1, f"swa_bwd_dq_w{window}": 1,
                     f"swa_bwd_dkv_w{window}": 1}
    assert calls_bare == dict(calls, **{f"swa_fwd_w{window}": 2})
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert float(jnp.abs(got[0]).max()) > 1e-3


@pytest.mark.parametrize("window", [256, 300], ids=["L_is_window",
                                                    "L_under_window"])
def test_a_window_that_reaches_the_row_is_the_causal_call(kernels_on,
                                                          monkeypatch, window):
    names = []
    real = fa._kernel_name
    monkeypatch.setattr(fa, "_kernel_name", lambda *a: names.append(
        real(*a)) or names[-1])
    q, k, v, do = _qkv((1, 2, 256, 64), seed=3)
    got = _sdpa_and_grads(q, k, v, do, window)
    assert sorted(set(names)) == ["flash_bwd_dkv_causal",
                                  "flash_bwd_dq_causal", "flash_fwd_causal"]
    want = _sdpa_and_grads(q, k, v, do, None)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_the_dense_path_masks_the_band_and_refuses_what_it_cannot_mean():
    q, k, v, _ = _qkv((2, 2, 24, 8), seed=5)
    out = F.sdpa_bhld(*(Tensor(a, _internal=True) for a in (q, k, v)),
                      is_causal=True, window=5)
    np.testing.assert_allclose(out._data, _dense(q, k, v, 5, 8 ** -0.5),
                               atol=2e-6)
    # Paddle's layout takes it too
    swapped = [Tensor(jnp.swapaxes(a, 1, 2), _internal=True)
               for a in (q, k, v)]
    again = F.scaled_dot_product_attention(*swapped, is_causal=True, window=5)
    np.testing.assert_allclose(jnp.swapaxes(again._data, 1, 2), out._data,
                               atol=1e-6)
    with pytest.raises(ValueError, match="is_causal"):
        F.sdpa_bhld(*swapped, window=5)
    with pytest.raises(ValueError, match="own key"):
        F.sdpa_bhld(*swapped, is_causal=True, window=0)


def test_band_sizes_and_the_names_a_trace_reads():
    # the cell's shape: square blocks of 1,024, bands of 128, two blocks a row
    assert fa.band_sizes(8192, 512, 128, 2) == (1024, 1024, 128)
    assert fa.band_sizes(8192, 512, 128, 2, block=256)[:2] == (256, 256)
    assert fa.band_sizes(384, 100, 64, 2) == (384, 384, 128)
    assert fa._kernel_name("fwd", True, 512) == "swa_fwd_w512"
    assert fa._kernel_name("bwd_dkv", True) == "flash_bwd_dkv_causal"
    assert fa._kernel_name("bwd_dq", False) == "flash_bwd_dq"


# ---- rotary ---------------------------------------------------------------------
def test_rotary_over_part_of_a_head_and_a_stated_attention_factor():
    """Laguna's full-attention table: 64 of 128 dims, YaRN at theta 500,000
    over an original 8,192 by 128, cos and sin times 1.4852...; against the
    formula written out (Hugging Face's ``_compute_yarn_parameters``)."""
    scaling = {"factor": 128, "beta_fast": 32, "beta_slow": 1,
               "original_max_position_embeddings": 8192}
    factor = 1.4852030263919618
    L, d, r = 40, 128, 64
    cos, sin = F.rotary_cos_sin(L, r, 500000.0, scaling, factor)
    assert cos.shape == sin.shape == (L, r)
    i = np.arange(r // 2)
    plain = 500000.0 ** (-2.0 * i / r)

    def dim_of(turns):
        return r * np.log(8192 / (turns * 2 * np.pi)) / (2 * np.log(500000.0))

    lo, hi = np.floor(dim_of(32)), np.ceil(dim_of(1))
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)
    freq = plain / 128 * ramp + plain * (1 - ramp)
    angle = np.arange(L)[:, None] * freq[None, :]
    np.testing.assert_allclose(cos[:, :r // 2], factor * np.cos(angle),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(sin[:, r // 2:], factor * np.sin(angle),
                               rtol=1e-6, atol=1e-6)
    assert freq[0] == 1.0 and freq[-1] < plain[-1] / 100   # both ends of it
    # the stated factor is YaRN's own for this stretch, 0.1 ln(128) + 1, so
    # the table without one is the same; another factor scales it
    own, _ = F.rotary_cos_sin(L, r, 500000.0, scaling)
    np.testing.assert_allclose(own, cos, rtol=1e-6)
    twice, _ = F.rotary_cos_sin(L, r, 500000.0, scaling, 2 * factor)
    np.testing.assert_allclose(twice, 2 * cos, rtol=1e-6)

    x = np.random.default_rng(0).normal(size=(2, 3, L, d)).astype(np.float32)
    got = F.rotary(Tensor(jnp.asarray(x), _internal=True), cos, sin)._data
    a, b = x[..., :r // 2], x[..., r // 2:r]
    c, s = factor * np.cos(angle), factor * np.sin(angle)
    want = np.concatenate([a * c - b * s, b * c + a * s, x[..., r:]], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # a table as wide as the head turns all of it, as it always did
    cos_all, sin_all = F.rotary_cos_sin(L, d, 10000.0)
    whole = F.rotary(Tensor(jnp.asarray(x), _internal=True), cos_all,
                     sin_all)._data
    assert float(np.abs(np.asarray(whole)[..., r:] - x[..., r:]).max()) > 0.1


# ---- the router's score -----------------------------------------------------------
def _layer(score, held=None, first=0, **kw):
    layer = moe.DroplessMoE(32, 16, 8, 3, first=first, held=held,
                            routed_scale=2.5, score=score, **kw)
    rng = np.random.default_rng(1)
    for p in layer.parameters():
        p.set_value(rng.normal(size=p.shape).astype(np.float32) * 0.3)
    return layer


@pytest.mark.parametrize("held,first", [(None, 0), (4, 2)],
                         ids=["all_held", "experts_2_to_5"])
def test_softmax_scores_against_a_dense_loop(held, first):
    layer = _layer("softmax", held, first)
    x = np.random.default_rng(2).normal(size=(2, 12, 32)).astype(np.float32)
    y, load = layer(Tensor(jnp.asarray(x), _internal=True))
    h = x.reshape(-1, 32)
    logits = h @ np.asarray(layer.router._data)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    choice = np.argsort(-s, axis=-1)[:, :3]
    gate, up, down = (np.asarray(w._data) for w in (
        layer.experts_gate, layer.experts_up, layer.experts_down))
    want = np.zeros_like(h)
    for t in range(len(h)):
        chosen = s[t, choice[t]]
        for e, w in zip(choice[t], 2.5 * chosen / chosen.sum()):
            j = e - first
            if 0 <= j < layer.held:
                a = h[t] @ gate[j]
                want[t] += w * ((a / (1 + np.exp(-a)) * (h[t] @ up[j]))
                                @ down[j])
    np.testing.assert_allclose(y.numpy().reshape(-1, 32), want, rtol=2e-4,
                               atol=2e-5)
    assert load.numpy().sum() == 24 * 3
    assert (load.numpy() == np.bincount(choice.ravel(), minlength=8)).all()
    with pytest.raises(ValueError, match="score"):
        moe.DroplessMoE(32, 16, 8, 3, score="tanh")


def test_the_sigmoid_call_is_lowered_as_before_there_was_a_choice(monkeypatch):
    """Cells 5-7 hold to their compiled steps: a layer that does not name a
    score lowers to the text it had with the one function there was (written
    out here as it stood), and the softmax's differs from it."""
    x = jax.ShapeDtypeStruct((2, 12, 32), jnp.float32)

    def lowered(layer):
        def run(a):
            y, load = layer(Tensor(a, _internal=True))
            return y._data, load._data
        return jax.jit(run).lower(x).as_text()

    now = lowered(_layer("sigmoid", 4, 2))

    def sigmoid_route(h, w_gate):
        return moe._keep(jax.nn.sigmoid(jnp.matmul(
            h.astype(jnp.float32), w_gate.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)))

    monkeypatch.setitem(OP_REGISTRY, "moe_route", sigmoid_route)
    assert lowered(_layer("sigmoid", 4, 2)) == now
    monkeypatch.undo()
    assert lowered(_layer("softmax", 4, 2)) != now
    np.testing.assert_array_equal(
        moe.sigmoid_route(jnp.ones((2, 32)), jnp.ones((32, 8))),
        moe.route_scores(jnp.ones((2, 32)), jnp.ones((32, 8))))
