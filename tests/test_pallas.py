"""Pallas kernel parity tests — interpret mode vs jnp reference on CPU
(SURVEY §4: 'Pallas kernels: interpret-mode parity vs jnp reference')."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import (flash_attention, fused_layer_norm,
                                   softmax_cross_entropy)

# the module: the package's attribute of that name is the function
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _sdpa_ref(q, k, v, causal, scale=None):
    scale = scale or 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Lq, Lk = s.shape[-2], s.shape[-1]
        m = jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq)
        s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def _qkv(seed, H, Lq, Lk, D, dtype):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(1, H, Lq, D), dtype),
            jnp.asarray(rng.randn(1, H, Lk, D), dtype),
            jnp.asarray(rng.randn(1, H, Lk, D), dtype))


def _rel(got, want):
    """Worst error as a share of the reference's largest entry."""
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(np.asarray(got, np.float32) - want)) /
                 np.max(np.abs(want)))


# (H, Lq, Lk, D, block_q): the shapes where the block rule chooses differently
F32_SHAPES = {
    "256x256x64_bq128": (4, 256, 256, 64, 128),
    "128x128x32_bq64": (2, 128, 128, 32, 64),
    "384x384x64": (2, 384, 384, 64, None),   # a multiple of 128, not of 256
}


class TestFlashAttention:
    @pytest.mark.parametrize("shape", ["256x256x64_bq128", "384x384x64"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_dense(self, causal, shape):
        H, Lq, Lk, D, block_q = F32_SHAPES[shape]
        q, k, v = _qkv(0, H, Lq, Lk, D, jnp.float32)
        out = flash_attention(q, k, v, None, causal, None, block_q, True)
        ref = _sdpa_ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("shape", ["128x128x32_bq64", "384x384x64"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal, shape):
        H, Lq, Lk, D, block_q = F32_SHAPES[shape]
        q, k, v = _qkv(1, H, Lq, Lk, D, jnp.float32)

        def f_pallas(q, k, v):
            return jnp.sum(flash_attention(q, k, v, None, causal, None,
                                           block_q, True) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(_sdpa_ref(q, k, v, causal) ** 2)

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)

    @pytest.mark.parametrize("Lq,Lk,D,block_q", [
        (64, 256, 32, 64),
        (128, 512, 64, None),   # the diagonal crosses blocks off the block
    ])                          # diagonal: q block 0 sees k up to 384..511
    def test_cross_attention_shapes(self, Lq, Lk, D, block_q):
        """Lq != Lk (decode / cross-attention): forward and gradients."""
        q, k, v = _qkv(2, 2, Lq, Lk, D, jnp.float32)
        out = flash_attention(q, k, v, None, True, None, block_q, True)
        ref = _sdpa_ref(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        gp = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, None, True, None, block_q, True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(_sdpa_ref(*a, True) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)

    @pytest.mark.parametrize("H,L,D,causal,block_q", [
        (2, 128, 64, True, 128),
        (1, 1024, 64, True, None),     # the benchmark's GPT cells' shape
        (2, 256, 128, False, None),
    ])
    def test_bf16_tolerance(self, H, L, D, causal, block_q):
        q, k, v = _qkv(3, H, L, L, D, jnp.bfloat16)
        out = flash_attention(q, k, v, None, causal, None, block_q, True)
        ref = _sdpa_ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                                   np.asarray(ref), atol=3e-2, rtol=3e-2)

    @pytest.mark.parametrize("H,L,D,causal", [
        (1, 1024, 64, True),
        (2, 256, 128, False),
    ])
    def test_bf16_grads_as_close_as_the_dense_bf16_path(self, H, L, D,
                                                        causal):
        """bf16 operands (q, k, v, dO, and p / ds cast for their matmuls):
        the gradients stay within the tolerance that the dense path meets
        when it is given the same bf16 inputs."""
        from paddle_tpu.nn.functional.attention import _sdpa
        q, k, v = _qkv(4, H, L, L, D, jnp.bfloat16)
        w = jnp.asarray(np.random.RandomState(5).randn(1, H, L, D),
                        jnp.float32)
        scale = 1.0 / np.sqrt(D)

        def loss(fn):
            return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

        def dense(q, k, v):
            return _sdpa(q, k, v, None, None, scale=scale, is_causal=causal,
                         dropout_p=0.0)

        want = jax.grad(loss(lambda *a: _sdpa_ref(*a, causal)),
                        argnums=(0, 1, 2))(q, k, v)
        got = jax.grad(loss(lambda *a: flash_attention(
            *a, None, causal, None, None, True)), argnums=(0, 1, 2))(q, k, v)
        dense_got = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
        for g, d, r in zip(got, dense_got, want):
            assert _rel(d, r) < 2e-2
            assert _rel(g, r) < 2e-2


# ---- the key bias (a padding mask inside the kernels) ------------------------
def _key_bias(case, B, Lk):
    """[B or 1, 1, Lk] float32, by case."""
    cols = np.arange(Lk)
    if case == "padded_rows":           # every row its own length
        kept = np.array([Lk, Lk // 2 - 3, 5] + [Lk] * (B - 3))
    elif case == "one_row_wholly_padded":
        kept = np.array([Lk - 1, 0, Lk // 3] + [Lk] * (B - 3))
    elif case == "one_row_for_every_batch_row":     # (1, 1, 1, Lk) masks
        kept = np.array([Lk - 37])
    elif case == "masked_keys_first":   # the running maximum starts at -1e30
        return jnp.asarray(np.where(cols >= Lk // 2 + 9, 0.0, -1e30)
                           [None, None].repeat(B, 0), jnp.float32)
    else:                               # "finite": an additive bias proper
        return jnp.asarray(np.random.RandomState(7).randn(B, 1, Lk) * 2,
                           jnp.float32)
    return jnp.asarray(np.where(cols < kept[:, None], 0.0, -1e30)[:, None],
                       jnp.float32)


BIAS_CASES = ["padded_rows", "one_row_wholly_padded",
              "one_row_for_every_batch_row", "masked_keys_first", "finite"]


class TestFlashKeyBias:
    """The biased kernels against ``sdpa``'s dense path with the same mask,
    at BERT-like shapes with blocks smaller than L, so that several k (and
    q) blocks stream and each takes its part of the bias."""

    @pytest.fixture(autouse=True)
    def small_blocks(self, monkeypatch):
        monkeypatch.setattr(fa, "_TARGET", (128, 128, 128))

    @staticmethod
    def _both(dtype, case, Lq, Lk, B=3, H=2, D=64):
        from paddle_tpu.nn.functional.attention import _sdpa
        rng = np.random.RandomState(11)
        q, k, v = (jnp.asarray(rng.randn(B, H, L, D), dtype)
                   for L in (Lq, Lk, Lk))
        w = jnp.asarray(rng.randn(B, H, Lq, D), jnp.float32)
        bias = _key_bias(case, B, Lk)

        def loss(fn):
            def f(q, k, v):
                out = fn(q, k, v)
                return jnp.sum(out.astype(jnp.float32) * w), out
            return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

        def dense(q, k, v):     # float32 operands: the reference
            return _sdpa(*(x.astype(jnp.float32) for x in (q, k, v)),
                         bias[:, None], None, scale=D ** -0.5,
                         is_causal=False, dropout_p=0.0)

        def kernels(q, k, v):
            return flash_attention(q, k, v, bias, False, None, None, True)

        (_, want), want_g = loss(dense)(q, k, v)
        (_, got), got_g = loss(kernels)(q, k, v)
        return (got, *got_g), (want, *want_g), v, bias

    @pytest.mark.parametrize("case", BIAS_CASES)
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 2e-2)],
                             ids=["f32", "bf16"])
    def test_forward_and_grads_match_the_dense_path(self, dtype, tol, case):
        got, want, v, bias = self._both(dtype, case, 256, 256)
        for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
            assert g.dtype == dtype
            assert _rel(g, r) < tol, (name, _rel(g, r))
        if case == "one_row_wholly_padded":
            # every score of the row is -1e30 on both paths: the mean of the
            # values, and gradients of that mean (not Lk times them)
            assert float(jnp.max(bias[1])) < -1e29
            mean = jnp.mean(v[1].astype(jnp.float32), axis=1, keepdims=True)
            for out in (got[0], want[0]):
                assert _rel(out[1], jnp.broadcast_to(mean, out[1].shape)) \
                    < tol
            for g, r in zip(got[1:], want[1:]):
                assert _rel(g[1], r[1]) < tol

    @pytest.mark.parametrize("Lq,Lk", [(128, 384), (384, 128)])
    def test_unequal_lengths(self, Lq, Lk):
        got, want, _, _ = self._both(jnp.float32, "padded_rows", Lq, Lk)
        for g, r in zip(got, want):
            assert _rel(g, r) < 2e-5

    @pytest.mark.parametrize("causal", [False, True])
    def test_no_bias_is_the_call_without_the_argument(self, causal):
        """``bias=None`` is a static case: the same kernels, bit for bit,
        and the same operands (q, k, v first; no fourth)."""
        q, k, v = _qkv(12, 2, 256, 256, 64, jnp.bfloat16)

        def grads(*bias):
            return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, *bias, causal=causal, interpret=True).astype(
                    jnp.float32) ** 2), argnums=(0, 1, 2))(q, k, v)

        for a, b in zip(grads(), grads(None)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        calls = str(jax.make_jaxpr(lambda: grads(None))()).count(
            "pallas_call")
        assert calls == 3

    def test_bias_gets_zeros_not_a_gradient(self):
        """The kernels compute no gradient for the bias; ``flash_route``
        sends a mask that wants one down the dense path."""
        q, k, v = _qkv(13, 2, 128, 128, 64, jnp.float32)
        bias = _key_bias("finite", 1, 128)
        g = jax.grad(lambda b: jnp.sum(flash_attention(
            q, k, v, b, False, None, None, True)))(bias)
        assert g.shape == bias.shape and not np.any(np.asarray(g))


# ---- what a recomputed region keeps -------------------------------------------
# (heads, key/value heads, Dqk, Dv, causal, key bias): a block's attention
KEPT_CASES = {
    "causal": (2, 2, 64, 64, True, False),
    "key_bias": (2, 2, 64, 64, False, True),
    "dqk192_dv128": (2, 2, 192, 128, True, False),
    "grouped_query": (4, 2, 64, 64, True, False),
}


class TestKeptByARecomputedRegion:
    """Two blocks (projections, ``sdpa`` through the kernels, a residual) each
    under ``jax.checkpoint``, rows of 256. Under the repo's policy the forward
    rule's names make the region keep ``o``, ``lse`` (and ``fix``) and its
    backward pass holds no forward call; under a policy that keeps nothing
    the forward runs a second time. Loss and gradients are the same to the
    bit."""

    L, C = 256, 128

    @pytest.fixture(autouse=True)
    def kernels_on(self):
        from paddle_tpu.ops import pallas as pk

        pk.set_enabled(True)
        yield
        pk.set_enabled(None)

    def _loss(self, case, policy):
        from paddle_tpu.nn.functional.attention import _sdpa

        H, Hkv, D, Dv, causal, biased = KEPT_CASES[case]
        widths = np.cumsum([H * D, Hkv * D, Hkv * Dv])
        rng = np.random.RandomState(21)
        x = jnp.asarray(rng.randn(2, self.L, self.C), jnp.float32)
        ws = [(jnp.asarray(rng.randn(self.C, widths[-1]) * 0.1, jnp.float32),
               jnp.asarray(rng.randn(H * Dv, self.C) * 0.1, jnp.float32))
              for _ in range(2)]
        mask = _key_bias("padded_rows", 3, self.L)[:2, None] if biased \
            else None

        def heads(t, n):
            return t.reshape(2, self.L, n, -1).transpose(0, 2, 1, 3)

        def block(x, w_in, w_out):
            q, k, v = jnp.split(jnp.tanh(x @ w_in), widths[:2], axis=-1)
            o = _sdpa(heads(q, H), heads(k, Hkv), heads(v, Hkv), mask, None,
                      scale=D ** -0.5, is_causal=causal, dropout_p=0.0,
                      mask_grad=False)
            return x + o.transpose(0, 2, 1, 3).reshape(x.shape[:2] + (-1,)) \
                @ w_out

        region = jax.checkpoint(block, policy=policy)

        def loss(x, ws):
            for w in ws:
                x = region(x, *w)
            return jnp.sum(x * x)

        return region, loss, x, ws

    @pytest.mark.parametrize("case", list(KEPT_CASES))
    def test_the_forward_runs_once_and_the_gradients_are_the_same(
            self, case, kernel_calls, capsys):
        from jax.ad_checkpoint import print_saved_residuals
        from paddle_tpu.framework.recompute import RECOMPUTE_KEEP

        H, _, _, Dv, causal, biased = KEPT_CASES[case]
        suffix = "_causal" if causal else ""
        policies = jax.checkpoint_policies

        def read(policy):
            region, loss, x, ws = self._loss(case, policy)
            fn = jax.value_and_grad(loss, argnums=(0, 1))
            print_saved_residuals(region, x, *ws[0])
            # what the region keeps of what it makes (a kept value that is
            # also on the way to the region's result is listed as the output
            # of jax's ``reduce_precision`` guard, not by its name)
            kept = [line.split()[0] for line in
                    capsys.readouterr().out.splitlines()
                    if "from the argument" not in line and
                    "from a constant" not in line]
            return kernel_calls(fn, x, ws), kept, jax.jit(fn)(x, ws)

        calls, kept, got = read(
            policies.save_only_these_names(RECOMPUTE_KEEP))
        calls_bare, kept_bare, want = read(policies.nothing_saveable)
        assert calls == {f"flash_fwd{suffix}": 2, f"flash_bwd_dq{suffix}": 2,
                         f"flash_bwd_dkv{suffix}": 2}
        assert calls_bare == dict(calls, **{f"flash_fwd{suffix}": 4})
        rows = [f"f32[{2 * H},1,{self.L}]"] * (2 if biased else 1)  # lse, fix
        assert sorted(kept) == sorted([f"f32[{2 * H},{self.L},{Dv}]"] + rows)
        assert kept_bare == []
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert float(jnp.abs(got[1][0]).max()) > 1e-3


class TestFlashBlockRule:
    """``block_sizes`` alone, no kernel: every shape ``flash_route`` takes
    gets blocks Mosaic can tile, inside the rule's own VMEM budget."""

    LENGTHS = [128, 256, 384, 512, 640, 1024, 1152, 1920, 2048, 4096, 8064,
               8192]

    @pytest.mark.parametrize("itemsize", [2, 4])
    @pytest.mark.parametrize("D", [64, 128, 192, 256])
    def test_blocks_divide_tile_and_fit(self, D, itemsize):
        assert fa.VMEM_BUDGET < 16 * 2 ** 20      # the scoped default
        for Lq in self.LENGTHS:
            for Lk in self.LENGTHS:
                bq, bk, sub = fa.block_sizes(Lq, Lk, D, itemsize)
                assert Lq % bq == 0 and Lk % bk == 0
                assert bq % sub == 0 and bk % sub == 0
                # lanes of the lse row and of the score block; a multiple
                # of 128 is one of the 8 (f32) and 16 (bf16) sublanes of the
                # q, k, v blocks too
                assert bq % 128 == 0 and bk % 128 == 0 and sub % 128 == 0
                assert fa.vmem_bytes(bq, bk, sub, D, itemsize) <= \
                    fa.VMEM_BUDGET
        # the estimate knows no L, and past the target neither do the
        # blocks: VMEM use does not grow with the sequence
        at = [fa.block_sizes(L, L, D, itemsize)
              for L in (1024, 2048, 4096, 8192)]
        assert len(set(at)) == 1

    def test_block_q_is_an_upper_bound(self):
        assert fa.block_sizes(1024, 1024, 64, 2, 128)[0] == 128
        assert fa.block_sizes(128, 128, 32, 4, 64)[0] == 64
        free = fa.block_sizes(1024, 1024, 64, 2)
        assert fa.block_sizes(1024, 1024, 64, 2, 4096) == free


class TestFusedLayerNorm:
    def test_forward_matches(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(64, 256), jnp.float32)
        g = jnp.asarray(rng.randn(256), jnp.float32)
        b = jnp.asarray(rng.randn(256), jnp.float32)
        out = fused_layer_norm(x, g, b, 1e-5, True)
        mean = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        ref = (x - mean) / jnp.sqrt(var + 1e-5) * g + b
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_grads_match(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(32, 128), jnp.float32)
        g = jnp.asarray(rng.randn(128), jnp.float32)
        b = jnp.asarray(rng.randn(128), jnp.float32)

        def f_pallas(x, g, b):
            return jnp.sum(fused_layer_norm(x, g, b, 1e-5, True) ** 2)

        def f_ref(x, g, b):
            mean = x.mean(-1, keepdims=True)
            var = x.var(-1, keepdims=True)
            return jnp.sum(((x - mean) / jnp.sqrt(var + 1e-5) * g + b) ** 2)

        gp = jax.grad(f_pallas, argnums=(0, 1, 2))(x, g, b)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, g, b)
        for a, b_ in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-4, rtol=1e-4)


ce = importlib.import_module("paddle_tpu.ops.pallas.softmax_ce")

# (N, V): the three shapes the tests had, then vocabularies the 2048-wide
# block does not divide (a one-chunk tail, one block narrower than the
# target, GPT-2's 50,304 with 1,152 columns in its 25th block, a tail that is
# no multiple of 128 lanes) and one it does
_CE_SHAPES = [(64, 4096), (16, 512), (32, 1024), (16, 2176), (24, 384),
              (8, 50304), (8, 2100), (32, 4096)]
_CE_CASES = [pytest.param(N, V, dt, id=f"{N}x{V}-{jnp.dtype(dt).name}")
             for N, V in _CE_SHAPES for dt in (jnp.float32, jnp.bfloat16)
             if dt == jnp.float32 or (N, V) in _CE_SHAPES[3:]]


def _ce_inputs(seed, N, V, dtype):
    """Logits, labels (row 0 hits the last column, row 1 the first column of
    the last vocabulary block, rows 2 and 4 are ignored) and row weights."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(N, V) * 2.0, dtype)
    lab = np.asarray(rng.randint(0, V, N), np.int32)
    bv = ce.block_sizes(N, V, jnp.dtype(dtype).itemsize)[1]
    lab[0], lab[1] = V - 1, (V - 1) // bv * bv
    lab[2] = lab[4] = -100
    return x, jnp.asarray(lab), jnp.asarray(rng.rand(N) + 0.5, jnp.float32)


def _ce_ref(x, lab):
    """Per-row loss by ``logsumexp`` in float32; ignored rows 0."""
    x = x.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(x, axis=-1)
    per = lse - x[jnp.arange(x.shape[0]), jnp.maximum(lab, 0)]
    return jnp.where(lab != -100, per, 0.0)


class TestSoftmaxCE:
    @pytest.mark.parametrize("N,V,dtype", _CE_CASES)
    def test_forward_matches(self, N, V, dtype):
        x, lab, _ = _ce_inputs(0, N, V, dtype)
        out = softmax_cross_entropy(x, lab, -100, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(_ce_ref(x, lab)),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("N,V,dtype", _CE_CASES)
    def test_ignore_index(self, N, V, dtype):
        x, lab, _ = _ce_inputs(1, N, V, dtype)
        lab = np.array(lab)
        lab[::2] = -100
        out, vjp = jax.vjp(
            lambda x: softmax_cross_entropy(x, jnp.asarray(lab), -100, True), x)
        assert np.all(np.asarray(out)[::2] == 0.0)
        assert np.all(np.asarray(out)[1::2] > 0.0)
        dx = np.asarray(vjp(jnp.ones(N, jnp.float32))[0], np.float32)
        assert np.all(dx[::2] == 0.0) and np.all(np.isfinite(dx))
        assert np.all(np.abs(dx[1::2]).sum(axis=-1) > 0.0)

    @pytest.mark.parametrize("N,V,dtype", _CE_CASES)
    def test_grads_match(self, N, V, dtype):
        x, lab, w = _ce_inputs(2, N, V, dtype)

        def f_pallas(x):
            return jnp.sum(softmax_cross_entropy(x, lab, -100, True) * w)

        gp = jax.grad(f_pallas)(x)
        gr = jax.grad(lambda x: jnp.sum(_ce_ref(x, lab) * w))(
            x.astype(jnp.float32))
        assert gp.dtype == x.dtype and gp.shape == x.shape
        # a bfloat16 gradient is the float32 one rounded once: 2**-9 of it
        rtol, atol = (1e-5, 1e-5) if dtype == jnp.float32 else (4e-3, 4e-5)
        np.testing.assert_allclose(np.asarray(gp, np.float32), np.asarray(gr),
                                   atol=atol, rtol=rtol)


class TestWiredPaths:
    """The F.sdpa / F.cross_entropy / layer_norm call sites route through
    the pallas kernels when enabled — parity vs the dense paths."""

    def _toggle(self, value):
        from paddle_tpu.ops import pallas as pk

        pk.set_enabled(value)

    def test_sdpa_routes_and_matches(self):
        import paddle_tpu as pt
        import paddle_tpu.nn.functional as F

        rng = np.random.RandomState(0)
        q = pt.to_tensor(rng.randn(2, 2, 128, 64).astype("float32"))
        k = pt.to_tensor(rng.randn(2, 2, 128, 64).astype("float32"))
        v = pt.to_tensor(rng.randn(2, 2, 128, 64).astype("float32"))
        self._toggle(False)
        dense = F.sdpa_bhld(q, k, v, is_causal=True).numpy()
        self._toggle(True)
        try:
            flash = F.sdpa_bhld(q, k, v, is_causal=True).numpy()
        finally:
            self._toggle(None)
        np.testing.assert_allclose(flash, dense, atol=2e-5, rtol=2e-5)

    def test_cross_entropy_routes_and_matches(self):
        import paddle_tpu as pt
        import paddle_tpu.nn.functional as F

        rng = np.random.RandomState(1)
        logits = pt.to_tensor(rng.randn(32, 512).astype("float32"))
        lab = rng.randint(0, 512, 32)
        lab[:4] = -100
        lab = pt.to_tensor(lab.astype("int64"))
        self._toggle(False)
        dense = float(F.cross_entropy(logits, lab).numpy())
        self._toggle(True)
        try:
            fused = float(F.cross_entropy(logits, lab).numpy())
        finally:
            self._toggle(None)
        np.testing.assert_allclose(fused, dense, atol=1e-5, rtol=1e-5)

    def test_layer_norm_routes_and_matches_with_grad(self):
        import paddle_tpu as pt
        import paddle_tpu.nn as nn

        rng = np.random.RandomState(2)
        x = rng.randn(16, 256).astype("float32")

        def run():
            pt.seed(5)
            ln = nn.LayerNorm(256)
            xt = pt.to_tensor(x, stop_gradient=False)
            out = ln(xt)
            loss = (out * out).mean()
            loss.backward()
            return out.numpy(), ln.weight.grad.numpy()

        self._toggle(False)
        dense_out, dense_gw = run()
        self._toggle(True)
        try:
            fused_out, fused_gw = run()
        finally:
            self._toggle(None)
        np.testing.assert_allclose(fused_out, dense_out, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(fused_gw, dense_gw, atol=1e-4, rtol=1e-4)
