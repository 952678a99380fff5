"""Distributed tests on the virtual 8-device CPU mesh (SURVEY §4):
collectives, DP parity vs single-device, TP parity, ring attention vs
dense, pipeline parity."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.optim as optim
import paddle_tpu.nn.functional as F
from paddle_tpu import distributed as dist


@pytest.fixture(autouse=True)
def _mesh_reset():
    yield
    dist.set_mesh(None)


def _require8():
    if jax.device_count() < 8:
        pytest.skip("needs 8 virtual devices")


class TestMesh:
    def test_init_mesh_infer(self):
        _require8()
        m = dist.init_mesh({"data": 2, "model": -1})
        assert m.shape == {"data": 2, "model": 4}
        assert dist.mesh_axis_size("model") == 4

    def test_init_mesh_bad_product(self):
        _require8()
        with pytest.raises(ValueError):
            dist.init_mesh({"data": 3})


class TestCollectives:
    def test_all_reduce_eager(self):
        _require8()
        dist.init_mesh({"data": 8})
        x = pt.to_tensor(np.arange(8, dtype="float32"))
        out = dist.all_reduce(x)
        # each shard holds 1 element; psum makes every element the sum
        np.testing.assert_allclose(out.numpy(), np.full(8, np.arange(8).sum()))

    def test_all_gather_inside_shard_map(self):
        _require8()
        m = dist.init_mesh({"data": 8})

        def f(x):
            return jax.lax.all_gather(x, "data", tiled=True)

        x = jnp.arange(8, dtype=jnp.float32)
        out = jax.shard_map(f, mesh=m, in_specs=P("data"),
                            out_specs=P("data"))(x)
        assert out.shape == (64,)

    def test_reduce_scatter(self):
        _require8()
        dist.init_mesh({"data": 8})
        x = pt.to_tensor(np.ones(64, "float32"))
        out = dist.reduce_scatter(x)
        # global length shrinks by the axis size; every element is the sum
        # of the 8 shards' contributions
        np.testing.assert_allclose(out.numpy(), np.full(8, 8.0))

    def test_broadcast(self):
        _require8()
        dist.init_mesh({"data": 8})
        x = pt.to_tensor(np.arange(8, dtype="float32"))
        out = dist.broadcast(x, src=3)
        np.testing.assert_allclose(out.numpy(), np.full(8, 3.0))

    def test_ppermute_ring(self):
        _require8()
        dist.init_mesh({"data": 8})
        x = pt.to_tensor(np.arange(8, dtype="float32"))
        perm = [(i, (i + 1) % 8) for i in range(8)]
        out = dist.ppermute(x, perm)
        np.testing.assert_allclose(out.numpy(), np.roll(np.arange(8), 1))


class TestDataParallel:
    def test_dp_matches_single_device(self):
        _require8()
        rng = np.random.RandomState(0)
        X = rng.randn(32, 8).astype("float32")
        Y = (X @ rng.randn(8, 1)).astype("float32")

        def build():
            pt.seed(5)
            m = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
            o = optim.Adam(0.05, parameters=m.parameters())
            return m, o

        # single-device fused baseline
        m1, o1 = build()
        s1 = pt.TrainStep(m1, o1, lambda m, x, y: F.mse_loss(m(x), y))
        base = [float(s1(X, Y)) for _ in range(5)]

        # 8-way data parallel
        mesh = dist.init_mesh({"data": 8})
        m2, o2 = build()  # pt.seed(5) makes init identical to m1's
        s2 = dist.DistributedTrainStep(m2, o2,
                                       lambda m, x, y: F.mse_loss(m(x), y),
                                       mesh=mesh)
        got = [float(s2(X, Y)) for _ in range(5)]
        np.testing.assert_allclose(got, base, rtol=2e-3)

    def test_dataparallel_wrapper_identity(self):
        m = nn.Linear(4, 2)
        w = dist.DataParallel(m)
        x = pt.to_tensor(np.ones((3, 4), "float32"))
        np.testing.assert_allclose(w(x).numpy(), m(x).numpy())
        assert "weight" in w.state_dict()


class TestTensorParallel:
    def test_column_row_parity(self):
        _require8()
        mesh = dist.init_mesh({"data": 2, "model": 4})
        rng = np.random.RandomState(1)
        x = rng.randn(6, 16).astype("float32")

        col = dist.ColumnParallelLinear(16, 32, gather_output=False)
        row = dist.RowParallelLinear(32, 8, input_is_parallel=True)

        with mesh:
            y = row(col(pt.to_tensor(x)))
        want = (x @ col.weight.numpy() + col.bias.numpy()) @ \
            row.weight.numpy() + row.bias.numpy()
        np.testing.assert_allclose(y.numpy(), want, rtol=1e-4, atol=1e-4)

    def test_vocab_parallel_embedding(self):
        _require8()
        mesh = dist.init_mesh({"model": 8})
        emb = dist.VocabParallelEmbedding(64, 16)
        ids = pt.to_tensor(np.array([[1, 5], [63, 0]]))
        with mesh:
            out = emb(ids)
        np.testing.assert_allclose(out.numpy(),
                                   emb.weight.numpy()[ids.numpy()], rtol=1e-5)

    def test_parallel_cross_entropy(self):
        _require8()
        mesh = dist.init_mesh({"model": 8})
        logits = np.random.RandomState(2).randn(4, 32).astype("float32")
        labels = np.array([0, 5, 31, 7])
        pce = dist.ParallelCrossEntropy()
        with mesh:
            loss = pce(pt.to_tensor(logits), pt.to_tensor(labels))
        want = F.cross_entropy(pt.to_tensor(logits), pt.to_tensor(labels),
                               reduction="none").numpy()
        np.testing.assert_allclose(loss.numpy(), want, rtol=1e-4)


class TestRingAttention:
    def test_matches_dense(self):
        _require8()
        mesh = dist.init_mesh({"sp": 8})
        rng = np.random.RandomState(3)
        q = rng.randn(2, 4, 32, 16).astype("float32")
        k = rng.randn(2, 4, 32, 16).astype("float32")
        v = rng.randn(2, 4, 32, 16).astype("float32")
        out = dist.ring_attention(pt.to_tensor(q), pt.to_tensor(k),
                                  pt.to_tensor(v), axis_name="sp")
        dense = F.sdpa_bhld(pt.to_tensor(q), pt.to_tensor(k),
                            pt.to_tensor(v))
        np.testing.assert_allclose(out.numpy(), dense.numpy(), rtol=2e-3,
                                   atol=2e-3)

    def test_causal_matches_dense(self):
        _require8()
        mesh = dist.init_mesh({"sp": 8})
        rng = np.random.RandomState(4)
        q = rng.randn(1, 2, 16, 8).astype("float32")
        out = dist.ring_attention(pt.to_tensor(q), pt.to_tensor(q),
                                  pt.to_tensor(q), axis_name="sp",
                                  causal=True)
        dense = F.sdpa_bhld(pt.to_tensor(q), pt.to_tensor(q),
                            pt.to_tensor(q), is_causal=True)
        np.testing.assert_allclose(out.numpy(), dense.numpy(), rtol=2e-3,
                                   atol=2e-3)

    def test_grad_flows(self):
        _require8()
        mesh = dist.init_mesh({"sp": 8})
        q = pt.to_tensor(np.random.randn(1, 2, 16, 8).astype("float32"),
                         stop_gradient=False)
        out = dist.ring_attention(q, q, q, axis_name="sp")
        pt.mean(out).backward()
        assert q.grad is not None and np.isfinite(q.grad.numpy()).all()

    def test_grads_match_dense(self):
        """VALUE parity of the backward through the ppermute ring (a
        finite-but-wrong gradient would train long-context models to
        garbage while every finiteness check stays green). Weighted loss
        so dOut is non-constant; causal on to cover the masked path."""
        _require8()
        mesh = dist.init_mesh({"sp": 8})
        rng = np.random.RandomState(5)
        qa = rng.randn(1, 2, 32, 8).astype("float32")
        ka = rng.randn(1, 2, 32, 8).astype("float32")
        va = rng.randn(1, 2, 32, 8).astype("float32")
        w = rng.randn(1, 2, 32, 8).astype("float32")

        def grads(attn_fn, **kw):
            q = pt.to_tensor(qa, stop_gradient=False)
            k = pt.to_tensor(ka, stop_gradient=False)
            v = pt.to_tensor(va, stop_gradient=False)
            out = attn_fn(q, k, v, **kw)
            (out * pt.to_tensor(w)).sum().backward()
            return [t.grad.numpy() for t in (q, k, v)]

        ring = grads(lambda q, k, v, **kw: dist.ring_attention(
            q, k, v, axis_name="sp", **kw), causal=True)
        dense = grads(F.sdpa_bhld, is_causal=True)
        for g_ring, g_dense, name in zip(ring, dense, "qkv"):
            np.testing.assert_allclose(
                g_ring, g_dense, rtol=2e-3, atol=2e-3,
                err_msg=f"d{name} diverges between ring and dense")

    def test_no_mesh_fallback(self):
        q = pt.to_tensor(np.random.randn(1, 2, 8, 4).astype("float32"))
        out = dist.ring_attention(q, q, q)
        dense = F.sdpa_bhld(q, q, q)
        np.testing.assert_allclose(out.numpy(), dense.numpy(), rtol=1e-5)


class TestPipeline:
    def test_pipeline_matches_sequential(self):
        _require8()
        mesh = dist.init_mesh({"pipe": 8})
        rng = np.random.RandomState(8)
        n_stages = 8
        D = 16
        Ws = rng.randn(n_stages, D, D).astype("float32") * 0.3
        bs = rng.randn(n_stages, D).astype("float32") * 0.1

        def stage_fn(params, x):
            W, b = params
            return jnp.tanh(x @ W + b)

        X = rng.randn(8, D).astype("float32")
        out = dist.pipeline_forward(stage_fn, (jnp.asarray(Ws), jnp.asarray(bs)),
                                    X, num_microbatches=4, mesh=mesh)
        want = X
        for s in range(n_stages):
            want = np.tanh(want @ Ws[s] + bs[s])
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-3,
                                   atol=2e-3)

    def test_pipeline_grads(self):
        _require8()
        mesh = dist.init_mesh({"pipe": 8})
        rng = np.random.RandomState(9)
        Ws = jnp.asarray(rng.randn(8, 8, 8).astype("float32") * 0.3)

        def stage_fn(W, x):
            return jnp.tanh(x @ W)

        X = jnp.asarray(rng.randn(4, 8).astype("float32"))

        def loss_fn(Ws):
            out = dist.pipeline_forward(stage_fn, Ws, X, num_microbatches=2,
                                        mesh=mesh)
            return jnp.mean(out ** 2)

        g = jax.grad(loss_fn)(Ws)
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).sum() > 0


class TestFleet:
    def test_fleet_init_builds_mesh(self):
        _require8()
        strat = dist.DistributedStrategy()
        strat.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
        dist.fleet.init(is_collective=True, strategy=strat)
        m = dist.get_mesh()
        assert m.shape == {"data": 2, "model": 4}

    def test_distributed_optimizer_passthrough(self):
        opt = optim.SGD(0.1, parameters=nn.Linear(2, 2).parameters())
        out = dist.fleet.distributed_optimizer(opt)
        assert out is opt


class TestCollectiveReviewRegressions:
    def test_dist_function_not_shadowed(self):
        import paddle_tpu

        out = paddle_tpu.dist(pt.to_tensor(np.array([1.0, 2.0])),
                              pt.to_tensor(np.array([1.0, 4.0])), p=2)
        np.testing.assert_allclose(float(out), 2.0)

    def test_all_reduce_scalar_identity(self):
        _require8()
        dist.init_mesh({"data": 8})
        s = pt.to_tensor(np.float32(3.5))
        out = dist.all_reduce(s)
        np.testing.assert_allclose(float(out), 3.5)

    def test_all_reduce_prod_negative(self):
        _require8()
        dist.init_mesh({"data": 8})
        vals = np.array([-2, -2, 1, 1, 1, 1, 1, 1], "float32")
        out = dist.all_reduce(pt.to_tensor(vals), op=dist.ReduceOp.PROD)
        np.testing.assert_allclose(out.numpy(), np.full(8, 4.0), rtol=1e-4)

    def test_all_gather_eager_identity_and_list(self):
        _require8()
        dist.init_mesh({"data": 8})
        x = pt.to_tensor(np.arange(16, dtype="float32"))
        out = dist.all_gather(x)
        np.testing.assert_allclose(out.numpy(), np.arange(16))
        parts = []
        dist.all_gather(parts, tensor=x)
        assert len(parts) == 8 and parts[0].shape == [2]

    def test_scatter(self):
        _require8()
        dist.init_mesh({"data": 8})
        chunks = [pt.to_tensor(np.full(2, float(i), "float32"))
                  for i in range(8)]
        out = dist.scatter(pt.zeros([16]), tensor_list=chunks, src=0)
        np.testing.assert_allclose(out.numpy(),
                                   np.repeat(np.arange(8.0), 2))

    def test_sharded_opt_state(self):
        _require8()
        mesh = dist.init_mesh({"data": 8})
        m = nn.Linear(16, 8)
        o = optim.Adam(0.01, parameters=m.parameters())
        s = dist.DistributedTrainStep(m, o,
                                      lambda mm, x, y: F.mse_loss(mm(x), y),
                                      mesh=mesh, shard_opt_state=True)
        st = o._accumulators[m.weight.name]
        assert "data" in str(st["moment1"].sharding.spec)
        x = np.random.randn(16, 16).astype("float32")
        y = np.random.randn(16, 8).astype("float32")
        l0 = float(s(x, y))
        for _ in range(3):
            l1 = float(s(x, y))
        assert l1 < l0


class TestEagerCollectiveShapes:
    """VERDICT r1 item 9: non-divisible eager collectives must raise, not
    silently return the input unreduced."""

    def test_odd_leading_dim_raises(self):
        from paddle_tpu.dist import env as denv
        from paddle_tpu.dist import collective as C

        mesh = denv.init_mesh({"data": 8})
        try:
            x = pt.to_tensor(np.arange(9, dtype="float32"))
            with pytest.raises(ValueError, match="not divisible"):
                C.all_reduce(x)
        finally:
            denv.set_mesh(None)

    def test_scalar_is_identity(self):
        from paddle_tpu.dist import env as denv
        from paddle_tpu.dist import collective as C

        mesh = denv.init_mesh({"data": 8})
        try:
            x = pt.to_tensor(np.float32(3.5))
            out = C.all_reduce(x)
            assert float(out.numpy()) == 3.5
        finally:
            denv.set_mesh(None)

    def test_divisible_reduces(self):
        from paddle_tpu.dist import env as denv
        from paddle_tpu.dist import collective as C

        mesh = denv.init_mesh({"data": 8})
        try:
            x = pt.to_tensor(np.arange(8, dtype="float32"))
            out = C.all_reduce(x)
            np.testing.assert_allclose(out.numpy(), np.full(8, 28.0))
        finally:
            denv.set_mesh(None)


class TestGPTPipeline:
    """The pp leg of the 4D flagship: real GPT blocks through the GPipe
    schedule, parity vs the sequential model (SURVEY §2 #23/#38)."""

    def _model(self, layers=4):
        from paddle_tpu.models.nlp.gpt import GPT, gpt_tiny

        pt.seed(0)
        cfg = gpt_tiny(dropout=0.0)
        cfg.layers = layers
        return GPT(cfg)

    def test_forward_parity_pp2(self):
        _require8()
        from paddle_tpu.models.nlp.gpt import GPTPipeline

        model = self._model(layers=4)  # 2 blocks per stage
        model.eval()
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pipe",))
        dist.set_mesh(mesh)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, model.cfg.vocab_size, (4, 16)).astype("int64")
        try:
            with mesh:
                pipe = GPTPipeline(model, num_microbatches=2)
                got = np.asarray(pipe(pt.to_tensor(ids)).numpy())
        finally:
            dist.set_mesh(None)
        want = np.asarray(model(pt.to_tensor(ids)).numpy())
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_forward_parity_dp2_pp2(self):
        _require8()
        from paddle_tpu.models.nlp.gpt import GPTPipeline

        model = self._model(layers=2)
        model.eval()
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("data", "pipe"))
        dist.set_mesh(mesh)
        rng = np.random.RandomState(1)
        ids = rng.randint(0, model.cfg.vocab_size, (4, 16)).astype("int64")
        try:
            with mesh:
                pipe = GPTPipeline(model, num_microbatches=2,
                                   batch_axis="data")
                got = np.asarray(pipe(pt.to_tensor(ids)).numpy())
        finally:
            dist.set_mesh(None)
        want = np.asarray(model(pt.to_tensor(ids)).numpy())
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_train_step_loss_decreases_pp2(self):
        _require8()
        from paddle_tpu.models.nlp.gpt import GPTPipeline

        model = self._model(layers=2)
        model.eval()
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pipe",))
        dist.set_mesh(mesh)
        rng = np.random.RandomState(2)
        ids = rng.randint(0, model.cfg.vocab_size, (4, 16)).astype("int64")
        labels = np.roll(ids, -1, axis=1)
        try:
            with mesh:
                pipe = GPTPipeline(model, num_microbatches=2)
                step = jax.jit(pipe.train_step_fn(lr=1e-1))
                stacked = pipe.stacked
                losses = []
                for _ in range(4):
                    loss, stacked = step(stacked, jnp.asarray(ids),
                                         jnp.asarray(labels))
                    losses.append(float(loss))
        finally:
            dist.set_mesh(None)
        assert np.isfinite(losses).all(), losses
        assert losses[-1] < losses[0], losses

    def test_forward_parity_dp2_tp2_pp2(self):
        """The composed 3-axis flagship (VERDICT r4 Next #3): TP-layer
        blocks inside the GPipe schedule over Mesh(('data','model','pipe'))
        — 'model' stays an auto (GSPMD) axis inside the manual
        shard_map, so the same executable carries dp + tp + pp."""
        _require8()
        from paddle_tpu.models.nlp.gpt import GPTPipeline

        model = self._model(layers=2)
        model.eval()
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                    ("data", "model", "pipe"))
        dist.set_mesh(mesh)
        rng = np.random.RandomState(3)
        ids = rng.randint(0, model.cfg.vocab_size, (4, 16)).astype("int64")
        try:
            with mesh:
                pipe = GPTPipeline(model, num_microbatches=2,
                                   batch_axis="data")
                got = np.asarray(pipe(pt.to_tensor(ids)).numpy())
        finally:
            dist.set_mesh(None)
        want = np.asarray(model(pt.to_tensor(ids)).numpy())
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_train_step_dp2_tp2_pp2_one_executable(self):
        """One jitted dp2 x tp2 x pp2 train step: loss decreases AND the
        compiled HLO really carries both parallelism mechanisms —
        collective-permute (the pp ring) and all-reduce (tp partial sums
        / dp grad sync)."""
        _require8()
        from paddle_tpu.models.nlp.gpt import GPTPipeline

        model = self._model(layers=2)
        model.eval()
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                    ("data", "model", "pipe"))
        dist.set_mesh(mesh)
        rng = np.random.RandomState(4)
        ids = rng.randint(0, model.cfg.vocab_size, (4, 16)).astype("int64")
        labels = np.roll(ids, -1, axis=1)
        try:
            with mesh:
                pipe = GPTPipeline(model, num_microbatches=2,
                                   batch_axis="data")
                step = jax.jit(pipe.train_step_fn(lr=1e-1))
                txt = step.lower(pipe.stacked, jnp.asarray(ids),
                                 jnp.asarray(labels)).compile().as_text()
                assert "collective-permute" in txt, "pp ring missing"
                assert "all-reduce" in txt, "tp/dp reductions missing"
                stacked = pipe.stacked
                losses = []
                for _ in range(4):
                    loss, stacked = step(stacked, jnp.asarray(ids),
                                         jnp.asarray(labels))
                    losses.append(float(loss))
        finally:
            dist.set_mesh(None)
        assert np.isfinite(losses).all(), losses
        assert losses[-1] < losses[0], losses

    def test_uneven_layers_raise(self):
        _require8()
        from paddle_tpu.models.nlp.gpt import GPTPipeline

        model = self._model(layers=3)  # 3 layers on 2 stages
        model.eval()
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pipe",))
        dist.set_mesh(mesh)
        ids = np.zeros((2, 8), "int64")
        try:
            with mesh, pytest.raises(AssertionError):
                GPTPipeline(model, num_microbatches=2)(pt.to_tensor(ids))
        finally:
            dist.set_mesh(None)


class TestAllToAllAttention:
    """Ulysses-style sequence parallelism (dist/ulysses.py): a2a to head
    sharding, local dense attention, a2a back — must match dense."""

    def test_matches_dense(self):
        _require8()
        mesh = dist.init_mesh({"sp": 8})
        rng = np.random.RandomState(5)
        q = rng.randn(2, 8, 32, 16).astype("float32")
        k = rng.randn(2, 8, 32, 16).astype("float32")
        v = rng.randn(2, 8, 32, 16).astype("float32")
        out = dist.all_to_all_attention(pt.to_tensor(q), pt.to_tensor(k),
                                        pt.to_tensor(v), axis_name="sp")
        dense = F.sdpa_bhld(pt.to_tensor(q), pt.to_tensor(k),
                            pt.to_tensor(v))
        np.testing.assert_allclose(out.numpy(), dense.numpy(), rtol=2e-3,
                                   atol=2e-3)

    def test_causal_and_grads(self):
        _require8()
        mesh = dist.init_mesh({"sp": 8})
        q = pt.to_tensor(np.random.RandomState(6)
                         .randn(1, 8, 16, 8).astype("float32"),
                         stop_gradient=False)
        out = dist.all_to_all_attention(q, q, q, axis_name="sp",
                                        causal=True)
        dense = F.sdpa_bhld(q, q, q, is_causal=True)
        np.testing.assert_allclose(out.numpy(), dense.numpy(), rtol=2e-3,
                                   atol=2e-3)
        pt.mean(out).backward()
        assert q.grad is not None and np.isfinite(q.grad.numpy()).all()

    def test_grads_match_dense(self):
        """VALUE parity of the backward through both all-to-alls (same
        rationale as the ring grad-parity test)."""
        _require8()
        mesh = dist.init_mesh({"sp": 8})
        rng = np.random.RandomState(7)
        qa = rng.randn(1, 8, 32, 8).astype("float32")
        ka = rng.randn(1, 8, 32, 8).astype("float32")
        va = rng.randn(1, 8, 32, 8).astype("float32")
        w = rng.randn(1, 8, 32, 8).astype("float32")

        def grads(attn_fn, **kw):
            q = pt.to_tensor(qa, stop_gradient=False)
            k = pt.to_tensor(ka, stop_gradient=False)
            v = pt.to_tensor(va, stop_gradient=False)
            out = attn_fn(q, k, v, **kw)
            (out * pt.to_tensor(w)).sum().backward()
            return [t.grad.numpy() for t in (q, k, v)]

        a2a = grads(lambda q, k, v, **kw: dist.all_to_all_attention(
            q, k, v, axis_name="sp", **kw), causal=True)
        dense = grads(F.sdpa_bhld, is_causal=True)
        for g_a, g_d, name in zip(a2a, dense, "qkv"):
            np.testing.assert_allclose(
                g_a, g_d, rtol=2e-3, atol=2e-3,
                err_msg=f"d{name} diverges between a2a and dense")

    def test_head_divisibility_error(self):
        _require8()
        mesh = dist.init_mesh({"sp": 8})
        q = pt.to_tensor(np.random.randn(1, 4, 16, 8).astype("float32"))
        try:
            dist.all_to_all_attention(q, q, q, axis_name="sp")
            raise AssertionError("expected ValueError")
        except ValueError as e:
            assert "divisible" in str(e)

    def test_no_mesh_fallback(self):
        q = pt.to_tensor(np.random.randn(1, 2, 8, 4).astype("float32"))
        out = dist.all_to_all_attention(q, q, q)
        dense = F.sdpa_bhld(q, q, q)
        np.testing.assert_allclose(out.numpy(), dense.numpy(), rtol=1e-5)


class TestShardedFusedDecode:
    def test_tp_sharded_generate_xla_parity(self):
        """The single-executable GPT decode under a ('data','model')
        mesh (tensor-parallel serving) must produce the same tokens as
        the unsharded decode — GSPMD shards the QKV/FFN projections per
        the Column/RowParallel constraints inside the one executable."""
        _require8()
        from paddle_tpu.models.nlp.gpt import GPT, gpt_tiny

        cfg = gpt_tiny(dropout=0.0)
        pt.seed(7)
        model = GPT(cfg)
        model.eval()
        ids = np.random.RandomState(4).randint(
            0, cfg.vocab_size, (2, 8)).astype("int64")
        base = np.asarray(model.generate_xla(
            ids, max_new_tokens=6, temperature=0.0).numpy())
        mesh = dist.init_mesh({"data": 2, "model": 4})
        try:
            with mesh:
                sharded = np.asarray(model.generate_xla(
                    ids, max_new_tokens=6, temperature=0.0).numpy())
        finally:
            dist.set_mesh(None)
        np.testing.assert_array_equal(base, sharded)
        # mesh is part of the executable identity: two cache entries
        assert len(model._xla_gen_cache) == 2
