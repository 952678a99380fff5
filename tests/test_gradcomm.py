"""Comm-efficient data parallelism: bucketed, accumulated, quantized
gradient all-reduce (``dist.gradcomm``, ISSUE 9).

The reference's DataParallel coalesces per-parameter NCCL all-reduces
into ``comm_buffer_size``-MB flat buffers and its DGC/fp16 strategies
compress the payload; EQuARX (arXiv:2506.17615) quantizes the ring
all-reduce itself with error feedback. Here the exchange is explicit
jax code over per-device local gradient partials (see
dist/gradcomm.py), spanning both execution paths:

- static: ``CompiledProgram.with_data_parallel(comm_options=...)``
- eager: ``DistributedTrainStep(..., comm_options=...)`` /
  ``DataParallel(layer, comm_buffer_size=...)``

Acceptance (all CPU-runnable on the 8-fake-device mesh): bucketing
strictly reduces all-reduce op counts vs the per-parameter baseline,
int8 cuts gradient wire bytes ~4x, fp32 bucketed matches the implicit
path BITWISE on the MLP (conv models: 1e-5 — XLA orders conv partial
sums differently between the vmapped and sharded programs), int8 stays
within 5% loss-trajectory tolerance over 20 LeNet steps, and
error-feedback residuals survive checkpoint round-trips.
"""
import importlib.util
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
import paddle_tpu.fluid as fluid
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import distributed as dist
from paddle_tpu import optim
from paddle_tpu.dist import gradcomm as gc
from paddle_tpu.dist.gradcomm import CommOptions

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _require8():
    if jax.device_count() < 8:
        pytest.skip("needs 8 virtual devices")


@pytest.fixture
def static_mode():
    # fresh scope per test: @comm@* exchange state (EF residuals, the
    # stochastic-rounding counter) lives in the scope and must not leak
    # between tests
    pt.enable_static()
    with fluid.scope_guard(fluid.Scope()):
        yield
    pt.disable_static()


@pytest.fixture(autouse=True)
def _mesh_reset():
    yield
    dist.set_mesh(None)


def _entry_profile(exe, entry=None):
    from paddle_tpu.obs import spmd

    pg = _load_tool("perf_gate")
    if entry is None:
        entry = next(iter(exe._cache.values()))
    hlo = pg.entry_hlo(entry)
    assert hlo is not None
    return spmd.collective_profile(
        hlo, mesh=(entry.mesh_axes, entry.mesh_device_ids)), hlo


def _all_reduced_arrays(hlo):
    """How many arrays the entry all-reduces, however XLA groups them: its
    combiner may carry several in one tuple-shaped instruction (XLA:CPU
    puts a small model's into one on some machines and not on others), so
    the instructions are the compiler's count and the operands the
    program's."""
    return sum(m.group(1).count("%") for m in re.finditer(
        r" all-reduce(?:-start)?\(([^)]*)\)", hlo))


# -- bucket planning (pure host logic) ---------------------------------------


class TestBucketPlan:
    def test_size_bounded_buckets(self):
        # 3 x 256B f32 grads under a 512B cap -> [2-member, 1-member]
        entries = [(f"g{i}", (64,), np.float32) for i in range(3)]
        plan = gc.plan_buckets(
            entries, CommOptions(bucket_bytes=512, last_bucket_bytes=512),
            ndev=8)
        assert [b.names for b in plan.buckets] == [("g0", "g1"), ("g2",)]
        assert plan.buckets[0].offsets == (0, 64)
        assert plan.buckets[0].numel == 128

    def test_first_bucket_uses_last_cap(self):
        # the reference's last_comm_buffer_size: a small FIRST bucket
        # gets the earliest-ready grads onto the wire sooner
        entries = [(f"g{i}", (64,), np.float32) for i in range(4)]
        plan = gc.plan_buckets(
            entries, CommOptions(bucket_bytes=768, last_bucket_bytes=256),
            ndev=8)
        assert plan.buckets[0].names == ("g0",)
        assert plan.buckets[1].names == ("g1", "g2", "g3")

    def test_param_larger_than_cap_gets_own_bucket(self):
        entries = [("small", (8,), np.float32),
                   ("huge", (1024,), np.float32),
                   ("tail", (8,), np.float32)]
        plan = gc.plan_buckets(
            entries, CommOptions(bucket_bytes=256, last_bucket_bytes=64),
            ndev=8)
        assert [b.names for b in plan.buckets] == \
            [("small",), ("huge",), ("tail",)]
        # never split: the huge grad is one contiguous member
        assert plan.buckets[1].numel == 1024

    def test_exactly_full_bucket_closes(self):
        # two grads summing exactly to the cap share a bucket; the next
        # opens a fresh one (boundary: == cap, not > cap)
        entries = [("a", (32,), np.float32), ("b", (32,), np.float32),
                   ("c", (1,), np.float32)]
        plan = gc.plan_buckets(
            entries, CommOptions(bucket_bytes=256, last_bucket_bytes=256),
            ndev=8)
        assert [b.names for b in plan.buckets] == [("a", "b"), ("c",)]
        # padding: 1 element padded up to the 8-device multiple
        assert plan.buckets[1].numel == 1
        assert plan.buckets[1].padded == 8

    def test_flatten_unflatten_roundtrip(self):
        entries = [("a", (2, 3), np.float32), ("b", (5,), np.float32)]
        plan = gc.plan_buckets(
            entries, CommOptions(bucket_bytes=1 << 20), ndev=4)
        rng = np.random.RandomState(0)
        locals_ = {"a": jnp.asarray(rng.randn(4, 2, 3), jnp.float32),
                   "b": jnp.asarray(rng.randn(4, 5), jnp.float32)}
        flats = plan.flatten_local(locals_)
        assert flats[0].shape == (4, plan.buckets[0].padded)
        out = plan.unflatten([f.sum(0) for f in flats])
        np.testing.assert_allclose(
            np.asarray(out["a"]), np.asarray(locals_["a"].sum(0)),
            rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(out["b"]), np.asarray(locals_["b"].sum(0)),
            rtol=1e-6)

    def test_option_validation(self):
        with pytest.raises(ValueError):
            CommOptions(bucket_bytes=0)
        with pytest.raises(ValueError):
            CommOptions(accumulate_steps=0)
        with pytest.raises(ValueError):
            CommOptions(quantize="fp8")
        with pytest.raises(ValueError):
            CommOptions(gradient_scale="median")

    def test_hash_uniform_deterministic_and_centered(self):
        a = gc.hash_uniform((1024,), jnp.uint32(7))
        b = gc.hash_uniform((1024,), jnp.uint32(7))
        c = gc.hash_uniform((1024,), jnp.uint32(8))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))
        x = np.asarray(a)
        assert x.min() >= -0.5 and x.max() < 0.5
        assert abs(x.mean()) < 0.05  # unbiased rounding noise


# -- static path -------------------------------------------------------------


def _mlp_program(lr=0.1, batch=16):
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[batch, 8])
        y = fluid.data(name="y", shape=[batch, 1])
        h = fluid.layers.fc(x, size=16, act="relu")
        out = fluid.layers.fc(h, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(out, y))
        fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    return prog, startup, loss


def _train_static(comm, steps=6, batch=16, seed=0):
    pt.seed(0)
    prog, startup, loss = _mlp_program(batch=batch)
    c = fluid.CompiledProgram(prog).with_data_parallel(
        loss_name=loss.name, comm_options=comm)
    exe = fluid.Executor()
    exe.run(startup)
    rng = np.random.RandomState(seed)
    losses = []
    for _ in range(steps):
        xb = rng.randn(batch, 8).astype(np.float32)
        yb = rng.randn(batch, 1).astype(np.float32)
        (lv,) = exe.run(c, feed={"x": xb, "y": yb}, fetch_list=[loss])
        losses.append(float(np.asarray(lv)))
    return losses, exe, prog


class TestStaticComm:
    def test_fp32_bucketed_bitwise_vs_implicit(self, static_mode):
        """The acceptance pin: the explicit bucketed exchange performs
        the same per-element partial-sum additions GSPMD's implicit
        all-reduce does, so the MLP loss trajectory matches BITWISE."""
        _require8()
        base, _, _ = _train_static(None)
        buck, exe, _ = _train_static(CommOptions())
        assert base == buck, (base, buck)
        _, hlo = _entry_profile(exe)
        # 4 params + 1 loss mean implicit -> 1 bucket + 1 loss explicit
        assert _all_reduced_arrays(hlo) == 2

    def test_bucketed_strictly_fewer_all_reduces(self, static_mode):
        _require8()
        _, exe0, _ = _train_static(None, steps=1)
        _, exe1, _ = _train_static(CommOptions(), steps=1)
        _, hlo0 = _entry_profile(exe0)
        _, hlo1 = _entry_profile(exe1)
        assert _all_reduced_arrays(hlo1) < _all_reduced_arrays(hlo0) == 5

    def test_int8_within_tolerance_and_ef_state(self, static_mode):
        _require8()
        base, _, _ = _train_static(None)
        q, exe, _ = _train_static(CommOptions(quantize="int8"))
        np.testing.assert_allclose(q, base, rtol=0.05, atol=0.02)
        # EF residual + rounding counter live as @comm@* persistables
        scope = fluid.global_scope()
        resid = scope.find_var(gc.EF_PREFIX + "0")
        assert resid is not None and resid.shape[0] == 8
        assert int(np.asarray(scope.find_var(gc.STEP_VAR))) == 6
        prof, _ = _entry_profile(exe)
        assert prof["quant_wire_bytes"] > 0

    def test_cache_key_carries_comm_axis(self, static_mode):
        _require8()
        pt.seed(0)
        prog, startup, loss = _mlp_program()
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(16, 8).astype(np.float32),
                "y": rng.randn(16, 1).astype(np.float32)}
        for comm in (None, CommOptions()):
            c = fluid.CompiledProgram(prog).with_data_parallel(
                loss_name=loss.name, comm_options=comm)
            exe.run(c, feed=feed, fetch_list=[loss])
        comms = {k.comm for k in exe._cache
                 if k.program_uid == prog._uid}
        assert comms == {None, CommOptions().cache_axis()}

    def test_accumulate_matches_double_batch(self, static_mode):
        """accumulate_steps=2 over batch-B microbatches == one exchange
        of the mean gradient over 2B samples: the trajectory must match
        implicit DP fed the concatenated 2B batches (the reference's
        gradient-merge semantics)."""
        _require8()
        rng = np.random.RandomState(3)
        xs = rng.randn(4, 16, 8).astype(np.float32)
        ys = rng.randn(4, 16, 1).astype(np.float32)

        # baseline: 2 implicit-DP steps on the concatenated batches
        pt.seed(0)
        prog, startup, loss = _mlp_program(batch=32)
        c = fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        exe = fluid.Executor()
        exe.run(startup)
        ref = []
        for w in range(2):
            xb = np.concatenate(xs[2 * w:2 * w + 2])
            yb = np.concatenate(ys[2 * w:2 * w + 2])
            (lv,) = exe.run(c, feed={"x": xb, "y": yb},
                            fetch_list=[loss])
            ref.append(float(np.asarray(lv)))

        # fused window K=4, exchange once per N=2 microbatches
        pt.seed(0)
        prog2, startup2, loss2 = _mlp_program(batch=16)
        c2 = fluid.CompiledProgram(prog2).with_data_parallel(
            loss_name=loss2.name,
            comm_options=CommOptions(accumulate_steps=2))
        exe2 = fluid.Executor()
        exe2.run(startup2)
        (traj,) = exe2.run_steps(c2, feeds={"x": xs, "y": ys},
                                 fetch_list=[loss2], steps=4)
        traj = np.asarray(traj).ravel()
        assert traj.shape == (4,)
        # per-microbatch losses of window w average to the 2B-batch loss
        np.testing.assert_allclose(
            [traj[0:2].mean(), traj[2:4].mean()], ref, rtol=1e-5)
        # exactly one compiled dispatch for the whole K=4 window
        assert exe2.dispatches == 1

    def test_accumulate_requires_fused_path(self, static_mode):
        _require8()
        pt.seed(0)
        prog, startup, loss = _mlp_program()
        c = fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name,
            comm_options=CommOptions(accumulate_steps=2))
        exe = fluid.Executor()
        exe.run(startup)
        feed = {"x": np.zeros((16, 8), np.float32),
                "y": np.zeros((16, 1), np.float32)}
        with pytest.raises(ValueError, match="fused path"):
            exe.run(c, feed=feed, fetch_list=[loss])

    def test_accumulate_must_divide_window(self, static_mode):
        _require8()
        pt.seed(0)
        prog, startup, loss = _mlp_program()
        c = fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name,
            comm_options=CommOptions(accumulate_steps=2))
        exe = fluid.Executor()
        exe.run(startup)
        feeds = [{"x": np.zeros((16, 8), np.float32),
                  "y": np.zeros((16, 1), np.float32)}] * 3
        with pytest.raises(ValueError, match="divide"):
            exe.run_steps(c, feeds=feeds, fetch_list=[loss])

    def test_ef_residuals_survive_checkpoint_roundtrip(self, static_mode):
        _require8()
        q, exe, prog = _train_static(CommOptions(quantize="int8"), steps=3)
        scope = fluid.global_scope()
        resid = np.asarray(scope.find_var(gc.EF_PREFIX + "0"))
        assert np.abs(resid).max() > 0  # quantization left real error
        import tempfile

        from paddle_tpu.framework import io as fio

        with tempfile.TemporaryDirectory() as d:
            fio.save_persistables(exe, d, main_program=prog)
            scope.set(gc.EF_PREFIX + "0", jnp.zeros_like(resid))
            scope.set(gc.STEP_VAR, jnp.int32(0))
            fio.load_persistables(exe, d, main_program=prog)
            np.testing.assert_array_equal(
                np.asarray(scope.find_var(gc.EF_PREFIX + "0")), resid)
            assert int(np.asarray(scope.find_var(gc.STEP_VAR))) == 3


# -- the LeNet acceptance gate (ISSUE 9) -------------------------------------


def _lenet_train(comm, steps=20, B=8):
    pt.seed(0)
    from paddle_tpu.models.vision import LeNet

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = pt.static.data("x", [B, 1, 28, 28], "float32")
        y = pt.static.data("y", [B], "int64")
        loss = F.cross_entropy(LeNet()(x), y)
        optim.Momentum(0.02, 0.9).minimize(loss)
    c = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, comm_options=comm)
    exe = fluid.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(steps):
        feed = {"x": rng.randn(B, 1, 28, 28).astype(np.float32),
                "y": rng.randint(0, 10, (B,)).astype(np.int64)}
        (lv,) = exe.run(c, feed=feed, fetch_list=[loss])
        losses.append(float(np.asarray(lv)))
    return losses, exe


class TestLeNetAcceptance:
    def test_bucketed_and_int8_acceptance(self, static_mode):
        """The ISSUE 9 acceptance bundle on the 8-fake-device
        with_data_parallel LeNet: strictly fewer all-reduce ops
        bucketed, ~4x lower gradient wire bytes int8, and both loss
        trajectories within tolerance over 20 steps (fp32 at 1e-5 —
        conv partial-sum order differs between the vmapped and sharded
        programs; the MLP pin above is bitwise — int8 at 5%)."""
        _require8()
        base, exe0 = _lenet_train(None)
        buck, exe1 = _lenet_train(CommOptions())
        quant, exe2 = _lenet_train(CommOptions(quantize="int8"))

        p0, _ = _entry_profile(exe0)
        p1, _ = _entry_profile(exe1)
        p2, _ = _entry_profile(exe2)
        # 10 LeNet params + loss mean -> 11+ implicit all-reduces;
        # bucketed: 1 bucket + loss. STRICTLY fewer, per CollectiveProfile
        assert p1["counts"]["all-reduce"] < p0["counts"]["all-reduce"]
        assert p1["n_ops"] < p0["n_ops"]
        # int8: ~4x lower gradient-exchange wire bytes (the s8 payload
        # rides all-to-all + all-gather; scales and the f32 loss
        # all-reduce are the small remainder)
        ratio = p0["wire_bytes"] / p2["wire_bytes"]
        assert 3.3 < ratio < 4.5, (p0["wire_bytes"], p2["wire_bytes"])
        assert p2["quant_wire_bytes"] > 0.9 * p2["wire_bytes"]

        np.testing.assert_allclose(buck, base, rtol=1e-5)
        np.testing.assert_allclose(quant, base, rtol=0.05, atol=0.02)

    def test_multi_bucket_overlap_structure(self, static_mode):
        """Reverse-topological bucketing, proven structurally: caps that
        force several buckets give the program one exchange a bucket, the
        small first one for the gradients the backward makes first. Where
        XLA keeps them apart, every bucket's all-reduce except the tail is
        scheduled BEFORE later compute (perf_gate ``interleaved``) — the
        placement an async backend overlaps; where its combiner has put
        them into one instruction there is no placement to read."""
        _require8()
        pg = _load_tool("perf_gate")
        _, exe = _lenet_train(
            CommOptions(bucket_bytes=64 << 10, last_bucket_bytes=16 << 10),
            steps=1)
        entry = next(iter(exe._cache.values()))
        plan = entry.comm_plan
        assert plan.n_buckets >= 3
        assert 4 * plan.buckets[0].numel <= 16 << 10
        prof, hlo = _entry_profile(exe)
        assert _all_reduced_arrays(hlo) == plan.n_buckets + 1    # + loss
        if prof["counts"]["all-reduce"] >= 4:
            ov = pg.overlap_stats(hlo)
            assert ov["interleaved"] >= 2, ov
            # and the gate API agrees
            assert pg.check_entry(entry, min_interleaved=2) == []


# -- eager path --------------------------------------------------------------


class TestEagerComm:
    def _data(self):
        rng = np.random.RandomState(0)
        X = rng.randn(32, 8).astype("float32")
        Y = (X @ rng.randn(8, 1)).astype("float32")
        return X, Y

    def _build(self):
        # unique_name.guard(): identical param names across builds, so
        # optimizer.state_dict() maps onto a freshly built model (the
        # reference's resume idiom — Adam moments + EF residuals are
        # keyed by parameter name)
        pt.seed(5)
        with pt.utils.unique_name.guard():
            m = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                              nn.Linear(16, 1))
            o = optim.Adam(0.05, parameters=m.parameters())
        return m, o

    @staticmethod
    def _loss(m, x, y):
        return F.mse_loss(m(x), y)

    def test_fp32_matches_implicit(self):
        _require8()
        X, Y = self._data()
        mesh = dist.init_mesh({"data": 8})
        m0, o0 = self._build()
        s0 = dist.DistributedTrainStep(m0, o0, self._loss, mesh=mesh)
        base = [float(s0(X, Y)) for _ in range(5)]
        m1, o1 = self._build()
        s1 = dist.DistributedTrainStep(m1, o1, self._loss, mesh=mesh,
                                       comm_options=CommOptions())
        got = [float(s1(X, Y)) for _ in range(5)]
        np.testing.assert_allclose(got, base, rtol=1e-4)
        prof = s1.collective_profile()
        assert prof is not None and prof["counts"]["all-reduce"] <= 2

    def test_dataparallel_wrapper_knobs_are_live(self):
        """The reference's comm_buffer_size on DataParallel now
        configures real bucketing (MIGRATING note)."""
        _require8()
        X, Y = self._data()
        mesh = dist.init_mesh({"data": 8})
        m0, o0 = self._build()
        s0 = dist.DistributedTrainStep(m0, o0, self._loss, mesh=mesh)
        base = [float(s0(X, Y)) for _ in range(3)]
        m1, o1 = self._build()
        w = dist.DataParallel(m1, comm_buffer_size=1)
        assert w.comm_options is not None
        assert w.comm_options.bucket_bytes == 1 << 20
        s1 = dist.DistributedTrainStep(w, o1, self._loss, mesh=mesh)
        got = [float(s1(X, Y)) for _ in range(3)]
        np.testing.assert_allclose(got, base, rtol=1e-4)

    def test_int8_checkpoint_roundtrip_continuity(self):
        """EF residuals ride optimizer.state_dict(): an interrupted
        int8 run restored from the checkpoint must continue EXACTLY as
        the uninterrupted one (the residual carries the rounding error
        of every past step)."""
        _require8()
        X, Y = self._data()
        mesh = dist.init_mesh({"data": 8})
        opts = CommOptions(quantize="int8")

        m0, o0 = self._build()
        s0 = dist.DistributedTrainStep(m0, o0, self._loss, mesh=mesh,
                                       comm_options=opts)
        unbroken = [float(s0(X, Y)) for _ in range(5)]

        m1, o1 = self._build()
        s1 = dist.DistributedTrainStep(m1, o1, self._loss, mesh=mesh,
                                       comm_options=opts)
        first = [float(s1(X, Y)) for _ in range(3)]
        mstate = {k: np.asarray(v) for k, v in m1.state_dict().items()}
        ostate = o1.state_dict()
        assert any(k.startswith(gc.EF_PREFIX) for k in ostate)
        assert int(ostate[gc.STEP_VAR + ".count"]) == 3

        m2, o2 = self._build()
        m2.set_state_dict(mstate)
        o2.set_state_dict(ostate)
        s2 = dist.DistributedTrainStep(m2, o2, self._loss, mesh=mesh,
                                       comm_options=opts)
        resumed = first + [float(s2(X, Y)) for _ in range(2)]
        np.testing.assert_allclose(resumed, unbroken, rtol=1e-5)

    def test_run_fused_accumulate(self):
        """run_fused with accumulate_steps=2: the exchange fires once
        per 2 microbatches inside the scan; the trajectory matches the
        N=1 comm step fed the concatenated 2B batches."""
        _require8()
        X, Y = self._data()
        rng = np.random.RandomState(7)
        Xs = np.stack([X, rng.randn(32, 8).astype("float32"),
                       X + 0.1, X - 0.1])
        Ys = np.stack([Y, (Xs[1] @ np.ones((8, 1), "float32")),
                       Y + 0.1, Y - 0.1])
        mesh = dist.init_mesh({"data": 8})

        m0, o0 = self._build()
        s0 = dist.DistributedTrainStep(m0, o0, self._loss, mesh=mesh,
                                       comm_options=CommOptions())
        ref = []
        for w in range(2):
            xb = np.concatenate(Xs[2 * w:2 * w + 2])
            yb = np.concatenate(Ys[2 * w:2 * w + 2])
            ref.append(float(s0(xb, yb)))

        m1, o1 = self._build()
        s1 = dist.DistributedTrainStep(
            m1, o1, self._loss, mesh=mesh,
            comm_options=CommOptions(accumulate_steps=2))
        losses = np.asarray(s1.run_fused([Xs, Ys], steps=4)._data).ravel()
        assert losses.shape == (4,)
        np.testing.assert_allclose(
            [losses[0:2].mean(), losses[2:4].mean()], ref, rtol=1e-4)
        # the params ended at the same point: one more identical update
        # on each side (a 2-microbatch window vs the concatenated batch)
        # must produce the same loss
        more = np.asarray(
            s1.run_fused([np.stack([X, X]), np.stack([Y, Y])],
                         steps=2)._data).ravel()
        np.testing.assert_allclose(
            more.mean(),
            float(s0(np.concatenate([X, X]), np.concatenate([Y, Y]))),
            rtol=1e-4)

    def test_accumulate_rejects_per_step_call(self):
        _require8()
        X, Y = self._data()
        mesh = dist.init_mesh({"data": 8})
        m, o = self._build()
        s = dist.DistributedTrainStep(
            m, o, self._loss, mesh=mesh,
            comm_options=CommOptions(accumulate_steps=2))
        with pytest.raises(ValueError, match="fused path"):
            s(X, Y)
        with pytest.raises(ValueError, match="divide"):
            s.run_fused([np.stack([X] * 3), np.stack([Y] * 3)], steps=3)

    def test_comm_requires_pure_dp_mesh(self):
        _require8()
        mesh = dist.init_mesh({"data": 2, "model": 4})
        m, o = self._build()
        with pytest.raises(ValueError, match="pure data-parallel"):
            dist.DistributedTrainStep(m, o, self._loss, mesh=mesh,
                                      comm_options=CommOptions())

    def test_unreached_param_update_skipped(self):
        """Params the backward never touches exchange zeros (static
        bucket layout) but must SKIP the optimizer update like the
        non-comm path — AdamW weight decay on a zero grad would
        silently shrink them."""
        _require8()
        X, Y = self._data()
        mesh = dist.init_mesh({"data": 8})
        pt.seed(5)
        with pt.utils.unique_name.guard():
            m = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                              nn.Linear(16, 1))
            unused = nn.Linear(4, 4)
            o = optim.AdamW(0.05, parameters=list(m.parameters()) +
                            list(unused.parameters()), weight_decay=0.1)
        before = {k: np.asarray(v) for k, v in
                  unused.state_dict().items()}
        s = dist.DistributedTrainStep(m, o, self._loss, mesh=mesh,
                                      models=[m, unused],
                                      comm_options=CommOptions())
        for _ in range(3):
            s(X, Y)
        for k, v in unused.state_dict().items():
            np.testing.assert_array_equal(np.asarray(v), before[k])

    def test_wrapper_comm_falls_back_on_tp_mesh(self):
        """An inherited DataParallel comm_buffer_size on a layout the
        explicit exchange can't serve warns and falls back to implicit
        GSPMD (source compat); explicit comm_options still raises."""
        _require8()
        X, Y = self._data()
        mesh = dist.init_mesh({"data": 2, "model": 4})
        m, o = self._build()
        w = dist.DataParallel(m, comm_buffer_size=25)
        with pytest.warns(RuntimeWarning, match="falls back"):
            s = dist.DistributedTrainStep(w, o, self._loss, mesh=mesh)
        assert s._comm is None
        assert np.isfinite(float(s(X, Y)))

    def test_int8_rejects_grad_scaler(self):
        """EF residuals live in loss-scale units and an overflow would
        quantize inf into them — the combination is rejected up front."""
        _require8()
        from paddle_tpu.amp import GradScaler

        mesh = dist.init_mesh({"data": 8})
        m, o = self._build()
        with pytest.raises(ValueError, match="GradScaler"):
            dist.DistributedTrainStep(
                m, o, self._loss, mesh=mesh, scaler=GradScaler(),
                comm_options=CommOptions(quantize="int8"))

    def test_indivisible_batch_rejected(self):
        """A batch no feed can shard over the mesh must raise, not run
        the full batch redundantly on every device. (P('data')-placed
        batches already fail at device_put; replicated batch_specs are
        the path that would silently replicate the compute.)"""
        _require8()
        from jax.sharding import PartitionSpec as P

        mesh = dist.init_mesh({"data": 8})
        m, o = self._build()
        s = dist.DistributedTrainStep(m, o, self._loss, mesh=mesh,
                                      batch_specs=[P(), P()],
                                      comm_options=CommOptions())
        rng = np.random.RandomState(0)
        with pytest.raises(ValueError, match="leading dim divides"):
            s(rng.randn(12, 8).astype("float32"),
              rng.randn(12, 1).astype("float32"))


class TestSplitUpdateSegment:
    class _Op:
        def __init__(self, type_, ins=(), outs=()):
            self.type, self.input_names, self.output_names = \
                type_, list(ins), list(outs)

    def test_rejects_backward_after_update(self):
        """The docstring contract: a second minimize()'s backward ops
        landing after the first update segment is a hard error, not
        silently misplaced ops."""
        ops = [self._Op("fc", ["x"], ["h"]),
               self._Op("fc@grad", ["h"], ["w@GRAD"]),
               self._Op("optimize_sgd", ["w", "w@GRAD"], ["w"]),
               self._Op("fill_ones_like", ["loss2"], ["loss2@GRAD"]),
               self._Op("fc@grad", ["loss2@GRAD"], ["v@GRAD"]),
               self._Op("optimize_sgd", ["v", "v@GRAD"], ["v"])]
        with pytest.raises(ValueError, match="AFTER the first update"):
            gc.split_update_segment(ops)

    def test_accepts_single_minimize_shape(self):
        ops = [self._Op("fc", ["x"], ["h"]),
               self._Op("fc@grad", ["h"], ["w@GRAD"]),
               self._Op("optimize_sgd", ["w", "w@GRAD"], ["w"])]
        comp, upd, cross = gc.split_update_segment(ops)
        assert len(comp) == 2 and len(upd) == 1
        assert cross == ["w@GRAD"]


# -- dataset-driven fused loop (satellite) -----------------------------------


class TestTrainFromDatasetFused:
    def _files(self, tmp_path, n_files=2, rows=64, dim=4):
        rng = np.random.RandomState(0)
        W = rng.randn(dim).astype(np.float32)
        paths = []
        for i in range(n_files):
            xs = rng.randn(rows, dim).astype(np.float32)
            ys = (xs @ W > 0).astype(np.int64)
            p = str(tmp_path / f"part-{i}.txt")
            with open(p, "w") as f:
                for xr, yr in zip(xs, ys):
                    vals = " ".join(f"{v:.6f}" for v in xr)
                    f.write(f"{len(xr)} {vals} 1 {int(yr)}\n")
            paths.append(p)
        return paths

    def _program(self, batch, dim=4):
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup):
            x = fluid.data(name="x", shape=[batch, dim])
            y = fluid.data(name="y", shape=[batch], dtype="int64")
            logits = fluid.layers.fc(x, size=2)
            loss = F.cross_entropy(logits, y)
            fluid.optimizer.Adam(learning_rate=5e-2).minimize(loss)
        return prog, startup, x, y, loss

    def _dataset(self, paths, x, y, batch):
        ds = fluid.DatasetFactory().create_dataset()
        ds.set_use_var([x, y])
        ds.set_batch_size(batch)
        ds.set_filelist(paths)
        return ds

    def test_fused_matches_per_step(self, tmp_path, static_mode):
        """steps_per_dispatch=K drives run_steps windows straight from
        the DevicePrefetcher; the final state matches the per-step loop
        with FEWER dispatches."""
        paths = self._files(tmp_path)  # 128 rows -> 8 batches of 16
        pt.seed(0)
        prog, startup, x, y, loss = self._program(batch=16)
        exe = fluid.Executor()
        exe.run(startup)
        out = exe.train_from_dataset(program=prog, dataset=self._dataset(
            paths, x, y, 16), fetch_list=[loss], print_period=0)
        per_step_final = float(np.asarray(out[0]))
        per_step_dispatches = exe.dispatches

        pt.seed(0)
        prog2, startup2, x2, y2, loss2 = self._program(batch=16)
        exe2 = fluid.Executor()
        exe2.run(startup2)
        out2 = exe2.train_from_dataset(
            program=prog2, dataset=self._dataset(paths, x2, y2, 16),
            fetch_list=[loss2], print_period=0, steps_per_dispatch=4)
        stacked = np.asarray(out2[0])
        assert stacked.shape == (4,)
        np.testing.assert_allclose(float(stacked[-1]), per_step_final,
                                   rtol=1e-6)
        assert exe2.dispatches < per_step_dispatches

    def test_fused_with_comm_accumulation(self, tmp_path, static_mode):
        """The whole stack composes: dataset -> prefetcher -> fused
        window -> bucketed exchange firing once per 2 microbatches."""
        _require8()
        paths = self._files(tmp_path)
        pt.seed(0)
        prog, startup, x, y, loss = self._program(batch=16)
        c = fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name,
            comm_options=CommOptions(accumulate_steps=2))
        exe = fluid.Executor()
        exe.run(startup)
        out = exe.train_from_dataset(
            program=c, dataset=self._dataset(paths, x, y, 16),
            fetch_list=[loss], print_period=0, steps_per_dispatch=4)
        stacked = np.asarray(out[0])
        assert stacked.shape == (4,)
        assert np.isfinite(stacked).all()

    def test_accum_tail_runs_as_smaller_window(self, tmp_path,
                                               static_mode):
        """With accumulate_steps=N a ragged tail cannot fall back to
        per-step run() (it rejects accumulation); whole N-multiples run
        as one smaller fused window, the remainder is dropped with a
        warning."""
        _require8()
        # 96 rows -> 6 batches of 16: one K=4 window + a 2-batch tail
        paths = self._files(tmp_path, n_files=1, rows=96)
        pt.seed(0)
        prog, startup, x, y, loss = self._program(batch=16)
        c = fluid.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name,
            comm_options=CommOptions(accumulate_steps=2))
        exe = fluid.Executor()
        exe.run(startup)
        out = exe.train_from_dataset(
            program=c, dataset=self._dataset(paths, x, y, 16),
            fetch_list=[loss], print_period=0, steps_per_dispatch=4)
        assert np.asarray(out[0]).shape == (2,)  # the K=2 tail window
        assert exe.dispatches == 2

        # 80 rows -> 5 batches: the 1-batch remainder is dropped loudly
        paths = self._files(tmp_path, n_files=1, rows=80)
        pt.seed(0)
        prog2, startup2, x2, y2, loss2 = self._program(batch=16)
        c2 = fluid.CompiledProgram(prog2).with_data_parallel(
            loss_name=loss2.name,
            comm_options=CommOptions(accumulate_steps=2))
        exe2 = fluid.Executor()
        exe2.run(startup2)
        with pytest.warns(RuntimeWarning, match="whole N-microbatch"):
            exe2.train_from_dataset(
                program=c2, dataset=self._dataset(paths, x2, y2, 16),
                fetch_list=[loss2], print_period=0, steps_per_dispatch=4)

    def test_tail_batches_consumed(self, tmp_path, static_mode):
        """A dataset not dividing into K-windows falls back to per-step
        run() for the tail instead of dropping full batches."""
        paths = self._files(tmp_path, n_files=1, rows=48)  # 3 batches
        pt.seed(0)
        prog, startup, x, y, loss = self._program(batch=16)
        exe = fluid.Executor()
        exe.run(startup)
        out = exe.train_from_dataset(
            program=prog, dataset=self._dataset(paths, x, y, 16),
            fetch_list=[loss], print_period=0, steps_per_dispatch=2)
        # last fetch comes from the per-step tail run: scalar loss
        assert np.asarray(out[0]).shape == ()


# -- tooling (satellite: donation sweep) -------------------------------------


@pytest.mark.slow
def test_donation_sweep_covers_model_zoo():
    """tools/perf_gate.py --donation-sweep: every sweep leg's fused
    entry must donate 100% of its persistable carry."""
    _require8()
    pg = _load_tool("perf_gate")
    rows, failures = pg.donation_sweep()
    assert failures == []
    assert {r["model"] for r in rows} == {"mlp", "lenet", "ngram_lm"}
    assert all(r["coverage"] == 1.0 for r in rows)
    assert "100%" in pg.render_sweep(rows)
