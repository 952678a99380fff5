"""Fused multi-step execution (ISSUE 6 tentpole #1/#2): Executor.run_steps
and TrainStep.run_fused drive K microbatches through one lax.scan
executable; the DevicePrefetcher overlaps host->device feed with
compute. Correctness pins: trajectories vs K sequential steps, state
advancement, error surfaces, and the journal's steps_fused records."""
import json
import os

import numpy as np
import pytest

import jax

import paddle_tpu as pt
import paddle_tpu.fluid as fluid
import paddle_tpu.nn as nn
import paddle_tpu.ops as ops
from paddle_tpu import optim
from paddle_tpu.io_ import (DevicePrefetcher, prefetch_to_device,
                            executor_feed_shardings)


@pytest.fixture
def static_mode():
    pt.enable_static()
    yield
    pt.disable_static()


def _build_mlp(batch=16, lr=0.05):
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


def _feeds(K, batch=16, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.randn(batch, 8).astype(np.float32),
             "y": rng.randn(batch, 1).astype(np.float32)}
            for _ in range(K)]


# -- Executor.run_steps ------------------------------------------------------


class TestRunSteps:
    def test_prestacked_dict_matches_feed_list(self, static_mode):
        K = 4
        feeds = _feeds(K)
        pt.seed(0)
        prog, startup, loss = _build_mlp()
        exe = fluid.Executor()
        exe.run(startup)
        (a,) = exe.run_steps(prog, feeds=feeds, fetch_list=[loss])

        pt.seed(0)
        prog2, startup2, loss2 = _build_mlp()
        exe2 = fluid.Executor()
        exe2.run(startup2)
        stacked = {n: np.stack([f[n] for f in feeds])
                   for n in feeds[0]}
        (b,) = exe2.run_steps(prog2, feeds=stacked, fetch_list=[loss2],
                              steps=K)
        assert a.tobytes() == b.tobytes()

    def test_persistables_advance_like_sequential(self, static_mode):
        """After a fused window the scope's parameters are bitwise what
        K sequential runs leave behind."""
        from paddle_tpu.static_.program import global_scope

        K = 4
        feeds = _feeds(K)
        pt.seed(0)
        prog, startup, loss = _build_mlp()
        exe = fluid.Executor()
        exe.run(startup)
        for f in feeds:
            exe.run(prog, feed=f, fetch_list=[loss])
        entry = next(iter(exe._cache.values()))
        seq_params = {n: np.asarray(global_scope().find_var(n))
                      for n in entry.updated}

        pt.seed(0)
        prog2, startup2, loss2 = _build_mlp()
        exe2 = fluid.Executor()
        exe2.run(startup2)
        exe2.run_steps(prog2, feeds=feeds, fetch_list=[loss2])
        entry2 = next(iter(exe2._cache.values()))
        assert tuple(entry2.updated)  # something persisted
        # identical builds list their persistables in the same order
        # (names differ by the unique-name counter)
        assert len(entry2.updated) == len(entry.updated)
        for n1, n2 in zip(entry.updated, entry2.updated):
            got = np.asarray(global_scope().find_var(n2))
            assert got.tobytes() == seq_params[n1].tobytes(), (n1, n2)

    def test_feed_validation_errors(self, static_mode):
        pt.seed(0)
        prog, startup, loss = _build_mlp()
        exe = fluid.Executor()
        exe.run(startup)
        feeds = _feeds(2)
        with pytest.raises(ValueError, match="at least one feed"):
            exe.run_steps(prog, feeds=[], fetch_list=[loss])
        with pytest.raises(ValueError, match="steps=3 but 2"):
            exe.run_steps(prog, feeds=feeds, fetch_list=[loss], steps=3)
        bad = [feeds[0], {"x": feeds[1]["x"]}]
        with pytest.raises(ValueError, match="same variables"):
            exe.run_steps(prog, feeds=bad, fetch_list=[loss])
        with pytest.raises(ValueError, match="explicit steps"):
            exe.run_steps(prog, feeds={"x": np.zeros((2, 16, 8))},
                          fetch_list=[loss])
        with pytest.raises(ValueError, match="leading microbatch axis"):
            exe.run_steps(
                prog, feeds={"x": np.zeros((2, 16, 8), np.float32),
                             "y": np.zeros((16, 1), np.float32)},
                fetch_list=[loss], steps=2)

    def test_multi_fetch_stacks_every_fetch(self, static_mode):
        K = 3
        pt.seed(0)
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup):
            x = fluid.data(name="x", shape=[4, 2])
            h = fluid.layers.fc(x, size=2)
            s = fluid.layers.reduce_sum(h)
            m = fluid.layers.reduce_mean(h)
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(1)
        feeds = [{"x": rng.randn(4, 2).astype(np.float32)}
                 for _ in range(K)]
        outs = exe.run_steps(prog, feeds=feeds, fetch_list=[s, m])
        assert len(outs) == 2
        assert outs[0].shape == (K,) and outs[1].shape == (K,)
        seq = [exe.run(prog, feed=f, fetch_list=[s, m]) for f in feeds]
        # two compiled programs: the window may fuse the two reductions
        # otherwise than a lone step does, so equal to float32's rounding
        # and not to the bit (a few ulp of a sum of 8 terms)
        for j in range(2):
            np.testing.assert_allclose(
                outs[j], [np.asarray(seq[k][j]) for k in range(K)],
                rtol=1e-5, atol=1e-6)

    def test_journal_records_steps_fused(self, static_mode, tmp_path):
        from paddle_tpu.obs.journal import RunJournal

        K = 4
        pt.seed(0)
        prog, startup, loss = _build_mlp()
        exe = fluid.Executor()
        exe.run(startup)
        feeds = _feeds(K)
        with RunJournal(str(tmp_path / "run"), compute_flops=False):
            exe.run_steps(prog, feeds=feeds, fetch_list=[loss])
            exe.run(prog, feed=feeds[0], fetch_list=[loss])
        recs = [json.loads(line) for line in
                open(tmp_path / "run" / "journal.jsonl")]
        steps = [r for r in recs if r["t"] == "step"]
        assert len(steps) == 2  # one record per DISPATCH, not per K
        fused, single = steps
        assert fused["steps_fused"] == K
        assert fused["examples"] == 16 * K
        assert fused["loss"] is not None  # trajectory endpoint scalar
        assert "steps_fused" not in single
        compiles = [r for r in recs if r["t"] == "event"
                    and r["kind"] == "compile"]
        assert any(e.get("steps_fused") == K for e in compiles)
        # run summary weights fused windows: 2 records, K+1 opt steps
        (end,) = [r for r in recs if r["t"] == "run_end"]
        assert end["summary"]["steps"] == 2
        assert end["summary"]["optimizer_steps"] == K + 1
        assert end["summary"]["productive_steps"] == K + 1

    def test_fetch_async_journal_does_not_sync(self, static_mode,
                                               tmp_path):
        """Async fetches must journal metadata-only summaries — no
        hidden scalar device read on the step path."""
        from paddle_tpu.obs.journal import RunJournal

        pt.seed(0)
        prog, startup, loss = _build_mlp()
        exe = fluid.Executor()
        exe.run(startup)
        f = _feeds(1)[0]
        with RunJournal(str(tmp_path / "run"), compute_flops=False):
            (lazy,) = exe.run(prog, feed=f, fetch_list=[loss],
                              fetch_async=True)
            assert isinstance(lazy, jax.Array)
        recs = [json.loads(line) for line in
                open(tmp_path / "run" / "journal.jsonl")]
        (step,) = [r for r in recs if r["t"] == "step"]
        assert step["loss"] is None  # not read off-device
        assert step["fetches"][0] == {"shape": [], "dtype": "float32"}


# -- TrainStep.run_fused -----------------------------------------------------


def _eager_setup(opt_cls=None, **opt_kw):
    pt.seed(0)
    model = nn.Linear(8, 1)
    opt_cls = opt_cls or optim.SGD
    opt = opt_cls(learning_rate=0.05, parameters=model.parameters(),
                  **opt_kw)
    step = pt.TrainStep(model, opt,
                        lambda m, x, y: ops.mean((m(x) - y) ** 2))
    return model, opt, step


def _eager_batches(K, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(16, 8).astype(np.float32),
             rng.randn(16, 1).astype(np.float32)) for _ in range(K)]


class TestRunFused:
    @pytest.mark.parametrize("opt_cls,kw", [
        (optim.SGD, {}),
        (optim.Momentum, {"momentum": 0.9}),
        (optim.AdamW, {}),
    ])
    def test_matches_sequential_trajectory(self, opt_cls, kw):
        K = 6
        batches = _eager_batches(K)
        m1, o1, s1 = _eager_setup(opt_cls, **kw)
        pt.seed(7)
        seq = [float(np.asarray(s1(*b)._data)) for b in batches]

        m2, o2, s2 = _eager_setup(opt_cls, **kw)
        pt.seed(7)
        traj = np.asarray(s2.run_fused(batches)._data)
        assert traj.shape == (K,)
        # same ops / keys / lr; XLA may fuse the scan body marginally
        # differently than the standalone step, so float tolerance
        np.testing.assert_allclose(traj, seq, rtol=1e-5, atol=1e-7)
        for p1, p2 in zip(m1.parameters(), m2.parameters()):
            np.testing.assert_allclose(
                np.asarray(p1._data), np.asarray(p2._data),
                rtol=1e-5, atol=1e-7)
        assert o2._global_step == K == o1._global_step

    def test_one_compile_entry_per_window_shape(self):
        _, _, step = _eager_setup()
        batches = _eager_batches(4)
        step.run_fused(batches)
        step.run_fused(batches)  # same shape: cached
        fused_sigs = [s for s in step._compiled
                      if isinstance(s, tuple) and s and s[0] == "fused"]
        assert len(fused_sigs) == 1
        step.run_fused(_eager_batches(2), steps=2)  # new K: new entry
        fused_sigs = [s for s in step._compiled
                      if isinstance(s, tuple) and s and s[0] == "fused"]
        assert len(fused_sigs) == 2

    def test_prestacked_matches_list_form(self):
        K = 4
        batches = _eager_batches(K)
        _, _, s1 = _eager_setup()
        pt.seed(9)
        a = np.asarray(s1.run_fused(batches)._data)
        _, _, s2 = _eager_setup()
        pt.seed(9)
        stacked = (np.stack([b[0] for b in batches]),
                   np.stack([b[1] for b in batches]))
        b = np.asarray(s2.run_fused(stacked, steps=K)._data)
        assert a.tobytes() == b.tobytes()

    def test_shape_mismatch_raises(self):
        _, _, step = _eager_setup()
        rows = _eager_batches(3)
        rows[1] = (rows[1][0][:8], rows[1][1][:8])
        with pytest.raises(ValueError, match="uniform shapes"):
            step.run_fused(rows)
        with pytest.raises(ValueError, match="steps must be >= 1"):
            step.run_fused([], steps=0)

    def test_stochastic_model_uses_per_step_keys(self):
        """Dropout inside the fused window: per-step pre-drawn keys give
        the sequential trajectory (same host RNG stream)."""
        import paddle_tpu.nn.functional as F

        def make():
            pt.seed(0)
            model = nn.Sequential(nn.Linear(8, 8), nn.Dropout(0.5),
                                  nn.Linear(8, 1))
            opt = optim.SGD(learning_rate=0.05,
                            parameters=model.parameters())
            return model, pt.TrainStep(
                model, opt, lambda m, x, y: ops.mean((m(x) - y) ** 2))

        K = 4
        batches = _eager_batches(K)
        _, s1 = make()
        pt.seed(42)
        seq = [float(np.asarray(s1(*b)._data)) for b in batches]
        _, s2 = make()
        pt.seed(42)
        traj = np.asarray(s2.run_fused(batches)._data)
        np.testing.assert_allclose(traj, seq, rtol=1e-5, atol=1e-7)
        assert len(set(np.round(traj, 6))) > 1  # dropout actually varied

    def test_collective_profile_covers_fused_entry(self):
        """The fused sig's captured arg structs support the PR-5
        collective profiling path (no collectives on one host device,
        but the lowering must succeed and profile as zero)."""
        _, _, step = _eager_setup()
        step.run_fused(_eager_batches(2), steps=2)
        prof = step.collective_profile()
        assert prof is not None and prof["n_ops"] == 0


# -- DevicePrefetcher --------------------------------------------------------


class TestDevicePrefetcher:
    def test_batches_arrive_in_order_as_device_arrays(self):
        feeds = [{"x": np.full((4, 2), i, np.float32)} for i in range(6)]
        got = list(prefetch_to_device(feeds, depth=2))
        assert len(got) == 6
        for i, b in enumerate(got):
            assert isinstance(b["x"], jax.Array)
            assert float(np.asarray(b["x"])[0, 0]) == float(i)

    def test_tuple_batches_and_tensor_unwrap(self):
        t = pt.to_tensor(np.ones((2, 2), np.float32))
        (a, b), = list(prefetch_to_device([(t, np.zeros(3))]))
        assert isinstance(a, jax.Array) and isinstance(b, jax.Array)

    def test_shardings_batch_container_mismatch_raises(self):
        """A shardings spec that can't be matched to the batch container
        must fail loudly (in batch order), never silently fall back to
        default placement."""
        sh = {"x": None}
        it = prefetch_to_device([(np.zeros(2, np.float32),)],
                                shardings=sh, depth=2)
        with pytest.raises(TypeError, match="cannot be matched"):
            next(it)
        it2 = prefetch_to_device([{"x": np.zeros(2, np.float32)}],
                                 shardings=[None], depth=2)
        with pytest.raises(TypeError, match="cannot be matched"):
            next(it2)

    def test_shardings_key_and_length_mismatches_raise(self):
        """Name-level mismatches fail loudly too: a shardings dict
        sharing no key with the batch, or a sequence longer than the
        batch — while a SUPERSET dict (executor_feed_shardings' '@lr'
        next to an {'x','y'} batch) stays legal."""
        batch = {"x": np.zeros(2, np.float32)}
        it = prefetch_to_device([batch], shardings={"X": None}, depth=2)
        with pytest.raises(TypeError, match="share no key"):
            next(it)
        it2 = prefetch_to_device([(np.zeros(2, np.float32),)],
                                 shardings=[None, None], depth=2)
        with pytest.raises(TypeError, match="extra entries"):
            next(it2)
        # superset dict is fine
        got = list(prefetch_to_device([batch],
                                      shardings={"x": None, "@lr": None}))
        assert isinstance(got[0]["x"], jax.Array)

    def test_executor_feed_shardings_strips_fused_scan_axis(self):
        """For a fused (steps=K) DP entry the helper returns PER-STEP
        shardings (leading scan axis stripped) so loader batches land
        on the batch-axis layout, and round-trip through run_steps."""
        if jax.local_device_count() < 2:
            pytest.skip("needs the 8-fake-device mesh")
        pt.enable_static()
        try:
            pt.seed(0)
            K = 2
            prog, startup, loss = _build_mlp()
            cp = fluid.CompiledProgram(prog).with_data_parallel(
                loss_name=loss.name)
            exe = fluid.Executor()
            exe.run(startup)
            feeds = _feeds(K)
            exe.run_steps(cp, feeds=feeds, fetch_list=[loss], steps=K)
            entry = next(iter(exe._cache.values()))
            assert entry.steps == K
            sh = executor_feed_shardings(entry)
            assert sh["x"].spec[0] == "data"  # per-step batch axis
            got = list(prefetch_to_device(feeds, shardings=sh))
            assert got[0]["x"].sharding.spec[0] == "data"
            assert got[0]["x"].shape == (16, 8)  # per-step, not stacked
            (traj,) = exe.run_steps(cp, feeds=got, fetch_list=[loss],
                                    steps=K)
            assert np.isfinite(traj).all()
        finally:
            pt.disable_static()

    def test_device_array_feeds_pass_through_unconverted(self):
        """A prefetched (committed, device-resident) feed must reach the
        executable without a host round-trip: the executor keeps the
        very same jax arrays (and TrainStep keeps device batch items)."""
        x = jax.device_put(np.ones((4, 2), np.float32))
        from paddle_tpu.static_.executor import Executor

        assert Executor._as_device(x) is x
        assert Executor._feed_shape_dtype(x) == ((4, 2), "float32")
        from paddle_tpu.framework.jit import _as_array

        assert _as_array(x) is x

    def test_honors_committed_shardings_from_entry(self):
        """Batches land pre-sharded on the compiled entry's committed
        feed shardings (the DP data-axis layout)."""
        if jax.local_device_count() < 2:
            pytest.skip("needs the 8-fake-device mesh")
        pt.enable_static()
        try:
            pt.seed(0)
            prog, startup, loss = _build_mlp()
            cp = fluid.CompiledProgram(prog).with_data_parallel(
                loss_name=loss.name)
            exe = fluid.Executor()
            exe.run(startup)
            f = _feeds(1)[0]
            exe.run(cp, feed=f, fetch_list=[loss])
            entry = next(iter(exe._cache.values()))
            sh = executor_feed_shardings(entry)
            assert set(sh) == {"@lr", "x", "y"}  # the fed LR scalar too
            got = list(prefetch_to_device([f], shardings=sh))
            xs = got[0]["x"].sharding
            assert xs.spec and xs.spec[0] == "data"
            assert got[0]["x"].sharding.mesh.devices.size == \
                jax.local_device_count()
            # and the prefetched batch is directly runnable
            (lv,) = exe.run(cp, feed=got[0], fetch_list=[loss])
            assert np.isfinite(lv).all()
        finally:
            pt.disable_static()

    def test_executor_feed_shardings_single_device_entry(self, ):
        pt.enable_static()
        try:
            pt.seed(0)
            prog, startup, loss = _build_mlp()
            exe = fluid.Executor()
            exe.run(startup)
            exe.run(prog, feed=_feeds(1)[0], fetch_list=[loss])
            entry = next(iter(exe._cache.values()))
            sh = executor_feed_shardings(entry)
            assert sh == {"@lr": None, "x": None, "y": None}
        finally:
            pt.disable_static()
