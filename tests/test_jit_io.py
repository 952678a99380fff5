"""Fused-step jit + serialization tests (model: reference
test_imperative_*.py jit tests and test_inference_model_io.py)."""
import os

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.optim as optim
import paddle_tpu.nn.functional as F


def _problem():
    rng = np.random.RandomState(0)
    X = rng.randn(64, 8).astype("float32")
    Y = (X @ rng.randn(8, 1)).astype("float32")
    return X, Y


class TestTrainStep:
    def test_fused_step_trains(self):
        X, Y = _problem()
        model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
        opt = optim.Adam(0.05, parameters=model.parameters())
        step = pt.TrainStep(model, opt,
                            lambda m, x, y: F.mse_loss(m(x), y))
        losses = [float(step(X, Y)) for _ in range(30)]
        assert losses[-1] < losses[0] * 0.1
        assert len(step._compiled) == 1  # one compilation for fixed shapes

    def test_fused_matches_eager(self):
        X, Y = _problem()

        def build():
            pt.seed(7)
            m = nn.Sequential(nn.Linear(8, 4), nn.Tanh(), nn.Linear(4, 1))
            o = optim.SGD(0.1, parameters=m.parameters())
            return m, o

        m1, o1 = build()
        m2, o2 = build()
        for n, p in m1.named_parameters():
            dict(m2.named_parameters())[n].set_value(p)

        step = pt.TrainStep(m1, o1, lambda m, x, y: F.mse_loss(m(x), y))
        fused = [float(step(X, Y)) for _ in range(5)]

        eager = []
        for _ in range(5):
            loss = F.mse_loss(m2(pt.to_tensor(X)), pt.to_tensor(Y))
            loss.backward()
            o2.step()
            o2.clear_grad()
            eager.append(float(loss))
        np.testing.assert_allclose(fused, eager, rtol=1e-4)

    def test_fused_step_with_clip_and_bn(self):
        X = np.random.RandomState(1).randn(32, 4, 6, 6).astype("float32")
        Y = np.random.RandomState(2).randint(0, 2, 32).astype("int64")
        model = nn.Sequential(nn.Conv2D(4, 8, 3), nn.BatchNorm2D(8), nn.ReLU(),
                              nn.Flatten(), nn.Linear(8 * 4 * 4, 2))
        opt = optim.Momentum(0.05, parameters=model.parameters(),
                             grad_clip=optim.ClipGradByGlobalNorm(1.0))
        step = pt.TrainStep(model, opt,
                            lambda m, x, y: F.cross_entropy(m(x), y))
        before = model[1]._mean.numpy().copy()
        l0 = float(step(X, Y))
        for _ in range(10):
            l = float(step(X, Y))
        assert l < l0
        assert not np.allclose(model[1]._mean.numpy(), before), \
            "BN running stats must update through the fused step"

    def test_dropout_varies_inside_jit(self):
        model = nn.Sequential(nn.Linear(8, 8), nn.Dropout(0.5))
        fwd = pt.to_static(model)
        x = np.ones((4, 8), "float32")
        a = fwd(x).numpy()
        b = fwd(x).numpy()
        assert not np.allclose(a, b), "dropout mask must differ per call"


class TestSaveLoad:
    def test_save_load_state_dict(self, tmp_path):
        m = nn.Linear(4, 3)
        p = str(tmp_path / "model.pdparams")
        pt.save(m.state_dict(), p)
        m2 = nn.Linear(4, 3)
        m2.set_state_dict(pt.load(p))
        x = pt.to_tensor(np.random.randn(2, 4).astype("float32"))
        np.testing.assert_allclose(m(x).numpy(), m2(x).numpy(), rtol=1e-6)

    def test_inference_model_roundtrip(self, tmp_path):
        pt.enable_static()
        main, startup = pt.static.Program(), pt.static.Program()
        with pt.program_guard(main, startup):
            x = pt.static.data("x", [4, 6], "float32")
            net = nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 2))
            out = net(x)
        pt.disable_static()
        exe = pt.static.Executor()
        exe.run(startup)
        X = np.random.RandomState(0).randn(4, 6).astype("float32")
        want = exe.run(main, feed={"x": X}, fetch_list=[out])[0]

        prefix = str(tmp_path / "infer")
        pt.framework.save_inference_model(prefix, [x], [out], exe,
                                          program=main)
        prog2, feeds, fetches = pt.framework.load_inference_model(prefix, exe)
        got = exe.run(prog2, feed={feeds[0]: X}, fetch_list=fetches)[0]
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_checkpoint_resume(self, tmp_path):
        X, Y = _problem()
        d = str(tmp_path / "ckpts")

        m = nn.Linear(8, 1)
        sched = optim.lr.StepDecay(0.1, step_size=5)
        opt = optim.Adam(sched, parameters=m.parameters())
        for i in range(3):
            loss = F.mse_loss(m(pt.to_tensor(X)), pt.to_tensor(Y))
            loss.backward()
            opt.step()
            opt.clear_grad()
            sched.step()
            pt.framework.save_checkpoint(d, i, m, opt, sched, keep_last=2)

        assert sorted(os.listdir(d)) == ["ckpt_1", "ckpt_2"]  # rotation

        m2 = nn.Linear(8, 1)
        sched2 = optim.lr.StepDecay(0.1, step_size=5)
        opt2 = optim.Adam(sched2, parameters=m2.parameters())
        step = pt.framework.load_checkpoint(d, m2, opt2, sched2)
        assert step == 2
        np.testing.assert_allclose(m2.weight.numpy(), m.weight.numpy())
        assert sched2.last_epoch == sched.last_epoch

    def test_load_checkpoint_empty_dir(self, tmp_path):
        assert pt.framework.load_checkpoint(str(tmp_path / "none")) is None


class TestReviewRegressions:
    def test_trainstep_with_frozen_param(self):
        from paddle_tpu.nn import ParamAttr

        m = nn.Sequential(
            nn.Linear(4, 6, weight_attr=ParamAttr(trainable=False)),
            nn.Linear(6, 1))
        opt = optim.SGD(0.1, parameters=m.parameters())
        step = pt.TrainStep(m, opt, lambda mm, x, y: F.mse_loss(mm(x), y))
        w_frozen = m[0].weight.numpy().copy()
        x = np.random.randn(8, 4).astype("float32")
        y = np.random.randn(8, 1).astype("float32")
        l0 = float(step(x, y))
        for _ in range(5):
            l = float(step(x, y))
        assert l < l0
        np.testing.assert_allclose(m[0].weight.numpy(), w_frozen)

    def test_static_grad_duplicate_input(self):
        pt.enable_static()
        main, startup = pt.static.Program(), pt.static.Program()
        with pt.program_guard(main, startup):
            x = pt.static.data("x", [3], "float32")
            xv = pt.static.default_main_program().global_block.create_var(
                name="xv", shape=[3], dtype="float32", persistable=True)
            pt.static.global_scope().set(
                "xv", np.array([1.0, 2.0, 3.0], "float32"))
            xv.is_parameter = True
            xv.stop_gradient = False
            y = pt.sum(xv * xv)  # d/dx (x*x) must be 2x, not x
            grads = pt.static.append_backward(y, parameter_list=[xv])
        pt.disable_static()
        exe = pt.static.Executor()
        out = exe.run(main, feed={"x": np.zeros(3, "float32")},
                      fetch_list=[grads[0][1]])
        np.testing.assert_allclose(out[0], [2.0, 4.0, 6.0], rtol=1e-6)

    def test_multi_precision_trainstep(self):
        m = nn.Linear(4, 4)
        m.bfloat16()
        opt = optim.Adam(0.01, parameters=m.parameters(),
                         multi_precision=True)
        step = pt.TrainStep(m, opt, lambda mm, x, y: F.mse_loss(
            mm(x).astype("float32"), y))
        x = np.random.randn(8, 4).astype("float32")
        y = np.random.randn(8, 4).astype("float32")
        step(x, y)
        step(x, y)
        name = m.weight.name
        master = opt._accumulators[name]["master"]
        import jax.numpy as jnp

        assert master.dtype == jnp.float32
        # master must track the bf16 param (same values up to rounding)
        np.testing.assert_allclose(np.asarray(master, dtype=np.float32),
                                   m.weight.numpy().astype(np.float32),
                                   atol=1e-2)
        # and must have actually moved from init
        assert opt._accumulators[name]["beta1_pow"] < 1.0

    def test_state_dict_prefix_skips_nonpersistable(self):
        m = nn.Linear(2, 2)
        m.register_buffer("scratch", pt.zeros([1]), persistable=False)
        sd = m.state_dict(structured_name_prefix="model.")
        assert "model.weight" in sd
        assert not any("scratch" in k for k in sd)

    def test_static_gradients_multi_target(self):
        pt.enable_static()
        main, startup = pt.static.Program(), pt.static.Program()
        with pt.program_guard(main, startup):
            blk = pt.static.default_main_program().global_block
            w = blk.create_var(name="w2", shape=[2], dtype="float32",
                               persistable=True)
            pt.static.global_scope().set("w2", np.array([1.0, 1.0], "float32"))
            w.is_parameter = True
            w.stop_gradient = False
            a = pt.sum(w * 2.0)
            b = pt.sum(w * 3.0)
            g = pt.static.gradients([a, b], [w])
        pt.disable_static()
        exe = pt.static.Executor()
        out = exe.run(main, feed={}, fetch_list=[g[0]])
        np.testing.assert_allclose(out[0], [5.0, 5.0], rtol=1e-6)

    def test_inference_model_with_assign(self, tmp_path):
        pt.enable_static()
        main, startup = pt.static.Program(), pt.static.Program()
        with pt.program_guard(main, startup):
            x = pt.static.data("x", [2, 3], "float32")
            lin = nn.Linear(3, 3)
            out = lin(x)
        pt.disable_static()
        exe = pt.static.Executor()
        prefix = str(tmp_path / "m")
        pt.framework.save_inference_model(prefix, [x], [out], exe,
                                          program=main)
        prog, feeds, fetches = pt.framework.load_inference_model(prefix, exe)
        X = np.ones((2, 3), "float32")
        r = exe.run(prog, feed={feeds[0]: X}, fetch_list=fetches)[0]
        assert r.shape == (2, 3)


@pytest.mark.parametrize("bf16_momentum", [False, True])
def test_set_compilation_cache_persists_executables(tmp_path, monkeypatch,
                                                    cache_config,
                                                    bf16_momentum):
    """pt.set_compilation_cache(dir) must actually write compiled
    executables to disk (the cross-process warm-start path the benchmark
    and chip_smoke.py use on hardware), and the AOT-compiled step must take a second
    call: with bf16 params + f32 master weights the Momentum slots once
    changed dtype after step one, which an AOT executable refuses."""
    import os

    import paddle_tpu as pt
    import paddle_tpu.nn as nn

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = str(tmp_path / "xla_cache")
    try:
        assert pt.set_compilation_cache(d, min_compile_time_secs=0.0) == d
        m = nn.Linear(64, 32)
        if bf16_momentum:
            m.bfloat16()
            opt = pt.optim.Momentum(0.1, 0.9, parameters=m.parameters(),
                                    multi_precision=True)
        else:
            opt = pt.optim.SGD(parameters=m.parameters(), learning_rate=0.1)
        step = pt.TrainStep(
            m, opt, lambda mm, x, y: ((mm(x.astype(mm.weight.dtype))
                                       .astype("float32") - y) ** 2).mean())
        for _ in range(2):
            step(np.ones((8, 64), "float32"), np.zeros((8, 32), "float32"))
        assert os.listdir(d), "no executables persisted"
    finally:
        # the off switch is part of the API; cache_config then puts the
        # process-wide settings back for the tests after this one
        pt.set_compilation_cache(None)
