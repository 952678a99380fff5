"""fluid optimizer/metrics/dygraph/framework namespace parity tests.

Mirrors the reference __all__ surfaces of fluid/optimizer.py,
fluid/metrics.py (EditDistance, DetectionMAP), fluid/framework.py
(places, flags, device_guard), fluid/clip.py (ErrorClipByValue,
set_gradient_clip), fluid/profiler.py, and fluid/dygraph/* (layer
catalogue, LR decays, save/load_dygraph, ParallelEnv, TracedLayer).
"""
import numpy as np
import pytest
import paddle_tpu as pt
import paddle_tpu.fluid.dygraph as D
import paddle_tpu.fluid as fluid
from paddle_tpu import optim, metrics
import paddle_tpu.ops as ops
from paddle_tpu.nn.layer import Layer
from paddle_tpu.optim.clip import set_gradient_clip


def test_fluid_namespace_parity_drive():
    pt.seed(0)


    class M(Layer):
        def __init__(self):
            super().__init__()
            self.w = self.create_parameter((2,))


    m = None
    for Opt in (optim.DecayedAdagradOptimizer, optim.LarsMomentumOptimizer,
                optim.DpsgdOptimizer):
        m = M()
        o = Opt(0.1, parameters=m.parameters())
        for _ in range(5):
            loss = ops.sum(m.w * m.w)
            loss.backward()
            o.step(); o.clear_grad()
    m = M()
    o = optim.DGCMomentumOptimizer(0.1, 0.9, parameters=m.parameters())
    loss = ops.sum(m.w * m.w); loss.backward(); o.step(); o.clear_grad()
    print("optimizers ok")

    ma = optim.ModelAverage(0.15, parameters=m.parameters())
    ma.step(); ma.apply(); ma.restore()
    ro = optim.RecomputeOptimizer(optim.SGD(0.1, parameters=m.parameters()))
    loss = ops.sum(m.w * m.w); ro.minimize(loss)
    po = optim.PipelineOptimizer(optim.SGD(0.1, parameters=m.parameters()))
    print("wrappers ok")

    set_gradient_clip(optim.ClipGradByGlobalNorm(1.0))
    o2 = optim.SGD(0.1, parameters=m.parameters())
    assert o2._grad_clip is not None
    set_gradient_clip(None)

    ed = metrics.EditDistance()
    ed.update(np.array([0.0, 2.0]), 2)
    avg, err = ed.eval()
    assert avg == 1.0 and err == 0.5
    m_ap = metrics.DetectionMAP(map_type="11point")
    det = np.array([[0, 0.9, 0, 0, 10, 10], [1, 0.8, 20, 20, 30, 30]], "float32")
    gt = np.array([[0, 0, 0, 10, 10], [1, 20, 20, 30, 30]], "float32")
    m_ap.update(det, gt)
    assert abs(m_ap.eval() - 1.0) < 1e-6
    print("metrics ok")

    assert len(fluid.cpu_places(2)) == 2
    fluid.set_flags({"FLAGS_foo": 1})
    assert fluid.get_flags("FLAGS_foo")["FLAGS_foo"] == 1
    with fluid.device_guard("cpu"):
        pass
    print("places/flags ok")

    x = pt.to_tensor(np.random.randn(2, 3, 8, 8).astype("float32"))
    assert list(D.Pool2D(2, "avg", 2)(x).shape) == [2, 3, 4, 4]
    pr = D.PRelu("channel", channel=3)
    assert list(pr(x).shape) == [2, 3, 8, 8]
    sn = D.SpectralNorm()
    w = pt.to_tensor(np.random.randn(6, 4).astype("float32"))
    assert list(sn(w).shape) == [6, 4]
    btp = D.BilinearTensorProduct(4, 5, 3)
    out = btp(pt.to_tensor(np.random.randn(2, 4).astype("float32")),
              pt.to_tensor(np.random.randn(2, 5).astype("float32")))
    assert list(out.shape) == [2, 3]
    nce_l = D.NCE(20, 6)
    l = nce_l(pt.to_tensor(np.random.randn(4, 6).astype("float32")),
              pt.to_tensor(np.random.randint(0, 20, (4, 1))))
    gu = D.GRUUnit(3 * 5)
    nh, rh, g = gu(pt.to_tensor(np.random.randn(2, 15).astype("float32")),
                   pt.to_tensor(np.zeros((2, 5), "float32")))
    assert list(nh.shape) == [2, 5]
    tc = D.TreeConv(4, 6, 2, max_depth=2)
    nodes = pt.to_tensor(np.random.randn(1, 5, 4).astype("float32"))
    edges = pt.to_tensor(np.array([[[0, 1], [0, 2], [1, 3], [0, 0]]], "float32"))
    o = tc(nodes, edges)
    assert list(o.shape) == [1, 5, 6, 2], o.shape
    print("dygraph layers ok")

    import tempfile
    pth = tempfile.mktemp()
    D.save_dygraph(m.state_dict(), pth)
    params, opt_state = D.load_dygraph(pth)
    assert len(params) >= 1
    assert D.enabled()
    env = D.ParallelEnv()
    assert env.nranks >= 1
    bs = D.BackwardStrategy(); bs.sort_sum_gradient = True
    gfn = D.dygraph_to_static_func(lambda a: a * 2)
    print("dygraph utils ok")
    print("NAMESPACE OK")


def test_reference_namespace_all_resolved():
    """Audit: every __all__ name of the reference fluid sub-namespaces
    resolves in the matching paddle_tpu namespace."""
    import ast, os

    def get_all(path):
        names = []
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "__all__":
                        try:
                            names += ast.literal_eval(node.value)
                        except Exception:
                            pass
        return set(names)

    base = "/root/reference/python/paddle/fluid/"
    if not os.path.isdir(base):
        return
    import paddle_tpu.fluid as PF
    import paddle_tpu.fluid.dygraph as D2
    import paddle_tpu.metrics as MM
    import paddle_tpu.nn.initializer as II
    import paddle_tpu.optim as OO
    import paddle_tpu.optim.clip as CC
    import paddle_tpu.utils.profiler as PP

    checks = {
        "framework.py": dir(PF) + dir(pt.static),
        "metrics.py": dir(MM),
        "initializer.py": dir(II),
        "clip.py": dir(CC),
        "optimizer.py": dir(OO),
        "profiler.py": dir(PP),
    }
    for mod, ours in checks.items():
        missing = sorted(n for n in get_all(base + mod)
                         if n not in set(ours))
        assert missing == [], f"{mod}: {missing}"
    dyg = set()
    for f in os.listdir(base + "dygraph/"):
        if f.endswith(".py"):
            dyg |= get_all(base + "dygraph/" + f)
    missing = sorted(n for n in dyg if n not in set(dir(D2)))
    assert missing == [], f"dygraph: {missing}"


def test_static_2x_surface():
    """paddle.static.create_parameter / static.nn.* resolve and build
    (2.x static spellings next to the fluid ones)."""
    import numpy as np

    import paddle_tpu as pt

    pt.enable_static()
    try:
        main, startup = pt.static.Program(), pt.static.Program()
        with pt.program_guard(main, startup):
            x = pt.static.data("x", [4, 8])
            w = pt.static.create_parameter([8, 2])
            h = pt.static.nn.fc(x, size=2)
        exe = pt.static.Executor()
        exe.run(startup)
        (o,) = exe.run(main, feed={"x": np.ones((4, 8), "float32")},
                       fetch_list=[h])
        assert np.asarray(o).shape == (4, 2)
        assert callable(pt.static.nn.conv2d)
        assert callable(pt.static.nn.batch_norm)
    finally:
        pt.disable_static()


def _reference_source(path):
    """A file of the reference's python/paddle tree, which is not part of
    this repository: the audit is for whoever has it mounted."""
    import os

    path = os.path.join("/root/reference/python/paddle", path)
    if not os.path.isfile(path):
        pytest.skip(f"the reference tree is not mounted here: no {path}")
    with open(path) as f:
        return f.read()


def test_reference_paddle_nn_surface_resolves():
    """Every name the reference's python/paddle/nn/__init__.py binds via
    explicit imports (it has no real __all__ — only a commented-out one)
    resolves on paddle_tpu.nn."""
    import ast

    import paddle_tpu.nn as nn

    tree = ast.parse(_reference_source("nn/__init__.py"))
    names = set()
    for node in tree.body:
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name != "*":
                    names.add(a.asname or a.name)
    assert names, "harvested nothing from the reference file"
    missing = sorted(n for n in names if not hasattr(nn, n)
                     and not n.startswith("_"))
    assert not missing, missing


def test_reference_paddle_toplevel_surface_resolves():
    """Every name the reference's python/paddle/__init__.py binds (explicit
    imports + __all__) resolves on paddle_tpu — including the long-tail
    check_import_scipy and the fill_constant creation alias."""
    import ast

    tree = ast.parse(_reference_source("__init__.py"))
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name != "*":
                    names.add(a.asname or a.name)
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    names.update(ast.literal_eval(node.value))
    assert names, "harvested nothing from the reference file"
    missing = sorted(n for n in names if not hasattr(pt, n)
                     and not n.startswith("_"))
    assert not missing, missing
    # the Windows scipy probe is callable and a no-op off-Windows
    pt.check_import_scipy("posix")


def test_2x_module_import_spellings():
    """Reference scripts import the 2.x surfaces as MODULES (ref:
    python/paddle/__init__.py package binds; distributed/launch.py is
    run as ``python -m paddle.distributed.launch``). Each dotted name
    must resolve through the import system, not just attribute access,
    and land on the same object the attribute exposes."""
    import importlib
    import subprocess
    import sys

    for spelling, attr_path in [
        ("paddle_tpu.tensor", "tensor"),
        ("paddle_tpu.tensor.creation", None),
        ("paddle_tpu.io", "io"),
        ("paddle_tpu.metric", "metric"),
        ("paddle_tpu.optimizer", "optimizer"),
        ("paddle_tpu.regularizer", "regularizer"),
        ("paddle_tpu.distributed", "distributed"),
        ("paddle_tpu.distributed.launch", None),
        ("paddle_tpu.fleet", "fleet"),
        ("paddle_tpu.imperative", "imperative"),
        ("paddle_tpu.static", "static"),
        ("paddle_tpu.device", "device"),
    ]:
        mod = importlib.import_module(spelling)
        if attr_path:
            assert getattr(pt, attr_path) is mod, spelling
    assert pt.tensor.concat is pt.concat
    assert pt.io.DataLoader is pt.DataLoader

    # python -m paddle_tpu.distributed.launch resolves (runpy path);
    # --help exits 0 without spawning workers
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch", "--help"],
        capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": "/root/repo"})
    assert r.returncode == 0, r.stderr[-500:]


def test_alias_submodules_share_identity():
    """Submodules imported through an alias package must be the SAME
    module object as the real spelling — a re-executed duplicate would
    carry independent state (e.g. a second dist/env.py whose mesh
    globals the real collectives never see)."""
    import importlib

    a = importlib.import_module("paddle_tpu.distributed.env")
    b = importlib.import_module("paddle_tpu.dist.env")
    assert a is b
    c = importlib.import_module("paddle_tpu.io.dataloader")
    d = importlib.import_module("paddle_tpu.io_.dataloader")
    assert c is d
    assert c.DataLoader is pt.DataLoader
    e = importlib.import_module("paddle_tpu.static.program")
    f = importlib.import_module("paddle_tpu.static_.program")
    assert e is f


def test_fleet_module_superset_of_singleton():
    """Both fleet spellings — the old ``distributed.fleet`` module and
    the ``paddle_tpu.fleet`` auto-parallel package that now owns the
    top-level alias — must expose the full singleton API via PEP 562
    forwarding (old fleet.* call sites resolve unchanged)."""
    import importlib

    m = importlib.import_module("paddle_tpu.distributed.fleet")
    m.init_worker()
    m.stop_worker()
    assert m.worker_num() >= 1
    assert callable(m.build_train_step)
    with pytest.raises(AttributeError):
        m.definitely_not_an_attr

    pkg = importlib.import_module("paddle_tpu.fleet")
    assert pt.fleet is pkg
    pkg.init_worker()
    pkg.stop_worker()
    assert pkg.worker_num() >= 1
    assert callable(pkg.build_train_step)
    assert pkg.DistributedStrategy is m.DistributedStrategy
    assert callable(pkg.auto_parallel)  # the new surface rides the alias
    with pytest.raises(AttributeError):
        pkg.definitely_not_an_attr
