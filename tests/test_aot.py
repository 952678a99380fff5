"""AOT executable cache (``paddle_tpu.runtime.aot``): cross-process
hydration, content-key drift, and per-site wiring.

The ISSUE-12 acceptance gates live here: a second process cold-starting
over a warm cache must record ZERO in-process XLA compiles in its run
journal and produce bitwise-identical fetches; any CacheKey drift
(changed feed shape, fused step count, parallelism layout) must MISS
and recompile — a stale load is structurally impossible because the key
is a content hash of the lowered module.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.runtime import aot

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_process_cache():
    """Tests drive the cache explicitly; none may leak one into the
    suite (configure() state or env would silently flip EVERY later
    compile onto the eager AOT path)."""
    saved = os.environ.pop(aot.ENV_DIR, None)
    yield
    aot.configure(None)
    if saved is not None:
        os.environ[aot.ENV_DIR] = saved


def _load_events(run_dir, kinds=("compile",)):
    evs = []
    for name in sorted(os.listdir(run_dir)):
        if not name.endswith(".jsonl"):
            continue
        with open(os.path.join(run_dir, name)) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except ValueError:
                    continue
                if r.get("t") == "event" and r.get("kind") in kinds:
                    evs.append(r)
    return evs


# -- cross-process hydration (the acceptance gate) ---------------------------


_PROC_SCRIPT = """
import os, sys
sys.path.insert(0, {root!r})
import numpy as np
import paddle_tpu as pt
import paddle_tpu.nn.functional as F
from paddle_tpu import optim

pt.seed(0)
rng = np.random.RandomState(0)
x = rng.randn(8, 4).astype("float32")
y = rng.randn(8, 1).astype("float32")
pt.enable_static()
try:
    main, startup = pt.static.Program(), pt.static.Program()
    with pt.program_guard(main, startup):
        xv = pt.static.data("x", [8, 4], "float32")
        yv = pt.static.data("y", [8, 1], "float32")
        h = pt.static.nn.fc(xv, 16, act="relu")
        out = pt.static.nn.fc(h, 1)
        loss = F.mse_loss(out, yv)
        optim.SGD(0.1).minimize(loss)
finally:
    pt.disable_static()
exe = pt.static.Executor()
exe.run(startup)
# two per-step dispatches + one fused K=2 window: both the single-step
# and the steps=K scan entries must ride the cache
outs = [np.asarray(exe.run(main, feed={{"x": x, "y": y}},
                           fetch_list=[loss])[0]) for _ in range(2)]
fused = exe.run_steps(main, feeds=[{{"x": x, "y": y}}] * 2,
                      fetch_list=[loss])
np.savez(os.path.join({out!r}), steps=np.stack(outs),
         fused=np.asarray(fused[0]))
"""


def _run_proc(tmp_path, tag, cache_dir):
    run_dir = str(tmp_path / f"run_{tag}")
    out = str(tmp_path / f"out_{tag}.npz")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PADDLE_TPU_AOT_CACHE=cache_dir, PADDLE_TPU_RUN_DIR=run_dir)
    r = subprocess.run(
        [sys.executable, "-c",
         _PROC_SCRIPT.format(root=ROOT, out=out)],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    return run_dir, np.load(out)


def test_second_process_cold_start_zero_compiles_bitwise(tmp_path):
    """Process A compiles + publishes; process B runs the SAME build
    with zero in-process XLA compiles — every compile event is
    via="aot_disk" — and bitwise-identical per-step AND fused
    fetches."""
    cache_dir = str(tmp_path / "cache")
    run_a, out_a = _run_proc(tmp_path, "a", cache_dir)
    run_b, out_b = _run_proc(tmp_path, "b", cache_dir)

    ev_a = _load_events(run_a)
    assert ev_a and all(e.get("via") == "xla" for e in ev_a), ev_a
    ev_b = _load_events(run_b)
    # THE gate: a warm cold start compiles nothing in-process
    assert ev_b and [e for e in ev_b if e.get("via") == "xla"] == [], ev_b
    assert sum(e.get("via") == "aot_disk" for e in ev_b) >= 2  # step+fused
    for e in ev_b:
        assert e.get("deserialize_ms", 0) >= 0
    assert np.array_equal(out_a["steps"], out_b["steps"])
    assert np.array_equal(out_a["fused"], out_b["fused"])


# -- content-key drift --------------------------------------------------------


def _build_fc(batch):
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optim

    pt.seed(0)
    pt.enable_static()
    try:
        main, startup = pt.static.Program(), pt.static.Program()
        with pt.program_guard(main, startup):
            xv = pt.static.data("x", [batch, 4], "float32")
            yv = pt.static.data("y", [batch, 1], "float32")
            loss = F.mse_loss(pt.static.nn.fc(xv, 4), yv)
            optim.SGD(0.1).minimize(loss)
    finally:
        pt.disable_static()
    return main, startup, loss


def _first_entry(exe):
    return next(iter(exe._cache.values()))


def test_cachekey_drift_misses_and_recompiles(tmp_path):
    """Changed feed shape, fused step count, or parallelism layout each
    produce a DIFFERENT content digest: a fresh compile, never a stale
    load — and the recompiled entries coexist in the cache."""
    cache = aot.configure(str(tmp_path / "cache"))
    rng = np.random.RandomState(0)

    def run(batch, steps=None, dp=False):
        main, startup, loss = _build_fc(batch)
        prog = main
        if dp:
            from paddle_tpu.static_.compiler import CompiledProgram

            prog = CompiledProgram(main).with_data_parallel(
                loss_name=loss.name)
        exe = pt.static.Executor()
        exe.run(startup)
        feed = {"x": rng.randn(batch, 4).astype("float32"),
                "y": rng.randn(batch, 1).astype("float32")}
        if steps:
            exe.run_steps(prog, feeds=[feed] * steps, fetch_list=[loss])
        else:
            exe.run(prog, feed=feed, fetch_list=[loss])
        return _first_entry(exe).aot_info

    base = run(8)
    assert base["source"] == "xla" and base["stored"]
    digests = {base["digest"]}
    for info in (run(16),            # feed-shape drift
                 run(8, steps=2),    # fused-K drift
                 run(8, steps=4),    # a different K is a different scan
                 run(8, dp=True)):   # layout drift (sharded module)
        assert info["source"] == "xla", info   # miss -> fresh compile
        assert info["digest"] not in digests, "stale digest reused"
        digests.add(info["digest"])
    # and the original still hydrates (nothing evicted or clobbered)
    again = run(8)
    assert again["source"] == "aot_disk", again
    assert cache.stats()["entries"] == len(digests)


# -- per-site wiring ----------------------------------------------------------


def test_trainstep_hydrates_bitwise(tmp_path):
    """Eager path: a rebuilt TrainStep over the same model (identical
    param names = identical calling convention; the opt-state dict
    keys are part of the digest) hydrates its per-signature executable
    from disk and reproduces the first build's loss trajectory
    bitwise. A model with DIFFERENT param names must miss instead —
    its treedef is a different calling convention."""
    import jax.numpy as jnp

    import paddle_tpu.nn as nn

    cache = aot.configure(str(tmp_path / "cache"))
    x = np.random.RandomState(0).randn(8, 16).astype("float32")
    y = np.random.RandomState(1).randn(8, 4).astype("float32")
    pt.seed(0)
    m = nn.Linear(16, 4)
    init = [np.asarray(p._data).copy() for p in m.parameters()]

    def losses():
        for p, a in zip(m.parameters(), init):
            p._data = jnp.asarray(a)  # rewind to the pristine replica
        opt = pt.optim.SGD(parameters=m.parameters(), learning_rate=0.1)
        step = pt.TrainStep(m, opt,
                            lambda mm, a, b: ((mm(a) - b) ** 2).mean())
        return [float(np.asarray(step(x, y)._data)) for _ in range(3)]

    la = losses()
    assert cache.stats()["stores"] == 1
    lb = losses()
    assert cache.stats()["hits"] == 1
    assert la == lb  # bitwise: identical executable, identical inputs

    # same math, new param NAMES: treedef drift -> a clean miss
    m2 = nn.Linear(16, 4)
    opt2 = pt.optim.SGD(parameters=m2.parameters(), learning_rate=0.1)
    pt.TrainStep(m2, opt2,
                 lambda mm, a, b: ((mm(a) - b) ** 2).mean())(x, y)
    assert cache.stats()["stores"] == 2


def test_the_compile_flow_is_on_the_obs_ring(tmp_path):
    """``load_or_compile`` writes a phase record round each stage, tracing
    on or off: a miss gives ``aot.lower``, ``aot.key``, ``aot.compile`` and
    ``aot.store``, a hit ``aot.lower``, ``aot.key`` and ``aot.load``, each
    with site, label, digest and source; ``info``'s milliseconds are the
    records' own durations (one measurement, two sinks), jax's own events
    are their children, and the registry counts the cache's answers."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import obs

    cache = aot.AOTCache(str(tmp_path / "cache"))
    args = (jnp.ones((4, 4)),)
    assert not obs.tracing_enabled()
    hits, misses = (obs.counter("aot.cache.hits").value,
                    obs.counter("aot.cache.misses").value)

    def flow():
        obs.clear_trace()
        fn = jax.jit(lambda x: jnp.tanh(x @ x) + 0.125)
        exe, info = aot.load_or_compile(fn, args, "trainstep", cache=cache,
                                        label="Tiny")
        events = obs.trace_events()
        return info, events, [e for e in events
                              if e["name"].startswith("aot.")]

    info, events, stages = flow()
    assert [e["name"] for e in stages] == \
        ["aot.lower", "aot.key", "aot.compile", "aot.store"]
    for e in stages:
        assert e["args"] == {"site": "trainstep", "label": "Tiny",
                             "digest": info["digest"], "source": "xla"}
        assert e["parent"] is None
    by_name = {e["name"]: e for e in stages}
    assert info["xla_compile_ms"] == \
        pytest.approx(by_name["aot.compile"]["dur"] / 1e3, rel=1e-9)
    parents = {e["name"]: e["parent"] for e in events
               if e["name"] in ("jax.lower", "jax.backend_compile")}
    assert parents == {"jax.lower": by_name["aot.lower"]["id"],
                       "jax.backend_compile": by_name["aot.compile"]["id"]}
    assert obs.counter("aot.cache.misses").value == misses + 1 == \
        misses + cache.misses

    info, events, stages = flow()
    assert [e["name"] for e in stages] == ["aot.lower", "aot.key", "aot.load"]
    assert {e["args"]["source"] for e in stages} == {"aot_disk"}
    assert info["deserialize_ms"] == \
        pytest.approx(stages[-1]["dur"] / 1e3, rel=1e-9)
    assert not [e for e in events if e["name"] == "jax.backend_compile"]
    assert obs.counter("aot.cache.hits").value == hits + 1 == \
        hits + cache.hits


def test_predictor_warm_export_and_hydration(tmp_path):
    """save_inference_model with a cache active ships a warm batch-1
    entry (the Predictor-path executable); a fresh Predictor then
    hydrates it and matches a cache-less Predictor bitwise."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.io import save_inference_model
    from paddle_tpu.inference.predictor import Config, Predictor

    prefix = str(tmp_path / "model" / "m")
    pt.seed(0)
    pt.enable_static()
    try:
        main, startup = pt.static.Program(), pt.static.Program()
        with pt.program_guard(main, startup):
            xv = pt.static.data("x", [1, 8], "float32")
            out = F.softmax(pt.static.nn.fc(xv, 4))
        exe = pt.static.Executor()
        exe.run(startup)
        cache = aot.configure(str(tmp_path / "cache"))
        save_inference_model(prefix, [xv], [out], exe,
                             program=main)
    finally:
        pt.disable_static()
        aot.configure(None)
    assert cache.stats()["stores"] >= 1  # the warm export published

    x = np.random.RandomState(0).randn(1, 8).astype("float32")
    oracle = Predictor(Config(prefix)).run({"x": x})[0]

    cfg = Config(prefix)
    cfg.aot_cache_dir = cache.dir
    hits0 = cache.stats()["hits"]
    got = Predictor(cfg).run({"x": x})[0]
    assert cache.stats()["hits"] == hits0 + 1
    assert np.array_equal(oracle, got)


def test_serve_engine_hydrates_identical_tokens(tmp_path):
    """A rebuilt ServeEngine replica hydrates its prefill + decode
    bucket executables from disk and generates identical tokens."""
    from paddle_tpu.serving.engine import ServeEngine, TinyLM
    from paddle_tpu.serving.kv_cache import PagedKVCache

    cache_dir = str(tmp_path / "cache")

    def serve():
        model = TinyLM(vocab_size=32, num_heads=2, head_dim=8, seed=3)
        kv = PagedKVCache(16, 4, 2, 8, max_seq_len=16)
        eng = ServeEngine(model, kv, aot_cache_dir=cache_dir)
        r = eng.submit([3, 1, 4, 1, 5], max_new_tokens=6)
        eng.run()
        return list(r.generated)

    toks_a = serve()
    cache = aot.resolve_cache(cache_dir)
    stores = cache.stats()["stores"]
    assert stores >= 2  # prefill bucket + decode bucket
    toks_b = serve()
    assert cache.stats()["hits"] >= 2
    assert cache.stats()["stores"] == stores  # nothing recompiled
    assert toks_a == toks_b


def test_hydrated_entry_keeps_donation(tmp_path):
    """perf_gate.donation_stats on a hydrated Executor entry: the
    donated persistable carry survives the serialize round-trip (the
    acceptance criterion's donation check)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "pg_aot", os.path.join(ROOT, "tools", "perf_gate.py"))
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)

    aot.configure(str(tmp_path / "cache"))
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(8, 4).astype("float32"),
            "y": rng.randn(8, 1).astype("float32")}

    def entry():
        main, startup, loss = _build_fc(8)
        exe = pt.static.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        return _first_entry(exe)

    entry()                       # publish
    hydrated = entry()            # hydrate
    assert (hydrated.aot_info or {}).get("source") == "aot_disk"
    stats = pg.donation_stats(pg.entry_hlo(hydrated))
    assert stats["count"] >= 1, stats
