"""paddle_tpu.obs: metrics registry, span tracer, and the
instrumentation woven through executor / dispatch / dataloader /
resilience / checkpoint IO.

The registry is process-wide by design, so tests that assert absolute
values call ``obs.metrics.reset()`` first (reset zeroes in place and
keeps registrations — exactly what the hot paths' interned references
rely on).
"""
import json
import os
import tempfile
import threading
import warnings

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import obs


@pytest.fixture
def tracing():
    """Clean, enabled tracer for one test; restores the prior state."""
    was_on = obs.tracing_enabled()
    obs.clear_trace()
    obs.enable_tracing()
    yield
    if not was_on:
        obs.disable_tracing()
    obs.clear_trace()


# -- metrics registry --------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_gauge_histogram_roundtrip(self):
        reg = obs.Registry()
        c = reg.counter("c")
        c.inc()
        c.inc(4)
        g = reg.gauge("g")
        g.set(7)
        g.dec(2)
        h = reg.histogram("h")
        for v in (1.0, 2.0, 100.0):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["c"] == 5
        assert snap["g"] == 5
        assert snap["h"]["count"] == 3
        assert snap["h"]["min"] == 1.0 and snap["h"]["max"] == 100.0
        assert snap["h"]["sum"] == pytest.approx(103.0)

    def test_get_or_create_interns_by_name(self):
        reg = obs.Registry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")  # name already a Counter

    def test_snapshot_is_json_safe_and_reset_keeps_registrations(self):
        reg = obs.Registry()
        c = reg.counter("a.b")
        c.inc(3)
        reg.histogram("a.h").observe(1.5)
        json.dumps(reg.snapshot())  # plain data, no instrument objects
        reg.reset()
        snap = reg.snapshot()
        assert snap["a.b"] == 0
        assert snap["a.h"] == {"count": 0}
        assert reg.counter("a.b") is c  # same object, zeroed in place
        c.inc()
        assert reg.snapshot()["a.b"] == 1

    def test_thread_safety_smoke(self):
        reg = obs.Registry()
        c = reg.counter("n")
        h = reg.histogram("h")

        def work():
            for i in range(1000):
                c.inc()
                h.observe(float(i % 7))

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000
        assert h.count == 8000

    def test_histogram_percentiles_ordered(self):
        h = obs.Histogram("lat", buckets=(1, 2, 4, 8, 16, 32))
        rng = np.random.RandomState(0)
        for v in rng.uniform(0.0, 30.0, size=2000):
            h.observe(v)
        p50, p90, p99 = (h.percentile(q) for q in (50, 90, 99))
        assert 0.0 < p50 < p90 < p99 <= 30.0
        assert p50 == pytest.approx(15.0, abs=2.0)  # uniform median

    def test_histogram_rejects_unsorted_buckets(self):
        with pytest.raises(ValueError):
            obs.Histogram("bad", buckets=(5, 1))


# -- span tracer -------------------------------------------------------------


class TestTrace:
    def test_disabled_span_records_nothing(self):
        was_on = obs.tracing_enabled()
        obs.disable_tracing()
        try:
            obs.clear_trace()
            with obs.span("ghost"):
                pass
            assert obs.trace_events() == []
        finally:
            if was_on:
                obs.enable_tracing()

    def test_nested_spans_chrome_roundtrip(self, tracing):
        with obs.span("outer", step=1):
            with obs.span("inner", kind="child"):
                pass
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "trace.json")
            n = obs.export_chrome_trace(path)
            with open(path) as f:
                doc = json.load(f)  # valid JSON or this raises
        assert n == 2
        spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        outer, inner = spans["outer"], spans["inner"]
        assert outer["args"] == {"step": 1}
        assert inner["tid"] == outer["tid"]
        # containment: the child lies inside the parent's [ts, ts+dur]
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        assert all(e["ph"] in ("X", "M") for e in doc["traceEvents"])

    def test_ring_buffer_bounds_span_count(self, tracing):
        obs.enable_tracing(capacity=16)
        try:
            for i in range(64):
                with obs.span(f"s{i}"):
                    pass
            events = obs.trace_events()
            assert len(events) == 16
            assert events[-1]["name"] == "s63"  # newest win
        finally:
            obs.enable_tracing(capacity=obs.trace.DEFAULT_CAPACITY)

    def test_unserializable_attr_degrades_to_str(self, tracing):
        with obs.span("odd", what=object()):
            pass
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "t.json")
            obs.export_chrome_trace(path)
            with open(path) as f:
                doc = json.load(f)
        (ev,) = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert "object object" in ev["args"]["what"]


# -- phase records: the second way into the same ring -------------------------


@pytest.fixture
def tracing_off():
    """Span tracing off and both stores empty for one test (a long
    process's store of phase records may be full)."""
    was_on = obs.tracing_enabled()
    obs.disable_tracing()
    obs.clear_trace()
    yield
    obs.clear_trace()
    if was_on:
        obs.enable_tracing()


class TestPhaseRecords:
    def test_a_phase_is_recorded_with_tracing_off_and_a_span_is_not(
            self, tracing_off):
        assert obs.span("a", x=1) is obs.span("b")     # the shared null
        with obs.trace.phase("startup.made_up", name="w", bytes=8):
            with obs.span("ghost"):
                pass
        (ev,) = obs.trace_events()
        assert ev["name"] == "startup.made_up"
        assert ev["args"] == {"name": "w", "bytes": 8}
        assert ev["parent"] is None and ev["dur"] >= 0

    def test_ids_and_parents_nest_across_phase_and_span(self, tracing):
        with obs.trace.phase("outer"):
            with obs.span("middle"):
                with obs.trace.phase("inner"):
                    pass
            with obs.span("second"):
                pass
        by_name = {e["name"]: e for e in obs.trace_events()}
        assert len({e["id"] for e in by_name.values()}) == 4
        assert by_name["outer"]["parent"] is None
        assert by_name["middle"]["parent"] == by_name["outer"]["id"]
        assert by_name["inner"]["parent"] == by_name["middle"]["id"]
        assert by_name["second"]["parent"] == by_name["outer"]["id"]
        # by start time, whichever store holds them
        assert [e["name"] for e in obs.trace_events()] == \
            ["outer", "middle", "inner", "second"]

    def test_parents_do_not_cross_threads(self, tracing_off):
        inside = threading.Event()
        leave = threading.Event()

        def other():
            with obs.trace.phase("theirs"):
                inside.set()
                leave.wait(5)

        t = threading.Thread(target=other)
        t.start()
        inside.wait(5)
        with obs.trace.phase("mine"):      # opened while "theirs" is open
            pass
        leave.set()
        t.join()
        by_name = {e["name"]: e for e in obs.trace_events()}
        assert by_name["mine"]["parent"] is None
        assert by_name["theirs"]["parent"] is None
        assert by_name["mine"]["tid"] != by_name["theirs"]["tid"]

    def test_a_record_after_the_fact_adopts_what_closed_inside_it(
            self, tracing_off):
        """jax's events arrive with a duration, as the work ends: an outer
        trace after the inner ones it held, a cache load before the backend
        compile that asked for it."""
        import time

        clock = time.perf_counter
        with obs.trace.phase("aot.compile"):
            t0 = clock()
            time.sleep(0.002)
            obs.trace.record("before", t0, clock())
            t1 = clock()
            time.sleep(0.002)
            t2 = clock()
            time.sleep(0.002)
            obs.trace.record("jax.cache_load", t2, clock(), event="load")
            time.sleep(0.002)
            obs.trace.record("jax.backend_compile", t1, clock())
        by_name = {e["name"]: e for e in obs.trace_events()}
        top = by_name["aot.compile"]["id"]
        assert by_name["before"]["parent"] == top
        assert by_name["jax.backend_compile"]["parent"] == top
        assert by_name["jax.cache_load"]["parent"] == \
            by_name["jax.backend_compile"]["id"]
        # and a record written at the top adopts a context that closed in it
        began = clock()
        time.sleep(0.001)
        with obs.trace.phase("startup.param_init"):
            pass
        obs.trace.record("startup.import", began, clock())
        by_name = {e["name"]: e for e in obs.trace_events()}
        assert by_name["startup.param_init"]["parent"] == \
            by_name["startup.import"]["id"]
        assert by_name["aot.compile"]["parent"] is None

    def test_start_up_records_outlive_a_ring_overflow(self, tracing):
        obs.enable_tracing(capacity=16)
        try:
            with obs.trace.phase("startup.made_up"):
                pass
            for i in range(64):
                with obs.span(f"s{i}"):
                    pass
            names = [e["name"] for e in obs.trace_events()]
            assert names[0] == "startup.made_up" and names[-1] == "s63"
            assert len(names) == 17
        finally:
            obs.enable_tracing(capacity=obs.trace.DEFAULT_CAPACITY)

    def test_the_phase_store_keeps_the_oldest(self, tracing_off,
                                              monkeypatch):
        monkeypatch.setattr(obs.trace, "PHASE_CAPACITY", 4)
        for i in range(8):
            with obs.trace.phase(f"p{i}"):
                pass
        assert [e["name"] for e in obs.trace_events()] == \
            ["p0", "p1", "p2", "p3"]

    def test_chrome_export_holds_phases_on_the_perf_counter_clock(
            self, tracing_off):
        import time

        t0 = time.perf_counter()
        with obs.trace.phase("aot.lower", site="trainstep"):
            pass
        obs.trace.record("startup.import", t0, time.perf_counter())
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "t.json")
            assert obs.export_chrome_trace(path) == 2
            with open(path) as f:
                doc = json.load(f)
        spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        assert spans["aot.lower"]["args"] == {"site": "trainstep"}
        assert spans["aot.lower"]["parent_id"] == \
            spans["startup.import"]["span_id"]
        ev = {e["name"]: e for e in obs.trace_events()}["startup.import"]
        assert obs.trace.to_perf_counter(ev["ts"]) == pytest.approx(t0)
        assert obs.trace.to_perf_counter(ev["ts"] + ev["dur"]) <= \
            time.perf_counter()

    def test_jax_compiles_become_records_and_cache_events_counters(
            self, tracing_off):
        """``core/device.py`` listens once a process: a program jax traces,
        lowers and compiles is three records with the event's name, under
        whatever is open; the persistent cache's answers are counters."""
        import jax
        import jax.numpy as jnp

        with obs.trace.phase("startup.param_init", name="w", bytes=16):
            jax.jit(lambda x: jnp.tanh(x) * 3.25)(jnp.ones((4,)))
        events = obs.trace_events()
        by_id = {e["id"]: e for e in events}
        init = next(e for e in events if e["name"] == "startup.param_init")
        compiled = [e for e in events if e["name"] == "jax.backend_compile"]
        assert {"jax.trace", "jax.lower", "jax.backend_compile"} <= \
            {e["name"] for e in events}
        assert compiled[-1]["args"]["event"] == \
            "/jax/core/compile/backend_compile_duration"
        for e in events:
            if e is not init:       # every one under the open phase
                while e["parent"] != init["id"]:
                    e = by_id[e["parent"]]
        hits = obs.counter("jax.cache.hits").value
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        assert obs.counter("jax.cache.hits").value == hits + 1
        assert obs.counter("jax.cache.misses").value >= 1

    def test_a_trainsteps_second_call_makes_no_phase_record(
            self, tracing_off):
        import paddle_tpu.nn as nn

        pt.seed(0)
        m = nn.Linear(8, 2)
        step = pt.TrainStep(
            m, pt.optim.SGD(parameters=m.parameters(), learning_rate=0.1),
            lambda mm, a, b: ((mm(a) - b) ** 2).mean())
        x = np.ones((4, 8), np.float32)
        y = np.zeros((4, 2), np.float32)
        step(x, y)
        first = [e for e in obs.trace_events()
                 if e["name"] == "trainstep.first_execute"]
        assert len(first) == 1 and "(4, 8)" in first[0]["args"]["sig"]
        # no cache active: the lazy jit compiled under it
        assert any(e["name"] == "jax.backend_compile" and
                   e["parent"] == first[0]["id"]
                   for e in obs.trace_events())
        obs.clear_trace()
        float(step(x, y).numpy())
        assert obs.trace_events() == []
        # another signature is a first call again
        step(np.ones((2, 8), np.float32), np.zeros((2, 2), np.float32))
        assert [e["name"] for e in obs.trace_events()
                if e["name"].startswith("trainstep.")] == \
            ["trainstep.first_execute"]

    def test_gauges_are_collected_when_the_registry_is_read(self):
        """A model's ``publish_gauges`` runs when the registry is read, by
        whichever reader (a snapshot, the Prometheus lines, the time
        series' snapshot that the SLO tick takes), not after a dispatch;
        held weakly, it goes with its model; one that fails leaves the
        reader its answer and is named in one warning."""
        import gc

        from paddle_tpu.obs import export, timeseries

        reg = obs.Registry()

        class Model:
            calls = 0

            def publish_gauges(self):
                Model.calls += 1
                reg.gauge("moe.made_up").set(Model.calls)

        class Broken:
            def publish_gauges(self):
                raise RuntimeError("the buffer was donated")

        model, broken = Model(), Broken()
        reg.add_collector(model.publish_gauges)
        reg.add_collector(model.publish_gauges)    # once, however often
        reg.add_collector(broken.publish_gauges)
        assert Model.calls == 0
        with pytest.warns(RuntimeWarning, match="Broken.publish_gauges "
                          "failed .*the buffer was donated"):
            assert reg.snapshot()["moe.made_up"] == 1
        with warnings.catch_warnings():
            warnings.simplefilter("error")         # said once, not again
            assert "paddle_tpu_moe_made_up 2.0" in \
                export.registry_lines(reg)
            assert timeseries.registry_snapshot(reg)["moe.made_up"] == \
                ("gauge", 3.0)
            del model
            gc.collect()
            assert reg.snapshot()["moe.made_up"] == 3


# -- instrumentation: static executor ----------------------------------------


def _build_train_parts():
    import paddle_tpu.fluid as fluid

    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[8, 4])
        y = fluid.data(name="y", shape=[8, 1])
        out = fluid.layers.fc(x, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(out, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return prog, startup, loss


def _feed(i=0):
    rng = np.random.RandomState(i)
    return {"x": rng.randn(8, 4).astype(np.float32),
            "y": rng.randn(8, 1).astype(np.float32)}


class TestExecutorInstrumentation:
    def test_train_loop_cache_counters_and_trace(self, tracing):
        import paddle_tpu.fluid as fluid

        pt.enable_static()
        try:
            pt.seed(0)
            prog, startup, loss = _build_train_parts()
            obs.metrics.reset()
            exe = fluid.Executor()
            exe.run(startup)  # empty program: no compile, no counters
            for i in range(3):
                exe.run(prog, feed=_feed(i), fetch_list=[loss])
            snap = obs.snapshot()
            # one program signature => exactly one compile; the acceptance
            # contract: snapshot's hit/miss counts match the compile count
            assert snap["executor.jit_cache.misses"] == 1
            assert snap["executor.jit_cache.hits"] == 2
            assert snap["executor.compile_ms"]["count"] == 1
            assert snap["executor.run_ms"]["count"] == 3
            assert snap["executor.fetch_ms"]["count"] == 3
            assert exe.cache_stats() == {"hits": 2, "misses": 1, "size": 1}
            # optimize-pass attribution reached the registry
            assert snap["analysis.pass.verifier.ms"]["count"] >= 1
        finally:
            pt.disable_static()
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "t.json")
            obs.export_chrome_trace(path)
            with open(path) as f:
                names = [e["name"] for e in json.load(f)["traceEvents"]]
        assert names.count("executor.compile") == 1
        assert names.count("executor.run") == 3

    def test_cache_stats_per_executor_not_global(self):
        import paddle_tpu.fluid as fluid

        pt.enable_static()
        try:
            pt.seed(0)
            prog, startup, loss = _build_train_parts()
            a, b = fluid.Executor(), fluid.Executor()
            a.run(startup)
            a.run(prog, feed=_feed(), fetch_list=[loss])
            a.run(prog, feed=_feed(), fetch_list=[loss])
            assert a.cache_stats() == {"hits": 1, "misses": 1, "size": 1}
            assert b.cache_stats() == {"hits": 0, "misses": 0, "size": 0}
        finally:
            pt.disable_static()


# -- instrumentation: eager dispatch sampling --------------------------------


class TestDispatchSampling:
    def test_off_by_default_and_counts_when_enabled(self):
        obs.metrics.reset()
        a = pt.to_tensor(np.ones((2, 2), np.float32))
        pt.add(a, a)
        assert obs.snapshot().get("dispatch.ops_total", 0) == 0
        obs.enable_op_sampling()
        try:
            pt.add(a, a)
            pt.matmul(a, a)
        finally:
            obs.disable_op_sampling()
        pt.add(a, a)  # after disable: not counted
        snap = obs.snapshot()
        assert snap["dispatch.ops_total"] == 2
        assert snap["dispatch.op.matmul"] == 1
        assert snap["dispatch.op.add"] == 1

    def test_stride_sampling_scales_counts(self):
        obs.metrics.reset()
        a = pt.to_tensor(np.ones((2, 2), np.float32))
        obs.enable_op_sampling(every=4)
        try:
            for _ in range(8):
                pt.add(a, a)
        finally:
            obs.disable_op_sampling()
        # one in four sampled, scaled back up: unbiased total estimate
        assert obs.snapshot()["dispatch.ops_total"] == 8


# -- instrumentation: dataloader ---------------------------------------------


class _Squares(pt.io.Dataset):
    def __len__(self):
        return 16

    def __getitem__(self, i):
        return np.float32(i * i)


class TestDataLoaderInstrumentation:
    def test_wait_histograms_and_queue_gauge(self, tracing):
        from paddle_tpu.io_.dataloader import DataLoader

        obs.metrics.reset()
        dl = DataLoader(_Squares(), batch_size=4, num_workers=2,
                        return_list=False)
        batches = [np.asarray(b) for b in dl]
        assert len(batches) == 4
        snap = obs.snapshot()
        assert snap["dataloader.producer_wait_ms"]["count"] == 4
        assert snap["dataloader.consumer_wait_ms"]["count"] >= 4
        assert "dataloader.queue_depth" in snap
        # 4 batch waits (+1 recorded for the end-of-epoch wait that
        # raised StopIteration)
        assert sum(1 for e in obs.trace_events()
                   if e["name"] == "dataloader.next") >= 4

    def test_worker_restart_counter(self):
        from paddle_tpu.io_.dataloader import DataLoader
        from paddle_tpu.resilience import inject

        obs.metrics.reset()
        with inject.chaos("loader_worker", at=2):
            dl = DataLoader(_Squares(), batch_size=4, num_workers=2,
                            return_list=False)
            batches = [np.asarray(b) for b in dl]
        assert len(batches) == 4  # restart budget absorbed the crash
        assert obs.snapshot()["dataloader.worker_restarts"] == 1


# -- instrumentation: resilience ---------------------------------------------


class TestResilienceInstrumentation:
    def test_chaos_retry_ticks_global_counter(self):
        from paddle_tpu.resilience import (GuardedExecutor, RecoveryPolicy,
                                           inject)

        pt.enable_static()
        try:
            pt.seed(0)
            prog, startup, loss = _build_train_parts()
            obs.metrics.reset()
            gexe = GuardedExecutor(policy=RecoveryPolicy(
                sleep=lambda s: None))
            gexe.run(startup)
            with inject.chaos("transient_execute", times=2):
                for i in range(3):
                    gexe.run(prog, feed=_feed(i), fetch_list=[loss])
            snap = obs.snapshot()
            assert snap["resilience.retries"] == 2 == gexe.stats.retries
            assert snap["resilience.steps"] == 3 == gexe.stats.steps
        finally:
            pt.disable_static()

    def test_skip_step_mirrors_into_registry(self):
        from paddle_tpu.resilience import (GuardedExecutor, RecoveryPolicy,
                                           inject)

        pt.enable_static()
        try:
            pt.seed(0)
            prog, startup, loss = _build_train_parts()
            obs.metrics.reset()
            gexe = GuardedExecutor(policy=RecoveryPolicy(
                on_nonfinite="skip_step", sleep=lambda s: None))
            gexe.run(startup)
            with inject.chaos("nan_feed", at=2, seed=3):
                for i in range(3):
                    gexe.run(prog, feed=_feed(i), fetch_list=[loss])
            snap = obs.snapshot()
            assert snap["resilience.nonfinite"] == 1
            assert snap["resilience.skipped"] == 1
            assert snap["resilience.steps"] == 2
        finally:
            pt.disable_static()


# -- instrumentation: checkpoint IO ------------------------------------------


class TestCheckpointInstrumentation:
    def test_save_load_verify_fallback_metrics(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.framework.io import (load_checkpoint,
                                             save_checkpoint,
                                             verify_checkpoint)

        obs.metrics.reset()
        with tempfile.TemporaryDirectory() as d:
            pt.seed(0)
            m = nn.Linear(4, 2)
            save_checkpoint(d, 1, model=m)
            save_checkpoint(d, 2, model=m)
            ok, _ = verify_checkpoint(os.path.join(d, "ckpt_2"))
            assert ok
            with open(os.path.join(d, "ckpt_2", "model.pdparams"),
                      "r+b") as f:
                f.truncate(4)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                assert load_checkpoint(d, model=nn.Linear(4, 2)) == 1
        snap = obs.snapshot()
        assert snap["checkpoint.saves"] == 2
        assert snap["checkpoint.save_ms"]["count"] == 2
        assert snap["checkpoint.loads"] == 1
        assert snap["checkpoint.load_ms"]["count"] == 1
        assert snap["checkpoint.verify_ms"]["count"] == 1
        assert snap["checkpoint.fallbacks"] == 1


# -- profiler rebases --------------------------------------------------------


class TestProfilerRebase:
    def test_step_timer_p99_and_registry(self):
        from paddle_tpu.utils.profiler import StepTimer

        obs.metrics.reset()
        t = StepTimer(skip_first=1)
        for _ in range(5):
            with t.step():
                pass
        s = t.summary()
        assert s["steps"] == 4
        assert s["p50_ms"] <= s["p90_ms"] <= s["p99_ms"] <= s["max_ms"]
        assert obs.snapshot()["step_timer.step_ms"]["count"] == 4
        t.reset()
        assert t.summary() == {"steps": 0}

    def test_fluid_profiler_block_records_spans(self):
        import paddle_tpu.fluid as fluid

        was_on = obs.tracing_enabled()
        obs.disable_tracing()  # the profiler window must enable it itself
        obs.clear_trace()
        try:
            with fluid.profiler.profiler("All", "total"):
                with fluid.profiler.span("user.block", tag=1):
                    pass
            names = [e["name"] for e in obs.trace_events()]
            assert "user.block" in names
            assert "profiler.window" in names
            # the window closed tracing again (it was off before)
            assert not obs.tracing_enabled()
        finally:
            if was_on:
                obs.enable_tracing()
            obs.clear_trace()
