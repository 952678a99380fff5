"""Flight recorder (obs.journal) + MFU/goodput (obs.mfu) + anomaly
detectors (obs.anomaly): the per-run telemetry layer over PR 3's
process-wide instruments.

Covers the PR's acceptance contract:
- a GuardedStep training loop under chaos (nonfinite feed +
  transient_execute) journals step records, retry/skip events, a fired
  nonfinite_streak anomaly, and an MFU/goodput run summary;
- with no journal configured the hooks perform zero journal work beyond
  a single None check (asserted by poisoning the RunJournal methods);
- two threads stepping one journal interleave to valid JSONL;
- an exception mid-run still yields a parseable postmortem file.
"""
import json
import os
import threading

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.fluid as fluid
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import obs, optim
from paddle_tpu.obs import anomaly, journal, mfu
from paddle_tpu.resilience import (GuardedExecutor, GuardedStep,
                                   RecoveryPolicy, inject)

NOSLEEP = {"sleep": lambda s: None}


@pytest.fixture(autouse=True)
def _no_global_journal():
    """Tests install journals explicitly; never leak one across tests."""
    yield
    if journal.ACTIVE is not None:
        journal.ACTIVE.close()
    journal.ACTIVE = None


def _read_journal(run_dir):
    out = []
    with open(os.path.join(run_dir, "journal.jsonl")) as f:
        for line in f:
            if line.strip():
                out.append(json.loads(line))
    return out


def _load_run_report():
    """The tools/run_report.py module, loaded the way test_tooling's
    _load_tool does (tools/ is not a package)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "run_report_under_test", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "run_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _eager_guard(policy_kw=None):
    pt.seed(0)
    m = nn.Linear(4, 1)
    opt = optim.SGD(learning_rate=0.1, parameters=m.parameters())
    step = pt.TrainStep(m, opt, lambda mm, x, y: F.mse_loss(mm(x), y),
                        check_nan=True)
    pol = RecoveryPolicy(**{"on_nonfinite": "skip_step", **NOSLEEP,
                            **(policy_kw or {})})
    return GuardedStep(step, pol)


def _batches(n, batch=8):
    rng = np.random.RandomState(0)
    return [(rng.randn(batch, 4).astype(np.float32),
             rng.randn(batch, 1).astype(np.float32)) for _ in range(n)]


def _static_loop(exe, steps=3):
    pt.seed(0)
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[8, 4])
        y = fluid.data(name="y", shape=[8, 1])
        out = fluid.layers.fc(x, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(out, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe.run(startup)
    for bx, by in _batches(steps):
        exe.run(prog, feed={"x": bx, "y": by}, fetch_list=[loss])


# -- acceptance: guarded chaos run produces the full flight record -----------


class TestGuardedChaosRun:
    def test_journal_has_steps_retries_skips_anomaly_and_summary(
            self, tmp_path):
        run_dir = str(tmp_path / "run")
        obs.start_run(run_dir, flush_every=1)
        guard = _eager_guard()
        # nonfinite feed for 3 CONSECUTIVE steps (the streak detector's
        # default threshold) + two transient execute faults retried away
        with inject.chaos("nan_feed", at=3, times=3, seed=7):
            with inject.chaos("transient_execute", times=2):
                for x, y in _batches(8):
                    guard(x, y)
        assert guard.stats.skipped == 3 and guard.stats.retries == 2
        summary = obs.end_run()

        recs = _read_journal(run_dir)
        types = {}
        for r in recs:
            types[r["t"]] = types.get(r["t"], 0) + 1
        assert types.get("run_start") == 1 and types.get("run_end") == 1
        assert types.get("step") == 8

        steps = [r for r in recs if r["t"] == "step"]
        assert sum(1 for s in steps if s.get("skipped")) == 3
        good = [s for s in steps if not s.get("skipped")]
        assert all(isinstance(s["loss"], float) for s in good)
        assert all(s.get("step_ms", 0) > 0 for s in steps)

        kinds = [r["kind"] for r in recs if r["t"] == "event"]
        assert kinds.count("resilience.retry") == 2
        assert kinds.count("resilience.skipped") == 3
        assert kinds.count("resilience.nonfinite") == 3
        assert "chaos.activate" in kinds  # the drill is in the record

        fired = {r["name"] for r in recs if r["t"] == "anomaly"}
        assert "nonfinite_streak" in fired

        # MFU/goodput summary: 8 productive-attempted, 3 skipped + 2
        # retried burned; eager path has no cost_analysis flops => mfu
        # is None but goodput accounting must be exact
        assert summary["goodput"] == pytest.approx(5 / 10)
        assert summary["skipped_steps"] == 3 and summary["retries"] == 2
        end = [r for r in recs if r["t"] == "run_end"][0]
        assert end["summary"]["goodput"] == pytest.approx(5 / 10)
        assert "mfu" in end["summary"]

    def test_static_guarded_executor_steps_and_flops(self, tmp_path):
        run_dir = str(tmp_path / "run")
        obs.start_run(run_dir, flush_every=1)
        pt.enable_static()
        try:
            gexe = GuardedExecutor(policy=RecoveryPolicy(**NOSLEEP))
            with inject.chaos("transient_execute", times=1):
                _static_loop(gexe, steps=3)
        finally:
            pt.disable_static()
        obs.end_run()
        recs = _read_journal(run_dir)
        steps = [r for r in recs if r["t"] == "step"]
        assert len(steps) == 3 and all(
            s["source"] == "executor" for s in steps)
        # first step carries the compile (jit-cache miss delta), later
        # ones are hits; CPU cost_analysis reports flops for MFU
        assert steps[0]["jit_cache"]["misses"] >= 1
        assert steps[-1]["jit_cache"]["hits"] >= 1
        assert [r for r in recs if r["t"] == "event"
                and r["kind"] == "compile"]
        assert all(s.get("examples") == 8 for s in steps)
        summary = [r for r in recs if r["t"] == "run_end"][0]["summary"]
        assert summary["retries"] == 1
        if steps[0].get("flops"):  # backend-dependent, exact when there
            assert summary["achieved_flops_per_s"] > 0

    def test_static_skip_reclassifies_executor_step(self, tmp_path):
        run_dir = str(tmp_path / "run")
        obs.start_run(run_dir, flush_every=1)
        pt.enable_static()
        try:
            gexe = GuardedExecutor(policy=RecoveryPolicy(
                on_nonfinite="skip_step", **NOSLEEP))
            import warnings

            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                with inject.chaos("nan_feed", at=2, seed=7):
                    _static_loop(gexe, steps=3)
        finally:
            pt.disable_static()
        assert gexe.stats.skipped == 1
        summary = obs.end_run()
        assert summary["skipped_steps"] == 1
        assert summary["productive_steps"] == 2
        # a NaN that reaches the fetches is durable in the step line
        # itself (nonfinite flag) — no reclassify needed
        recs = _read_journal(run_dir)
        bad = [r for r in recs if r["t"] == "step" and r.get("nonfinite")]
        assert len(bad) == 1
        rr = _load_run_report()
        run = rr.load_run(run_dir)
        assert len(rr._finite_losses(run)) == 2  # NaN step excluded
        # lazy backend event folded back into the header by the loader
        assert run["header"]["backend"] == "cpu"

    def test_late_skip_reclassifies_durably(self, tmp_path):
        """The scan_state case: the executor records a productive step
        (finite loss) and the guard discards it AFTERWARDS. The step's
        JSONL line is already flushed, so the correction must ride the
        resilience.skipped event and be applied by the loader."""
        run_dir = str(tmp_path / "run")
        j = journal.RunJournal(run_dir, flush_every=1,
                               compute_flops=False).start()
        j.record_step(loss=1.0, step_ms=5.0, source="executor")
        j.record_step(loss=0.9, step_ms=5.0, source="executor")
        ev = j.event("resilience.skipped", source="guarded_executor")
        assert ev["reclassified_step"] == 2
        j.close()
        assert j.accounting.skipped == 1 and j.accounting.productive == 1
        rr = _load_run_report()
        run = rr.load_run(run_dir)
        flags = [s.get("skipped", False) for s in run["steps"]]
        assert flags == [False, True]  # durable despite the early flush
        assert rr._finite_losses(run) == [1.0]

    def test_eager_skip_never_reclassifies_a_static_step(self, tmp_path):
        """Mixed usage: a static eval step followed by an eager
        GuardedStep skip must not reclassify the (unrelated) executor
        step — the eager guard records its own skipped step."""
        run_dir = str(tmp_path / "run")
        obs.start_run(run_dir, flush_every=1)
        pt.enable_static()
        try:
            _static_loop(fluid.Executor(), steps=1)  # productive eval
        finally:
            pt.disable_static()
        guard = _eager_guard()
        with inject.chaos("nan_feed", at=1, times=1, seed=7):
            guard(*_batches(1)[0])  # skipped eager step
        summary = obs.end_run()
        assert summary["productive_steps"] == 1  # the eval step survives
        assert summary["skipped_steps"] == 1     # counted exactly once
        recs = _read_journal(run_dir)
        assert not any("reclassified_step" in r for r in recs
                       if r["t"] == "event")

    def test_second_run_into_same_dir_keeps_rotated_parts(self, tmp_path):
        """Rotation numbering must continue across runs into one dir —
        a fresh instance restarting at journal.1.jsonl would os.replace
        over the first run's rotated history."""
        run_dir = str(tmp_path / "run")
        for _ in range(2):
            j = journal.RunJournal(run_dir, flush_every=1, max_bytes=600,
                                   compute_flops=False).start()
            for i in range(20):
                j.record_step(loss=float(i), step_ms=1.0)
            j.close()
        run = _load_run_report().load_run(run_dir)
        assert not run["parse_errors"]
        assert len(run["steps"]) == 40  # nothing clobbered
        assert run["header"] is not None  # run 1's header survives too


# -- zero-overhead contract --------------------------------------------------


class TestInactiveHooksDoNothing:
    def test_step_paths_never_touch_a_journal_when_inactive(
            self, tmp_path, monkeypatch):
        """With ACTIVE None, the hooks must be a single None check: every
        RunJournal entry point is poisoned to raise — and so are the
        PR-5 SPMD observability entry points (sharding summaries, device
        gauges) — and the executor, guarded step, StepTimer, dataloader,
        and checkpoint paths must still run clean."""
        assert journal.ACTIVE is None

        def boom(*a, **k):
            raise AssertionError("journal work performed while inactive")

        for name in ("record_step", "record_executor_run",
                     "record_request", "record_memory", "event",
                     "note_step_ms", "sync_step", "postmortem"):
            monkeypatch.setattr(journal.RunJournal, name, boom)
        # the per-compile sharding event and device telemetry must also
        # stay behind the ACTIVE/tracing gates
        from paddle_tpu.obs import spmd

        monkeypatch.setattr(spmd, "sharding_summary", boom)
        monkeypatch.setattr(spmd, "update_device_gauges", boom)
        # the fleet aggregator and SLO exporter are PULL-only readers:
        # nothing on a step/serve path may ever invoke them unprompted
        from paddle_tpu.obs import export as obs_export
        from paddle_tpu.obs import fleet as obs_fleet

        monkeypatch.setattr(obs_fleet, "load_journal", boom)
        monkeypatch.setattr(obs_fleet, "load_fleet", boom)
        monkeypatch.setattr(obs_fleet, "aggregate", boom)
        monkeypatch.setattr(obs_fleet, "merge_chrome_traces", boom)
        monkeypatch.setattr(obs_fleet, "router_summary", boom)
        monkeypatch.setattr(obs_export, "prometheus_text", boom)
        monkeypatch.setattr(obs_export, "write_textfile", boom)
        monkeypatch.setattr(obs_export, "router_lines", boom)
        monkeypatch.setattr(obs_export, "scrape", boom)
        monkeypatch.setattr(obs_export, "merge_expositions", boom)
        monkeypatch.setattr(obs_export.MetricsExporter, "render", boom)
        # the reqtrace reader (timeline assembly / attribution / lane
        # export) is pull-only too: the serve path writes req.* events
        # through the same ACTIVE gate and must never read them back
        from paddle_tpu.obs import reqtrace as obs_reqtrace

        for name in ("assemble", "assemble_run", "attribute",
                     "attribute_run", "tail_report",
                     "request_lane_events", "write_request_trace"):
            monkeypatch.setattr(obs_reqtrace, name, boom)
        # the SLO engine (PR 19) is strictly opt-in: with no evaluator
        # installed on the router and no statusz consumer, nothing on a
        # step/serve path may window a snapshot, evaluate a burn rate,
        # or render the status plane
        from paddle_tpu.obs import slo as obs_slo
        from paddle_tpu.obs import timeseries as obs_timeseries

        monkeypatch.setattr(obs_timeseries.SeriesStore, "observe", boom)
        monkeypatch.setattr(obs_timeseries.SeriesStore, "sample", boom)
        monkeypatch.setattr(obs_timeseries, "registry_snapshot", boom)
        monkeypatch.setattr(obs_timeseries, "exposition_snapshot", boom)
        monkeypatch.setattr(obs_slo.SLOEvaluator, "observe", boom)
        monkeypatch.setattr(obs_slo, "evaluate_run", boom)
        monkeypatch.setattr(obs_slo, "load_any", boom)
        monkeypatch.setattr(obs_export, "statusz_data", boom)
        monkeypatch.setattr(obs_export, "render_statusz_html", boom)
        monkeypatch.setattr(obs_export, "slo_engine_lines", boom)
        monkeypatch.setattr(obs_export.MetricsExporter,
                            "render_statusz", boom)
        monkeypatch.setattr(obs_fleet, "slo_summary", boom)
        # the tenant chargeback plane (PR 20) is pull-only too: the
        # meter/cache accumulate plain ints on the hot path, but
        # nothing on a step/serve path may ever roll up, audit, or
        # render a tenant view unprompted — every reader is poisoned
        # while the tenant-tagged lifecycles below run in full
        from paddle_tpu.obs import usage as obs_usage

        for name in ("engine_tenant_usage", "router_tenant_usage",
                     "fairness_audit", "fairness_record",
                     "rollup_requests", "merge_tenant_rollups",
                     "tenant_slo_slices"):
            monkeypatch.setattr(obs_usage, name, boom)
        monkeypatch.setattr(obs_export, "tenant_lines", boom)
        monkeypatch.setattr(obs_fleet, "tenant_summary", boom)
        monkeypatch.setattr(obs_fleet, "merged_tenant_summary", boom)

        pt.enable_static()
        try:
            _static_loop(fluid.Executor(), steps=2)
        finally:
            pt.disable_static()

        guard = _eager_guard()
        with inject.chaos("nan_feed", at=1, seed=7):
            for x, y in _batches(2):
                guard(x, y)

        from paddle_tpu.utils.profiler import StepTimer

        t = StepTimer(skip_first=0)
        with t.step():
            pass

        from paddle_tpu.framework.io import load_checkpoint, save_checkpoint

        d = str(tmp_path / "ckpt")
        m = nn.Linear(4, 2)
        save_checkpoint(d, 1, model=m)
        assert load_checkpoint(d, model=nn.Linear(4, 2)) == 1

        # serving hooks (PR 7): a full engine lifecycle — compile,
        # prefill, decode, preemption-free finish — and a Predictor
        # run must also perform zero journal work when inactive
        from paddle_tpu.serving import PagedKVCache, ServeEngine, TinyLM

        eng = ServeEngine(TinyLM(num_heads=2, head_dim=8),
                          PagedKVCache(16, 4, 2, 8))
        req = eng.submit([1, 2, 3], max_new_tokens=2, tenant="t0")
        eng.run(max_steps=20)
        assert req.state == "FINISHED" and len(req.generated) == 2
        eng.cancel(eng.submit([1], max_new_tokens=1, tenant="t1"))
        # metering kept charging (always-on ints) while every reader
        # stayed poisoned — the engine's truth is there to pull later
        assert eng.usage.busy_ns > 0 and "t0" in eng.usage.device_ns

        # serve-fleet hooks (router dispatch/requeue/scale, replica
        # pool spawn/death/retire): a full routed lifecycle — submit,
        # dispatch, a killed replica's requeue + relaunch, drain-down,
        # rejection — must perform zero journal/export work when
        # inactive (every router.* / fleet.* event is ACTIVE-guarded;
        # the exporters are pull-only)
        from paddle_tpu.serving import ManualClock
        from paddle_tpu.serving.fleet import (ReplicaPool, ReplicaSpec,
                                              Router)
        from paddle_tpu.resilience import ReplicaSupervisor

        fclock = ManualClock()
        fpool = ReplicaPool(
            ReplicaSpec(vocab_size=32, pages=32, page_size=4,
                        max_seq_len=16, token_budget=64),
            replicas=2, mode="local", clock=fclock,
            supervisor=ReplicaSupervisor(sleep=lambda s: None))
        frouter = Router(fpool, clock=fclock)
        fr = frouter.submit([1, 2, 3], max_new_tokens=2, tenant="t0")
        with pytest.raises(ValueError):
            frouter.submit([1] * 30, max_new_tokens=30,
                           tenant="t1")  # reject path (tenant-tagged)
        frouter.dispatch()
        fpool.replicas[fr.replica_id].kill()
        frouter.check_replicas()           # requeue + relaunch
        for _ in range(30):
            frouter.step()
            fclock.advance(0.01)
            if not frouter.inflight and not frouter.queue_depth:
                break
        assert fr.state == "FINISHED" and fr.requeues == 1
        drainee = fpool.active()[-1]
        drainee.drain()
        frouter.poll()                     # retire path
        frouter.close()

        # reqtrace write hooks (PR 18): a pressured engine run forcing
        # preemption, resume, and decode-step marks (the req.preempt /
        # req.admit(resumed) / req.decode_mark emit sites) must also
        # collapse to the single None check when inactive
        from paddle_tpu.serving import Scheduler

        pcache = PagedKVCache(8, 2, 2, 8, max_seq_len=8)
        peng = ServeEngine(TinyLM(num_heads=2, head_dim=8), pcache,
                           scheduler=Scheduler(pcache,
                                               token_budget=64))
        preqs = [peng.submit([1, 2], max_new_tokens=6,
                             tenant=f"t{i % 2}")
                 for i in range(4)]
        peng.run(max_steps=200)
        assert all(r.state == "FINISHED" for r in preqs)
        assert peng.scheduler.preemptions >= 1
        # the page-second integrals closed (alloc==free) and the
        # preempting run still metered both tenants — always-on
        # accumulation, pull-only reads
        assert not pcache.page_usage()["open"]
        assert set(peng.usage.device_ns) == {"t0", "t1"}

        import tempfile

        from paddle_tpu.framework.io import save_inference_model
        from paddle_tpu.inference import Predictor

        pt.enable_static()
        try:
            prog, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(prog, startup):
                xi = fluid.data(name="x", shape=[2, 4])
                oi = fluid.layers.fc(xi, size=2)
            exe = fluid.Executor()
            exe.run(startup)
            with tempfile.TemporaryDirectory() as td:
                prefix = os.path.join(td, "m")
                save_inference_model(prefix, ["x"], [oi], program=prog)
                Predictor(prefix).run(
                    {"x": np.zeros((2, 4), np.float32)})
        finally:
            pt.disable_static()


# -- concurrency + crash safety ----------------------------------------------


class TestJournalDurability:
    def test_two_threads_interleave_to_valid_jsonl(self, tmp_path):
        run_dir = str(tmp_path / "run")
        j = journal.RunJournal(run_dir, flush_every=3,
                               compute_flops=False).start()
        errs = []

        def work(tid):
            try:
                for i in range(100):
                    j.record_step(loss=float(i), step_ms=1.0,
                                  source=f"thread{tid}")
            except Exception as e:  # pragma: no cover
                errs.append(e)

        ts = [threading.Thread(target=work, args=(k,)) for k in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        j.close()
        assert not errs
        recs = _read_journal(run_dir)  # every line must json.loads
        steps = [r for r in recs if r["t"] == "step"]
        assert len(steps) == 200
        assert sorted(r["step"] for r in steps) == list(range(1, 201))

    def test_exception_mid_run_yields_parseable_postmortem(self, tmp_path):
        run_dir = str(tmp_path / "run")
        guard = _eager_guard(policy_kw={"max_retries": 1})
        with pytest.raises(inject.TransientChaosError):
            with journal.RunJournal(run_dir, flush_every=100) as j:
                with inject.chaos("transient_execute", times=5):
                    for x, y in _batches(4):  # retry budget dies mid-run
                        guard(x, y)
        assert j.closed
        pm = json.load(open(os.path.join(run_dir, journal.POSTMORTEM_FILE)))
        assert pm["exception"]["type"] == "TransientChaosError"
        assert pm["last_events"]  # the retry that preceded the death
        assert pm["summary"]["retries"] >= 1
        # the journal itself closed cleanly despite the big flush_every
        recs = _read_journal(run_dir)
        assert recs[-1]["t"] == "run_end"

    def test_rotation_keeps_every_record(self, tmp_path):
        run_dir = str(tmp_path / "run")
        j = journal.RunJournal(run_dir, flush_every=1, max_bytes=2048,
                               compute_flops=False).start()
        for i in range(100):
            j.record_step(loss=float(i), step_ms=1.0)
        j.close()
        parts = [f for f in os.listdir(run_dir)
                 if f.startswith("journal.") and f.endswith(".jsonl")]
        assert len(parts) > 1  # rotated at least once
        # the CLI loader reads rotated parts oldest-first: every record
        # survives rotation
        run = _load_run_report().load_run(run_dir)
        assert not run["parse_errors"]
        assert len(run["steps"]) == 100
        assert run["summary"]["productive_steps"] == 100


# -- detectors + accounting (unit level) -------------------------------------


class TestDetectors:
    def test_loss_spike_and_rearm(self):
        det = anomaly.LossSpike(window=8, factor=8.0, min_steps=4)
        for i in range(6):
            assert det.update({"loss": 1.0 + 0.01 * i}) is None
        fired = det.update({"loss": 100.0})
        assert fired and fired["loss"] == 100.0
        # a sustained excursion fires ONCE (docstring contract), and a
        # recovery re-arms the detector for the next excursion
        assert det.update({"loss": 120.0}) is None
        assert det.update({"loss": 1.0}) is None
        assert det.update({"loss": 100.0})

    def test_plateau_fires_once_per_plateau(self):
        det = anomaly.LossPlateau(window=5, rel_eps=1e-3)
        fires = [det.update({"loss": 1.0}) for _ in range(20)]
        assert sum(1 for f in fires if f) == 1

    def test_nonfinite_streak_resets(self):
        det = anomaly.NonfiniteStreak(threshold=2)
        assert det.update({"loss": 1.0}) is None
        assert det.update({"skipped": True}) is None
        assert det.update({"skipped": True})  # streak hits 2
        assert det.update({"skipped": True}) is None  # once per streak
        assert det.update({"loss": 1.0}) is None
        assert det.update({"loss": float("nan")}) is None
        assert det.update({"nonfinite": True})  # new streak

    def test_throughput_drop_and_rearm(self):
        det = anomaly.ThroughputDrop(window=8, factor=2.0, min_steps=4)
        for _ in range(6):
            assert det.update({"step_ms": 10.0}) is None
        assert det.update({"step_ms": 50.0})
        assert det.update({"step_ms": 50.0}) is None  # same slowdown
        assert det.update({"step_ms": 10.0}) is None  # recovery re-arms
        assert det.update({"step_ms": 55.0})

    def test_ttft_spike_and_rearm(self):
        det = anomaly.TtftSpike(window=8, factor=6.0, min_steps=4,
                                floor_ms=0.5)
        for i in range(6):
            assert det.update({"ttft_ms": 10.0 + 0.1 * i}) is None
        assert det.update({"ttft_ms": 200.0})
        # a sustained latency excursion fires ONCE; recovery re-arms
        assert det.update({"ttft_ms": 250.0}) is None
        assert det.update({"ttft_ms": 10.0}) is None
        assert det.update({"ttft_ms": 200.0})
        # records without a TTFT field (training steps) are ignored
        assert det.update({"loss": 1.0, "step_ms": 5.0}) is None

    def test_serving_detectors_env_spec(self):
        dets = anomaly.serving_detectors("")
        assert sorted(d.name for d in dets) == \
            sorted(anomaly.SERVING_DETECTORS)
        tuned = anomaly.serving_detectors(
            "ttft_spike:factor=3;loss_spike:factor=99")
        spike = [d for d in tuned
                 if isinstance(d, anomaly.TtftSpike)][0]
        # non-serving names in the shared env spec are ignored here
        assert spike.factor == 3.0
        assert not any(isinstance(d, anomaly.LossSpike) for d in tuned)
        assert anomaly.serving_detectors("off") == []

    def test_starvation_ratio_and_rearm(self):
        det = anomaly.DataloaderStarvation(ratio=0.5, min_wait_ms=1.0,
                                           min_steps=1)
        assert det.update({"step_ms": 10.0, "dl_wait_ms": 2.0}) is None
        assert det.update({"step_ms": 10.0, "dl_wait_ms": 8.0})
        assert det.update({"step_ms": 10.0, "dl_wait_ms": 9.0}) is None
        assert det.update({"step_ms": 10.0, "dl_wait_ms": 1.0}) is None
        assert det.update({"step_ms": 10.0, "dl_wait_ms": 8.0})

    def test_env_spec_overrides_and_off(self):
        dets = anomaly.default_detectors("nonfinite_streak:threshold=7")
        streak = [d for d in dets
                  if isinstance(d, anomaly.NonfiniteStreak)][0]
        assert streak.threshold == 7
        assert anomaly.default_detectors("off") == []
        with pytest.raises(KeyError):
            anomaly.default_detectors("nope:x=1")

    def test_engine_ticks_counter_and_callback_errors_are_swallowed(self):
        obs.metrics.reset()
        hits = []

        def cb(fired):
            hits.append(fired)
            raise RuntimeError("buggy reaction")

        eng = anomaly.AnomalyEngine(
            [anomaly.NonfiniteStreak(threshold=1)], callback=cb)
        out = eng.observe({"step": 5, "skipped": True})
        assert out and hits and hits[0]["name"] == "nonfinite_streak"
        assert obs.metrics.counter("anomaly.nonfinite_streak").value == 1


class TestMFU:
    def test_goodput_math(self):
        assert mfu.goodput(8, 1, 1) == pytest.approx(0.8)
        assert mfu.goodput(0, 0, 0) is None

    def test_accounting_summary(self):
        acc = mfu.MFUAccounting(peak=1e12)
        for _ in range(4):
            acc.record(step_ms=10.0, flops=5e9, examples=32)
        acc.record(step_ms=10.0, productive=False)
        acc.note_retry()
        s = acc.summary()
        assert s["goodput"] == pytest.approx(4 / 6)
        assert s["achieved_flops_per_s"] == pytest.approx(5e11)
        assert s["mfu"] == pytest.approx(0.5)
        assert s["examples_per_s"] == pytest.approx(128 / 0.05)

    def test_entry_attribution_via_cache_stats(self):
        pt.enable_static()
        try:
            exe = fluid.Executor()
            _static_loop(exe, steps=2)
        finally:
            pt.disable_static()
        stats = exe.cache_stats(per_entry=True)
        assert {"hits", "misses", "size", "entries"} <= set(stats)
        assert len(stats["entries"]) == stats["size"] == 1
        e = stats["entries"][0]
        assert e["optimize_level"] == 1
        # CPU XLA reports memory/cost analysis: bytes and flops land
        assert e["memory_bytes"] is None or e["memory_bytes"] > 0
        # pinned default shape unchanged (test_obs relies on it)
        assert set(exe.cache_stats()) == {"hits", "misses", "size"}


class TestStatsHardening:
    def test_cost_dict_list_valued_entries(self):
        from paddle_tpu.utils import stats

        ca = {"flops": [1.0, 2.0], "bytes accessed": 7,
              "utilization": "n/a", "weird": object()}
        out = stats._cost_dict(ca)
        assert out["flops"] == 3.0 and out["bytes accessed"] == 7.0
        assert "utilization" not in out and "weird" not in out

    def test_cost_dict_list_of_dicts_sums(self):
        from paddle_tpu.utils import stats

        out = stats._cost_dict([{"flops": 2.0}, {"flops": 3.0},
                                "junk"])
        assert out == {"flops": 5.0}

    def test_cost_dict_none_and_junk(self):
        from paddle_tpu.utils import stats

        assert stats._cost_dict(None) == {}
        assert stats._cost_dict(object()) == {}
        assert stats._cost_dict({"x": np.float32(1.5)}) == {"x": 1.5}
        assert stats._cost_dict({"x": np.zeros(())})["x"] == 0.0
        assert stats._cost_dict({"x": np.zeros(3)}) == {}
