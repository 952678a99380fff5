"""SPMD observability (obs.spmd): collective accounting, sharding
introspection, per-device telemetry.

Covers the PR's acceptance contract:
- HLO collective parsing against canned snippets (hand-computed byte
  volumes; async -start/-done pairs; explicit and iota replica groups;
  mesh-axis attribution) — no TPU needed;
- an 8-fake-device ``with_data_parallel`` run reports nonzero
  all-reduce bytes attributed to the 'data' axis, and the
  ShardingReport shows the feeds sharded with 1/8 per-device
  footprints;
- journal integration: a ``sharding`` event per compile, per-step comm
  deltas once the lazy entry analysis lands, and the run summary's
  comm accounting;
- per-device memory gauges + Chrome-trace device lanes degrade cleanly
  on backends without ``memory_stats`` (host CPU);
- TrainStep.collective_profile on a DistributedTrainStep sees the DP
  grad all-reduce.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn.functional as F
from paddle_tpu import optim
from paddle_tpu.obs import journal, mfu, spmd, trace


@pytest.fixture(autouse=True)
def _no_global_journal():
    yield
    if journal.ACTIVE is not None:
        journal.ACTIVE.close()
    journal.ACTIVE = None


# -- HLO parsing (no backend work) -------------------------------------------


class TestHloParsing:
    def test_all_reduce_bytes_hand_computed(self):
        hlo = ("%all-reduce = f32[128,64]{1,0} all-reduce("
               "f32[128,64]{1,0} %dot), channel_id=1, "
               "replica_groups=[1,8]<=[8], use_global_device_ids=true, "
               "to_apply=%add")
        prof = spmd.collective_profile(hlo)
        assert prof["counts"] == {"all-reduce": 1}
        assert prof["bytes"] == {"all-reduce": 128 * 64 * 4}
        assert prof["total_bytes"] == 32768
        # 8-ring: 2*(8-1)/8 of the payload on the wire
        assert prof["wire_bytes"] == int(32768 * 1.75)

    def test_tuple_result_and_bf16(self):
        hlo = ("%a2a = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-to-all("
               "bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %b), "
               "replica_groups={{0,1},{2,3},{4,5},{6,7}}")
        prof = spmd.collective_profile(hlo)
        assert prof["bytes"] == {"all-to-all": 2 * 8 * 8 * 2}
        # groups of 2: (2-1)/2 of the payload
        assert prof["wire_bytes"] == 8 * 8 * 2

    def test_async_pair_counts_once(self):
        hlo = ("%s = f32[16]{0} all-gather-start(f32[2]{0} %p), "
               "replica_groups=[1,8]<=[8], dimensions={0}\n"
               "%d = f32[16]{0} all-gather-done(f32[16]{0} %s)")
        prof = spmd.collective_profile(hlo)
        assert prof["counts"] == {"all-gather": 1}
        assert prof["bytes"] == {"all-gather": 64}

    def test_async_tuple_start_picks_result_not_sum(self):
        # real XLA async form: -start results are (operand, result[,
        # context]) bundles; summing would double-count the payload
        hlo = ("%s = (f32[2]{0}, f32[16]{0}) all-gather-start("
               "f32[2]{0} %p), replica_groups=[1,8]<=[8], "
               "dimensions={0}\n"
               "%cp = (f32[32]{0}, f32[32]{0}, u32[], u32[]) "
               "collective-permute-start(f32[32]{0} %q), "
               "source_target_pairs={{0,1},{1,0}}")
        prof = spmd.collective_profile(hlo)
        assert prof["bytes"] == {"all-gather": 64,
                                 "collective-permute": 128}

    def test_reduce_scatter_wire_counts_full_payload(self):
        # result is ONE shard (16*4=64B) of a 4-device group: the ring
        # still moves (4-1)/4 of the FULL 256B payload = 192B
        hlo = ("%rs = f32[16]{0} reduce-scatter(f32[64]{0} %x), "
               "replica_groups=[2,4]<=[8], dimensions={0}, "
               "to_apply=%add")
        prof = spmd.collective_profile(hlo)
        assert prof["bytes"] == {"reduce-scatter": 64}
        assert prof["wire_bytes"] == 3 * 64

    def test_non_collective_lines_ignored(self):
        hlo = ("%gte = f32[4,4]{1,0} get-tuple-element((f32[4,4]{1,0}, "
               "f32[4,4]{1,0}) %all-to-all.2), index=0\n"
               "ROOT %t = (f32[]) tuple(f32[] %c)")
        prof = spmd.collective_profile(hlo)
        assert prof["n_ops"] == 0
        assert prof["total_bytes"] == 0

    def test_iota_replica_groups_with_transpose(self):
        # [4,2]<=[2,4]T(1,0): iota(8).reshape(2,4).T.reshape(4,2)
        groups = spmd._parse_groups("[4,2]<=[2,4]T(1,0)")
        assert groups == [[0, 4], [1, 5], [2, 6], [3, 7]]

    def test_axis_attribution_single_and_multi(self):
        axes = {"data": 2, "model": 4}
        ids = np.arange(8).reshape(2, 4)
        # all-reduce over 'model': devices sharing the data coordinate
        hlo_m = ("%ar = f32[4]{0} all-reduce(f32[4]{0} %x), "
                 "replica_groups=[2,4]<=[8], to_apply=%add")
        prof = spmd.collective_profile(hlo_m, mesh=(axes, ids))
        assert prof["by_axis"] == {"model": 16}
        # all-reduce over 'data': groups {0,4},{1,5},{2,6},{3,7}
        hlo_d = ("%ar = f32[4]{0} all-reduce(f32[4]{0} %x), "
                 "replica_groups=[4,2]<=[2,4]T(1,0), to_apply=%add")
        prof = spmd.collective_profile(hlo_d, mesh=(axes, ids))
        assert prof["by_axis"] == {"data": 16}
        # one group spanning everything: the full axis product
        hlo_all = ("%ar = f32[4]{0} all-reduce(f32[4]{0} %x), "
                   "replica_groups=[1,8]<=[8], to_apply=%add")
        prof = spmd.collective_profile(hlo_all, mesh=(axes, ids))
        assert prof["by_axis"] == {"data+model": 16}

    def test_unattributable_groups_fall_back_to_question_mark(self):
        axes = {"data": 2, "model": 4}
        ids = np.arange(8).reshape(2, 4)
        hlo = ("%ar = f32[4]{0} all-reduce(f32[4]{0} %x), "
               "replica_groups={{0,3},{1,2},{4,7},{5,6}}, "
               "to_apply=%add")
        prof = spmd.collective_profile(hlo, mesh=(axes, ids))
        assert prof["by_axis"] == {"?": 16}

    def test_collective_permute_source_target_pairs(self):
        hlo = ("%cp = f32[32]{0} collective-permute(f32[32]{0} %p), "
               "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}")
        prof = spmd.collective_profile(hlo)
        assert prof["counts"] == {"collective-permute": 1}
        assert prof["bytes"] == {"collective-permute": 128}
        assert prof["wire_bytes"] == 128  # permute: payload moves once

    def test_merge_profiles(self):
        a = spmd.collective_profile(
            "%x = f32[4]{0} all-reduce(f32[4]{0} %p), "
            "replica_groups=[1,2]<=[2], to_apply=%add")
        merged = spmd.merge_profiles([a, a, None])
        assert merged["counts"] == {"all-reduce": 2}
        assert merged["total_bytes"] == 2 * a["total_bytes"]
        assert spmd.merge_profiles([None, {}]) is None


class TestRoofline:
    def test_comm_share_math(self):
        rl = spmd.comm_roofline(
            {"total_bytes": 1 << 20, "wire_bytes": 2 << 20},
            flops=1e9, peak=1e12, bw=200e9)
        comm_s = (2 << 20) / 200e9
        assert rl["comm_time_s"] == pytest.approx(comm_s)
        assert rl["compute_time_s"] == pytest.approx(1e-3)
        assert rl["comm_share"] == pytest.approx(
            comm_s / (comm_s + 1e-3))
        assert rl["bound"] == "compute"

    def test_missing_inputs_yield_none_not_fiction(self):
        rl = spmd.comm_roofline({"total_bytes": 10, "wire_bytes": 10},
                                flops=None, peak=None, bw=None)
        assert rl["comm_share"] is None
        assert rl["bound"] is None

    def test_ici_bandwidth_env_override(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_ICI_BW", "123e9")
        assert spmd.ici_bandwidth() == pytest.approx(123e9)


# -- live 8-fake-device data-parallel ----------------------------------------


def _dp_program(B):
    import paddle_tpu.fluid as fluid

    main, startup = pt.static.Program(), pt.static.Program()
    with pt.program_guard(main, startup):
        x = pt.static.data("x", [B, 8], "float32")
        y = pt.static.data("y", [B], "int64")
        h = fluid.layers.fc(x, size=16, act="relu")
        logits = fluid.layers.fc(h, size=4)
        loss = F.cross_entropy(logits, y)
        optim.Momentum(0.01, 0.9).minimize(loss)
    return main, startup, loss


class TestDataParallelAccounting:
    def test_entry_reports_nonzero_all_reduce_and_feed_sharding(self):
        from paddle_tpu.static_.compiler import CompiledProgram

        ndev = len(__import__("jax").devices())
        assert ndev == 8  # conftest contract
        B = 2 * ndev
        pt.enable_static()
        try:
            main, startup, loss = _dp_program(B)
        finally:
            pt.disable_static()
        exe = pt.static.Executor()
        exe.run(startup)
        cp = CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(B, 8).astype("float32"),
                "y": rng.randint(0, 4, (B,)).astype("int64")}
        exe.run(cp, feed=feed, fetch_list=[loss])

        compiled = next(iter(exe._cache.values()))
        prof = mfu.entry_analysis(compiled)["collectives"]
        assert prof is not None and prof["n_ops"] > 0
        assert prof["bytes"].get("all-reduce", 0) > 0
        assert prof["by_axis"].get("data", 0) > 0

        rep = spmd.sharding_report(compiled)
        assert rep["mesh"] == {"data": ndev}
        by_name = {r["name"]: r for r in rep["vars"]}
        assert by_name["x"]["spec"] == "data"
        assert by_name["x"]["per_device_bytes"] * ndev == \
            by_name["x"]["bytes"]
        persist = [r for r in rep["vars"]
                   if r["role"].startswith("persistable")]
        assert persist and all(r["spec"] == "replicated"
                               for r in persist)
        assert all(r["per_device_bytes"] == r["bytes"] for r in persist)

        stats = exe.cache_stats(per_entry=True)
        e = stats["entries"][0]
        assert e["collectives"]["bytes"]["all-reduce"] > 0
        assert e["mesh"] == {"data": ndev}

    def test_single_device_entry_reports_no_collectives(self):
        pt.enable_static()
        try:
            main, startup, loss = _dp_program(4)
        finally:
            pt.disable_static()
        exe = pt.static.Executor()
        exe.run(startup)
        feed = {"x": np.zeros((4, 8), "float32"),
                "y": np.zeros((4,), "int64")}
        exe.run(main, feed=feed, fetch_list=[loss])
        compiled = next(iter(exe._cache.values()))
        prof = mfu.entry_analysis(compiled)["collectives"]
        assert prof is not None and prof["n_ops"] == 0
        rep = spmd.sharding_report(compiled)
        assert rep["mesh"] is None
        assert all(r["spec"] == "replicated" for r in rep["vars"])

    def test_journal_sharding_event_and_step_comm(self, tmp_path):
        from paddle_tpu.static_.compiler import CompiledProgram

        ndev = len(__import__("jax").devices())
        B = 2 * ndev
        pt.enable_static()
        try:
            main, startup, loss = _dp_program(B)
        finally:
            pt.disable_static()
        exe = pt.static.Executor()
        exe.run(startup)
        cp = CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(B, 8).astype("float32"),
                "y": rng.randint(0, 4, (B,)).astype("int64")}
        run_dir = str(tmp_path / "run")
        with journal.RunJournal(run_dir, flush_every=1):
            exe.run(cp, feed=feed, fetch_list=[loss])
            # force the lazy analysis to land, then step again so the
            # journal's non-blocking lookup attributes comm
            compiled = next(iter(exe._cache.values()))
            mfu.entry_analysis(compiled)
            exe.run(cp, feed=feed, fetch_list=[loss])

        recs = []
        with open(os.path.join(run_dir, "journal.jsonl")) as f:
            for line in f:
                if line.strip():
                    recs.append(json.loads(line))
        shardings = [r for r in recs if r.get("t") == "event"
                     and r.get("kind") == "sharding"]
        assert len(shardings) == 1  # one per compiled entry
        assert shardings[0]["mesh"] == {"data": ndev}
        specs = {v["name"]: v["spec"] for v in shardings[0]["vars"]}
        assert specs.get("x") == "data"
        comm_steps = [r for r in recs if r.get("t") == "step"
                      and r.get("comm")]
        assert comm_steps, "no step carried comm after analysis landed"
        assert comm_steps[-1]["comm"]["all_reduce_bytes"] > 0
        end = [r for r in recs if r.get("t") == "run_end"]
        assert end and end[0]["summary"]["comm_bytes_per_step"] > 0

    def test_backend_event_carries_per_device_identity(self, tmp_path):
        run_dir = str(tmp_path / "run")
        with journal.RunJournal(run_dir, flush_every=1) as j:
            j.record_step(loss=1.0, step_ms=1.0)
        recs = []
        with open(os.path.join(run_dir, "journal.jsonl")) as f:
            for line in f:
                if line.strip():
                    recs.append(json.loads(line))
        be = [r for r in recs if r.get("t") == "event"
              and r.get("kind") == "backend"]
        assert len(be) == 1
        assert be[0]["platform"] == "cpu"
        assert be[0]["device_count"] == 8
        assert be[0]["device_kinds"] == {"cpu": 8}
        assert len(be[0]["devices"]) == 8
        assert {d["id"] for d in be[0]["devices"]} == set(range(8))


# -- TrainStep profile --------------------------------------------------------


class TestTrainStepProfile:
    def test_distributed_step_sees_dp_all_reduce(self):
        import paddle_tpu.nn as nn
        from paddle_tpu import distributed as dist
        from paddle_tpu.dist import env as denv

        mesh = denv.init_mesh({"data": 8})
        try:
            model = nn.Linear(8, 4)
            opt = optim.Momentum(learning_rate=0.1, momentum=0.9,
                                 parameters=model.parameters())
            step = dist.DistributedTrainStep(
                model, opt,
                lambda m, x, y: F.cross_entropy(m(x), y), mesh=mesh)
            x = np.random.RandomState(0).randn(16, 8).astype("float32")
            y = np.random.RandomState(1).randint(
                0, 4, (16,)).astype("int64")
            assert step.collective_profile() is None  # pre-first-step
            step(x, y)
            prof = step.collective_profile()
            assert prof is not None
            assert prof["bytes"].get("all-reduce", 0) > 0
            assert prof["by_axis"].get("data", 0) > 0
            assert step.collective_profile() is prof  # cached
        finally:
            denv.set_mesh(None)

    def test_placement_is_a_child_of_the_distributed_steps_call(self):
        """``DistributedTrainStep`` puts the batch on the mesh under the
        span ``trainstep.place``, the first child of the one
        ``trainstep.call`` that ``TrainStep.__call__`` opens."""
        import paddle_tpu.nn as nn
        from paddle_tpu import distributed as dist
        from paddle_tpu.dist import env as denv

        mesh = denv.init_mesh({"data": 8})
        try:
            model = nn.Linear(8, 4)
            opt = optim.SGD(learning_rate=0.1,
                            parameters=model.parameters())
            step = dist.DistributedTrainStep(
                model, opt,
                lambda m, x, y: F.cross_entropy(m(x), y), mesh=mesh)
            x, y = np.zeros((16, 8), "float32"), np.zeros((16,), "int64")
            step(x, y)
            trace.clear_trace()
            trace.enable_tracing()
            try:
                step(x, y)
            finally:
                trace.disable_tracing()
            spans = [e for e in trace.trace_events()
                     if e["name"].startswith("trainstep.")]
            assert [e["name"] for e in spans] == [
                "trainstep.call", "trainstep.place", "trainstep.feed",
                "trainstep.execute", "trainstep.rebind"]
            assert {e["parent"] for e in spans[1:]} == {spans[0]["id"]}
        finally:
            trace.clear_trace()
            denv.set_mesh(None)

    def test_plain_trainstep_profiles_without_collectives(self):
        import paddle_tpu.nn as nn

        model = nn.Linear(4, 2)
        opt = optim.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())
        step = pt.TrainStep(
            model, opt, lambda m, x, y: F.cross_entropy(m(x), y))
        x = np.zeros((4, 4), "float32")
        y = np.zeros((4,), "int64")
        step(x, y)
        prof = step.collective_profile()
        assert prof is not None and prof["n_ops"] == 0


# -- per-device telemetry -----------------------------------------------------


class TestDeviceTelemetry:
    def test_memory_stats_none_safe_on_cpu(self):
        stats = spmd.device_memory_stats()
        assert len(stats) == 8
        assert {d["id"] for d in stats} == set(range(8))
        # host CPU exposes no memory_stats: fields degrade to None,
        # never raise
        assert all(d["bytes_in_use"] is None for d in stats)
        got, high = spmd.update_device_gauges()
        assert len(got) == 8 and high is None

    def test_device_counter_lanes_in_chrome_trace(self, tmp_path):
        was = trace.tracing_enabled()
        trace.enable_tracing()
        try:
            trace.clear_trace()
            trace.device_counter(0, "bytes_in_use", 123.0,
                                 label="device 0 (fake)")
            trace.device_counter(3, "bytes_in_use", 456.0)
            path = str(tmp_path / "trace.json")
            trace.export_chrome_trace(path)
        finally:
            if not was:
                trace.disable_tracing()
            trace.clear_trace()
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        counters = [e for e in events if e["ph"] == "C"]
        assert {e["pid"] for e in counters} == \
            {trace.DEVICE_PID_BASE, trace.DEVICE_PID_BASE + 3}
        names = {e["args"]["name"] for e in events
                 if e["ph"] == "M" and e["pid"] >= trace.DEVICE_PID_BASE}
        assert "device 0 (fake)" in names and "device 3" in names

    def test_device_counter_noop_when_tracing_off(self):
        assert not trace.tracing_enabled()
        trace.clear_trace()     # phase records are there whatever is off
        trace.device_counter(0, "bytes_in_use", 1.0)
        assert not trace.trace_events()


# -- run_report comm gate -----------------------------------------------------


def test_diff_flags_comm_appearing_from_zero_baseline():
    """A TP-only base run (comm recorded, zero all-reduce) regressing to
    ANY all-reduce must trip the comm gate — 0 is a valid baseline."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "run_report_spmd_test", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "run_report.py"))
    rr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rr)

    def run_with(ar_bytes):
        return {"steps": [{"step": i + 1, "loss": 1.0, "step_ms": 5.0,
                           "comm": {"all_reduce_bytes": ar_bytes,
                                    "total_bytes": ar_bytes + 100}}
                          for i in range(5)],
                "anomalies": [], "summary": None, "events": [],
                "header": None, "parse_errors": []}

    rep = rr.diff_runs(run_with(0), run_with(4096))
    assert rep["comm_regression"] and rep["regression"]
    assert not rr.diff_runs(run_with(0), run_with(0))["comm_regression"]
    assert not rr.diff_runs(run_with(100), run_with(101))["comm_regression"]


# -- persistable footprint (framework/io) ------------------------------------


def test_persistable_footprint_matches_scope_bytes():
    from paddle_tpu.framework.io import persistable_footprint

    pt.enable_static()
    try:
        main, startup, _ = _dp_program(8)
    finally:
        pt.disable_static()
    exe = pt.static.Executor()
    exe.run(startup)
    fp = persistable_footprint(main)
    assert fp["total_bytes"] > 0
    by_name = {r["name"]: r for r in fp["vars"]}
    # fc weight: 8x16 f32 = 512 bytes (the first fc's weight)
    w = [r for r in fp["vars"] if r["shape"] == (8, 16)]
    assert w and w[0]["bytes"] == 8 * 16 * 4
    assert all(r["bytes"] is not None for r in by_name.values())
