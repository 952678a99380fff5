"""``benchmark/startup_records.py`` and the eight readers that move
``setup_s``, on made-up records: the cut at the window's first stamp, the
ancestor rule that keeps the six named durations disjoint, the lazy-jit case
where the compile sits under ``trainstep.first_execute``, and a float from
every reader in a cell whose program wrote no record at all."""
import types

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmark import harness, startup_records

READERS = ("import_s", "param_init_s", "step_lower_s", "step_load_s",
           "step_first_execute_s", "small_programs_s", "cache_misses",
           "setup_unnamed_s")
MAIN, WORKER = 1, 2


def rec(rid, name, start, end, /, parent=None, tid=MAIN, **args):
    return {"id": rid, "name": name, "start": float(start), "end": float(end),
            "parent": parent, "tid": tid, "args": args}


def warm_run():
    """A start-up as a warm run's records tell it, seconds from the import's
    first line; the window starts at 40."""
    step = {"site": "trainstep", "label": "GPT"}
    return [
        rec(1, "startup.import", 0, 5),
        rec(2, "startup.param_init", 1, 2, parent=1, name="table"),  # in import
        rec(3, "jax.backend_compile", 1.2, 1.7, parent=2),
        rec(4, "jax.trace", 6, 7),                    # the benchmark's weights
        rec(5, "jax.trace", 6.2, 6.5, parent=4),      # an inner jit of it
        rec(6, "startup.param_init", 8, 10, name="wte"),
        rec(7, "jax.backend_compile", 8.5, 9.5, parent=6),
        rec(8, "jax.cache_load", 8.6, 9.4, parent=7),
        rec(9, "startup.param_init", 10, 11, name="wpe"),
        rec(10, "jax.backend_compile", 12, 14),       # an optimizer slot
        rec(11, "jax.cache_load", 12.5, 13.5, parent=10),
        rec(12, "aot.lower", 20, 23, **step),
        rec(13, "jax.trace", 20, 22, parent=12),
        rec(14, "jax.lower", 22, 23, parent=12),
        rec(15, "aot.key", 23, 24, **step),
        rec(16, "aot.load", 24, 26, source="aot_disk", **step),
        rec(17, "trainstep.first_execute", 26, 29, sig="s"),
        rec(18, "aot.lower", 30, 30.5, site="executor"),  # another site's
        rec(19, "jax.trace", 30, 30.4, parent=18),
        rec(20, "jax.backend_compile", 31, 33, tid=WORKER),  # beside, not in
        rec(21, "jax.backend_compile", 39, 41),       # ends inside the window
        rec(22, "jax.backend_compile", 50, 60),       # the reference's
    ]


WARM = {"import_s": 5.0, "param_init_s": 3.0, "step_lower_s": 4.0,
        "step_load_s": 2.0, "step_first_execute_s": 3.0,
        "small_programs_s": 1.0 + 2.0 + 0.4}


def test_the_cut_is_the_windows_first_stamp():
    early = startup_records.before(warm_run(), 40.0)
    assert [r["id"] for r in early] == list(range(1, 21))
    assert [r["id"] for r in startup_records.before(warm_run(), 41.0)] == \
        list(range(1, 22))


def test_a_record_comes_with_its_ancestors_nearest_first():
    early = {r["id"]: r for r in startup_records.before(warm_run(), 40.0)}
    assert [a["id"] for a in early[8]["ancestors"]] == [7, 6]
    assert [a["id"] for a in early[3]["ancestors"]] == [2, 1]
    assert early[12]["ancestors"] == []
    # a parent the store no longer holds, and a loop, end the walk
    lost = startup_records.before(
        [rec(2, "jax.trace", 0, 1, parent=1),
         rec(3, "jax.trace", 0, 1, parent=4),
         rec(4, "jax.trace", 0, 1, parent=3)], 2.0)
    assert [[a["id"] for a in r["ancestors"]] for r in lost] == \
        [[], [4], [3]]


@pytest.mark.parametrize("metric", sorted(WARM))
def test_the_six_named_durations_of_a_warm_run(metric):
    got = startup_records.named_seconds(warm_run(), 40.0)
    assert set(got) == set(WARM)
    assert got[metric] == pytest.approx(WARM[metric])


def test_the_six_count_no_second_twice_and_the_rest_is_unnamed():
    """Nested records (a compile under a parameter, a cache load under the
    compile, an inner trace under the outer, a parameter inside the import)
    count once, under the outermost that a metric reads: the six add up to
    at most the time from the import's start to the window's."""
    records = warm_run()
    named = startup_records.named_seconds(records, 40.0)
    assert sum(named.values()) == pytest.approx(20.4)
    assert startup_records.unnamed_seconds(records, 40.0) == \
        pytest.approx(40.0 - 20.4)
    # flat, every record at the top, 7.6 of those seconds would count twice
    flat = [dict(r, parent=None) for r in records]
    assert sum(startup_records.named_seconds(flat, 40.0).values()) == \
        pytest.approx(28.0)


def test_another_threads_records_run_beside_the_timeline_not_in_it():
    records = warm_run()
    alone = [r for r in records if r["tid"] == MAIN]
    assert startup_records.named_seconds(records, 40.0) == \
        startup_records.named_seconds(alone, 40.0)
    # with no import record there is no thread to choose: all count
    no_import = [r for r in records if r["id"] > 3]
    assert startup_records.named_seconds(no_import, 40.0)[
        "small_programs_s"] == pytest.approx(3.4 + 2.0)
    assert startup_records.unnamed_seconds(no_import, 40.0) == 0.0


def test_a_lazy_jit_compiles_under_its_first_execute():
    """No cache active: no ``aot.*`` record, and the step's trace, lower and
    compile are children of ``trainstep.first_execute``: they count there,
    not as small programs."""
    records = [
        rec(1, "startup.import", 0, 2),
        rec(2, "trainstep.first_execute", 5, 30, sig="s"),
        rec(3, "jax.trace", 5, 8, parent=2),
        rec(4, "jax.lower", 8, 9, parent=2),
        rec(5, "jax.backend_compile", 9, 29, parent=2),
        rec(6, "jax.backend_compile", 31, 32),
    ]
    got = startup_records.named_seconds(records, 35.0)
    assert got["step_first_execute_s"] == pytest.approx(25.0)
    assert got["small_programs_s"] == pytest.approx(1.0)
    assert got["step_lower_s"] == got["step_load_s"] == 0.0
    assert startup_records.unnamed_seconds(records, 35.0) == \
        pytest.approx(35.0 - 28.0)


def test_a_miss_is_the_compile_and_the_store():
    step = {"site": "trainstep"}
    records = [rec(1, "aot.lower", 0, 1, **step), rec(2, "aot.key", 1, 2, **step),
               rec(3, "aot.compile", 2, 12, source="xla", **step),
               rec(4, "jax.backend_compile", 2, 12, parent=3),
               rec(5, "aot.store", 12, 13, source="xla", **step),
               rec(6, "aot.compile", 14, 15, site="trainstep_fused")]
    got = startup_records.named_seconds(records, 20.0)
    assert got["step_lower_s"] == pytest.approx(2.0)
    assert got["step_load_s"] == pytest.approx(11.0)
    assert got["small_programs_s"] == 0.0


def _window(first_stamp=40.0):
    return types.SimpleNamespace(stamps=[first_stamp, first_stamp + 1.0])


def _reader(name):
    return harness.load_module("layer_metrics", name)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_made_up_records(name, monkeypatch):
    from paddle_tpu import obs

    monkeypatch.setattr(startup_records, "program_records", warm_run)
    jax_misses = obs.counter("jax.cache.misses")
    aot_misses = obs.counter("aot.cache.misses")
    before = jax_misses.value + aot_misses.value
    jax_misses.inc(2)
    aot_misses.inc()
    want = dict(WARM, cache_misses=before + 3.0, setup_unnamed_s=40.0 - 20.4)
    value = _reader(name).read(_window())
    assert isinstance(value, float)
    assert value == pytest.approx(want[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_a_float_where_the_program_wrote_no_record(
        name, monkeypatch):
    """The parent of the PR that brought the records has none, and no
    counter: every reader still reports, 0.0."""
    from paddle_tpu.obs import metrics, trace

    monkeypatch.setattr(trace, "trace_events", lambda: [])
    monkeypatch.setattr(metrics, "REGISTRY", metrics.Registry())
    value = _reader(name).read(_window())
    assert isinstance(value, float) and value == 0.0
    # and a program whose tracer has no public clock at all
    monkeypatch.delattr(trace, "to_perf_counter")
    assert _reader(name).read(_window()) == 0.0


def test_the_programs_records_come_on_the_windows_clock():
    """``program_records`` against the real tracer: a phase record made now
    lies between two readings of ``time.perf_counter``, and comes with the
    id and parent the ancestor rule needs."""
    import time

    from paddle_tpu.obs import trace

    trace.clear_trace()     # a long process's store of phases may be full
    t0 = time.perf_counter()
    with trace.phase("aot.lower", site="trainstep"):
        time.sleep(0.002)
        trace.record("jax.trace", time.perf_counter() - 1e-3,
                     time.perf_counter(), event="made up")
    t1 = time.perf_counter()
    mine = [r for r in startup_records.program_records()
            if t0 <= r["start"] and r["end"] <= t1]
    assert [r["name"] for r in mine] == ["aot.lower", "jax.trace"]
    assert mine[1]["parent"] == mine[0]["id"]
    got = startup_records.named_seconds(mine, t1)
    assert got["step_lower_s"] == pytest.approx(mine[0]["end"] -
                                                mine[0]["start"])
    assert got["small_programs_s"] == 0.0


def test_the_eight_are_the_manifests_last_entries_and_move_setup_s():
    entries = harness.manifest()["per_layer"][-8:]
    assert [m["name"] for m in entries] == list(READERS)
    for m in entries:
        assert m["moves"] == "setup_s" and "workloads" not in m
        assert not hasattr(_reader(m["name"]), "reports")
