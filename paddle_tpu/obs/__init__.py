"""paddle_tpu.obs — unified telemetry: metrics registry + span tracer.

The reference ships a first-class profiler (``fluid/profiler.py`` over
the C++ platform profiler); this package is its TPU-native counterpart
plus the production metrics layer the reference keeps in VLOG counters:

- ``metrics``  — process-wide registry of named Counters / Gauges /
  fixed-bucket Histograms; ``snapshot()`` / ``reset()``; thread-safe,
  allocation-free on the tick path.
- ``trace``    — ``span(name, **attrs)`` wall-time spans in a bounded
  ring buffer, exported as Chrome ``chrome://tracing`` JSON, and the same
  span as a ``jax.profiler.TraceAnnotation`` in a device profile being
  taken; opt-in via env ``PADDLE_TPU_TRACE=1`` or ``enable_tracing()``.
  ``phase(name, **attrs)`` / ``record(name, start, end, **attrs)`` write
  the same record ALWAYS, for work done once a process or once a compile
  (the start-up timeline); every record has an ``id`` and a ``parent``.
- ``report``   — human-readable table / JSON dump of the registry
  (``tools/obs_report.py`` is the CLI front door).
- ``journal``  — per-run JSONL flight recorder (``RunJournal``): run
  header, per-step records, discrete events, anomaly firings, and an
  MFU/goodput summary; env ``PADDLE_TPU_RUN_DIR`` auto-starts one
  (``tools/run_report.py`` renders and diffs runs).
- ``anomaly``  — stateful detectors (loss spike/plateau, nonfinite
  streak, throughput drop, dataloader starvation) evaluated on each
  journal step; thresholds via env ``PADDLE_TPU_ANOMALY``.
- ``mfu``      — MFU/goodput accounting from XLA ``cost_analysis``
  FLOPs per compiled executable + the per-chip peak table
  (``mfu.PEAK_FLOPS_BY_KIND``, keyed by ``device_kind``).
- ``spmd``     — SPMD observability: CollectiveProfile (per-kind
  collective counts/bytes parsed from the executable's HLO, attributed
  to mesh axes), comm roofline vs ``PADDLE_TPU_ICI_BW``/chip table,
  ShardingReport per Executor cache entry, per-device memory gauges +
  Chrome-trace device lanes (``tools/shard_report.py`` is the CLI).
- ``reqtrace`` — request-scoped distributed tracing: assemble the
  ``req.*`` journal events (router + replicas) into per-request
  timelines, exact tail-latency phase attribution (rate-limit wait /
  router queue / scheduler queue / prefill / preemption loss summing
  to e2e), and Perfetto request lanes with flow arrows across
  requeues (``tools/request_report.py`` is the CLI).
- ``fleet``    — cross-rank aggregation over per-rank journals
  (``<run_dir>/rank_NN/``, written when gang launchers hand workers
  ``PADDLE_TPU_RANK``): step alignment, cross-rank skew,
  straggler/hang attribution, merged request percentiles, merged
  Chrome traces with pid=rank lanes (``tools/fleet_report.py`` is the
  CLI).
- ``lockdep``  — opt-in runtime lock-order validation (env
  ``PADDLE_TPU_LOCKDEP``): instrumented ``lock(name)``/``rlock(name)``
  factories feed a process-wide acquisition-order graph; the first
  cycle raises/journals a PTC004 with both witness stacks, and
  ``lockdep.held_ms.<name>`` histograms land in the registry. The
  runtime half of ``analysis.concurrency``'s static lint.
- ``export``   — live SLO signal plane: the registry + per-replica
  serving SLOs + per-rank heartbeat ages as Prometheus text over a
  localhost HTTP endpoint (``MetricsExporter``, which also serves the
  ``/statusz`` fleet status page) or an atomic textfile.
- ``timeseries`` — fixed-interval rolling windows over registry
  snapshots or scraped expositions (``SeriesStore``): windowed counter
  rates, gauge trends, and histogram percentiles / threshold
  fractions over the last 1m/5m/30m/3h, exact under a ManualClock.
- ``slo``      — declarative serving SLOs on top of ``timeseries``:
  per-objective error budgets, Google-SRE multi-window multi-burn-rate
  alerting (fast page 14.4x over 5m+30m, slow warn 6x over 30m+3h),
  latched ``slo.fire``/``slo.clear`` journal events with worst-replica
  attribution, and post-hoc ``evaluate_run`` for finished run dirs
  (``tools/slo_report.py`` is the CLI; ``serve_bench --slo`` the
  exit gate).

Instrumented sites (all zero-overhead when idle — one flag/None check,
no host sync, mirroring the ``resilience.inject`` ``if ACTIVE`` hooks;
a *phase* is written whether or not tracing is on, once a process or once
a compile, never in a steady step; PERF.md section 7 lists who reads each):

======================  ====================================================
subsystem               instruments
======================  ====================================================
static_/executor.py     ``executor.jit_cache.hits|misses``,
                        ``executor.compile_ms``, ``executor.run_ms``,
                        ``executor.fetch_ms``; spans ``executor.compile``,
                        ``executor.run``
analysis (passes)       ``analysis.pass.<name>.ms`` per optimization pass
core/dispatch.py        ``dispatch.ops_total``, ``dispatch.op.<type>``
                        behind ``enable_op_sampling()`` /
                        env ``PADDLE_TPU_OBS_SAMPLE`` (off by default:
                        the eager hot path pays one None check)
io_/dataloader.py       ``dataloader.queue_depth`` gauge,
                        ``dataloader.producer_wait_ms``,
                        ``dataloader.consumer_wait_ms``,
                        ``dataloader.worker_restarts``; span
                        ``dataloader.next``
resilience              ``resilience.retries|steps|nonfinite|skipped|``
                        ``rollbacks|degraded``
framework/io.py         ``checkpoint.save_ms|load_ms|verify_ms``,
                        ``checkpoint.saves|loads|fallbacks``; spans
                        ``checkpoint.save|load``
framework/jit.py        spans ``trainstep.call`` (``step_num``; a
                        ``StepTraceAnnotation``) > ``trainstep.feed``,
                        ``trainstep.execute``, ``trainstep.rebind``;
                        phase ``trainstep.first_execute`` (``sig``): the
                        first call of a signature, in ``execute``'s place
dist/parallel.py        span ``trainstep.place`` (the batch onto the mesh),
                        first child of ``DistributedTrainStep``'s
                        ``trainstep.call``
paddle_tpu/__init__.py  phase ``startup.import``: the package's import,
                        first line to last
nn/layer.py             phase ``startup.param_init`` (``name``, ``bytes``):
                        one a parameter, round its initializer
runtime/aot.py          phases ``aot.lower``, ``aot.key``, then
                        ``aot.load`` (hit) or ``aot.compile`` +
                        ``aot.store`` (miss), each with ``site``,
                        ``label``, ``digest``, ``source``; counters
                        ``aot.cache.hits|misses`` (every ``AOTCache``'s
                        answers in the process)
core/device.py          phases ``jax.trace``, ``jax.lower``,
                        ``jax.backend_compile``, ``jax.cache_load``
                        (``event``, ``fun_name``), written from
                        jax.monitoring's duration events: every program
                        the process traces, lowers, compiles or loads;
                        ``jax.cache.hits|misses``
models/nlp (LatentMoE,  gauges ``moe.slots_held``,
HybridMoE)              ``moe.load_max_over_mean``,
                        ``moe.window_passes_max``,
                        ``moe.window_live_share``, ``loss.lm``,
                        ``loss.mtp``, ``linear_attn.*``: computed when the
                        registry is read (``Registry.collect()``, called
                        by ``snapshot()``, ``export.registry_lines`` and
                        ``timeseries.registry_snapshot``; ``TrainStep``
                        registers ``publish_gauges`` with
                        ``add_collector``), never in a step
utils/profiler.py       ``step_timer.step_ms`` (StepTimer rebase)
======================  ====================================================
"""
from __future__ import annotations

import os as _os

from . import lockdep  # noqa: F401  (first: others build locks through it)
from . import metrics, trace, report, anomaly, mfu, journal, spmd  # noqa: F401,E501
from . import fleet, export, reqtrace  # noqa: F401
from . import timeseries, slo  # noqa: F401  (after metrics/export)
from .metrics import (counter, gauge, histogram, snapshot, reset,  # noqa: F401
                      Counter, Gauge, Histogram, Registry, REGISTRY)
from .trace import (span, phase, enable_tracing,  # noqa: F401
                    disable_tracing, tracing_enabled, clear_trace,
                    trace_events, export_chrome_trace)
from .journal import RunJournal, start_run, end_run  # noqa: F401
from .export import MetricsExporter  # noqa: F401

__all__ = [
    "metrics", "trace", "report", "anomaly", "mfu", "journal", "spmd",
    "fleet", "export", "reqtrace", "lockdep", "timeseries", "slo",
    "counter", "gauge", "histogram", "snapshot", "reset",
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "span", "phase", "enable_tracing", "disable_tracing", "tracing_enabled",
    "clear_trace", "trace_events", "export_chrome_trace",
    "enable_op_sampling", "disable_op_sampling", "op_sampling_enabled",
    "RunJournal", "start_run", "end_run", "MetricsExporter",
]

# -- eager op sampling -------------------------------------------------------
# The dispatcher cannot afford a registry lookup per op, so sampling is
# push-style: enabling installs a closure over pre-interned counters into
# core.dispatch (the exact pattern resilience.inject uses for nan_op).

_op_sampling = False


def enable_op_sampling(every=1):
    """Count eager op dispatches into ``dispatch.ops_total`` and
    ``dispatch.op.<type>``, sampling one in ``every`` calls. Off by
    default; also enabled at import by env ``PADDLE_TPU_OBS_SAMPLE``
    (its integer value is the sampling stride, ``1`` = every op)."""
    global _op_sampling
    from ..core import dispatch

    every = max(1, int(every))
    total = metrics.counter("dispatch.ops_total")
    per_op: dict = {}  # op type -> Counter, interned outside the lock
    if every == 1:
        def hook(name):
            total.inc()
            c = per_op.get(name)
            if c is None:
                c = per_op[name] = metrics.counter("dispatch.op." + name)
            c.inc()
    else:
        state = {"n": 0}

        def hook(name):
            # stride sampling: the +every correction keeps ops_total an
            # unbiased estimate of the true dispatch count
            state["n"] += 1
            if state["n"] % every:
                return
            total.inc(every)
            c = per_op.get(name)
            if c is None:
                c = per_op[name] = metrics.counter("dispatch.op." + name)
            c.inc(every)
    dispatch.set_op_metrics_hook(hook)
    _op_sampling = True


def disable_op_sampling():
    global _op_sampling
    from ..core import dispatch

    dispatch.set_op_metrics_hook(None)
    _op_sampling = False


def op_sampling_enabled():
    return _op_sampling


_sample_env = _os.environ.get("PADDLE_TPU_OBS_SAMPLE", "")
if _sample_env.lower() not in ("", "0", "false"):
    try:
        enable_op_sampling(int(_sample_env))
    except ValueError:
        enable_op_sampling(1)
