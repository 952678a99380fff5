"""Profiler (ref: python/paddle/fluid/profiler.py — profiler context,
start/stop, per-op timing report).

TPU-native: three layers.
- ``profiler()`` / start_profiler / stop_profiler wrap ``jax.profiler``
  traces (view in TensorBoard / xprof — this is where XLA fusion and MXU
  utilization actually show up; the reference's per-CUDA-kernel timers
  have no TPU analog because the whole step is one executable) AND turn
  on ``paddle_tpu.obs`` span tracing for the window. Every ``obs.span``
  (compiles, runs, dataloader waits, ``trainstep.call`` / ``feed`` /
  ``execute`` / ``rebind``) is then also a ``TraceAnnotation`` in the
  profile's ``/host:CPU`` plane, on the clock of the device ops, which
  the compiled step names by phase, program op and kernel — one trace
  holds both (and ``obs.export_chrome_trace`` still exports the ring).
- ``span(...)`` re-exported from ``obs.trace`` for ad-hoc host ranges
  (the role nvprof ranges play in the reference).
- ``StepTimer`` / ``add_profiler_step`` give the host-side per-step
  wall-clock stats the reference prints (min/max/mean, imgs-per-sec),
  rebased on the ``obs.metrics`` registry: every step also lands in the
  process-wide ``step_timer.step_ms`` histogram.
"""
from __future__ import annotations

import contextlib
import os
import time

import numpy as np

from ..obs import journal as _journal
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from ..obs.trace import span  # noqa: F401  (re-export)

__all__ = ["profiler", "start_profiler", "stop_profiler",
           "add_profiler_step", "StepTimer", "cuda_profiler", "span"]

_trace_dir = None
_window = None  # (span-cm, tracing-was-enabled-before)


def start_profiler(state=None, tracer_option=None, log_dir="/tmp/pt_profile"):
    """ref: profiler.start_profiler. Starts a jax.profiler trace and
    enables obs span tracing for the window."""
    global _trace_dir, _window
    import jax

    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    _trace_dir = log_dir
    was_on = _trace.tracing_enabled()
    _trace.enable_tracing()
    sp = _trace.span("profiler.window", log_dir=log_dir)
    sp.__enter__()
    _window = (sp, was_on)


def stop_profiler(sorted_key=None, profile_path=None):
    """ref: profiler.stop_profiler. Ends the trace; returns the dir.
    Span tracing reverts to its pre-window state (env ``PADDLE_TPU_TRACE``
    keeps it on)."""
    global _trace_dir, _window
    import jax

    jax.profiler.stop_trace()
    if _window is not None:
        sp, was_on = _window
        sp.__exit__(None, None, None)
        if not was_on:
            _trace.disable_tracing()
        _window = None
    d, _trace_dir = _trace_dir, None
    return d


@contextlib.contextmanager
def profiler(state=None, sorted_key=None, profile_path=None,
             log_dir="/tmp/pt_profile"):
    """ref: profiler.profiler context manager."""
    start_profiler(state, log_dir=log_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*a, **k):
    """API-parity shim: there is no CUDA on TPU; this is a no-op trace."""
    yield


class StepTimer:
    """Host-side per-step timing (the reference's profiler report numbers).

    Exact wall-times stay local (so ``summary()`` percentiles are exact,
    not bucket-interpolated); each step is additionally observed into the
    shared ``obs.metrics`` histogram named ``<name>.step_ms`` so the
    process-wide report sees training cadence without a StepTimer
    reference.

    >>> t = StepTimer()
    >>> for batch in loader:
    ...     with t.step():
    ...         loss = train_step(*batch)
    >>> t.summary()   # {'steps': N, 'mean_ms': ..., 'p50_ms': ...}
    """

    def __init__(self, skip_first=1, name="step_timer"):
        self.skip_first = skip_first
        self.times = []
        self._seen = 0
        self._hist = _metrics.histogram(f"{name}.step_ms")

    @contextlib.contextmanager
    def step(self):
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
        self._seen += 1
        if self._seen > self.skip_first:
            self.times.append(dt)
            self._hist.observe(dt * 1e3)
            if _journal.ACTIVE is not None:  # feeds the next step record
                _journal.ACTIVE.note_step_ms(dt * 1e3)

    def summary(self):
        if not self.times:
            return {"steps": 0}
        a = np.asarray(self.times) * 1e3
        return {"steps": len(a), "mean_ms": float(a.mean()),
                "p50_ms": float(np.percentile(a, 50)),
                "p90_ms": float(np.percentile(a, 90)),
                "p99_ms": float(np.percentile(a, 99)),
                "max_ms": float(a.max())}

    def reset(self):
        self.times.clear()
        self._seen = 0


_step_timer = StepTimer()


def add_profiler_step(*a, **k):
    """ref: profiler.add_profiler_step hook for Executor loops."""
    return _step_timer


def reset_profiler():
    """ref: fluid/profiler.py reset_profiler: drop collected records.
    jax.profiler traces are per start/stop window, so this is a no-op
    between windows; StepTimer state resets explicitly via .reset()."""
    return None
