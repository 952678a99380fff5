"""Span tracer: one call, two clocks.

``span("executor.compile", uid=3)`` records one complete event into a
bounded in-memory ring buffer on ``time.perf_counter``
(``export_chrome_trace(path)`` dumps it as ``chrome://tracing`` / Perfetto
JSON) AND opens a ``jax.profiler.TraceAnnotation`` of the same name and
attributes. While a ``jax.profiler`` trace is being taken
(``utils.profiler.profiler(log_dir=...)`` enables span tracing for its
window) the program's spans therefore land in the profile's ``/host:CPU``
plane, on the clock the device ops are stamped with: one XProf / Perfetto
trace holds the dataloader waits, ``trainstep.call`` step markers (a span
with a ``step_num`` attribute is a ``StepTraceAnnotation``, by which XProf
groups device ops into steps) and the device's instructions, which the
compiled step names by program op, phase and kernel (``core.dispatch.apply``,
``TrainStep``, ``ops/pallas``). Outside a profile the annotation is inert and
the ring alone records.

Off by default. ``span()`` with tracing disabled returns one shared
no-op context manager — no allocation, no clock read, no import of
``jax.profiler``, one module-bool check (the same discipline as the
``resilience.inject`` ``if ACTIVE`` hooks). Opt in per process with env
``PADDLE_TPU_TRACE=1`` or at runtime with ``enable_tracing()``.

The ring buffer is bounded (default 65536 spans): a week-long serving
process can leave tracing on and the newest spans win.

**Phase records** are the second way in, for work that happens once a
process or once a compile (the package's import, a parameter's
initializer, a step's lower / hash / cache load / compile, its first
execute, every program jax traces, lowers, compiles or loads): the same
record, written whether or not span tracing is on, by ``phase(name,
**attrs)`` round the work or by ``record(name, start, end, **attrs)``
after it (the package import cannot hold a context manager of a module it
is still importing; jax's duration events arrive with a duration). They
cost a clock read or two at set-up and nothing in a steady step, and they
live in a store of their own (the first ``PHASE_CAPACITY``, oldest kept:
they are the start-up), so a long run's step spans never evict them.

Every record carries an ``id`` and the ``parent`` that was open on its
thread when it began: a thread-local stack, pushed only when a record is
really made. A record written after the fact takes the innermost open
record as its parent and adopts the records which closed under that parent
on its thread since it began (an outer jax trace arrives after the inner
ones it held; a cache load arrives before the backend compile that asked
for it), so self time is a walk over ``parent``, not a guess from
containment. ``to_perf_counter(ts)`` puts a record's ``ts`` on
``time.perf_counter``.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import threading
import time

__all__ = [
    "span", "phase", "record", "to_perf_counter",
    "enable_tracing", "disable_tracing", "tracing_enabled",
    "clear_trace", "trace_events", "export_chrome_trace",
    "device_counter", "set_rank", "current_rank",
    "flow_start", "flow_finish",
    "DEFAULT_CAPACITY", "PHASE_CAPACITY", "DEVICE_PID_BASE",
    "RANK_PID_STRIDE",
]

DEFAULT_CAPACITY = 65536
# phase records a process keeps (a few thousand parameters, a few programs
# each, and every function jax traces once); past it the oldest stay: they
# are the start-up
PHASE_CAPACITY = 65536
# per-device lanes render as separate Chrome-trace processes; their pids
# are offset far above any real host pid so they never collide with the
# host lane
DEVICE_PID_BASE = 1 << 20
# per-RANK namespace inside the device pid band: rank r's device d lane
# is DEVICE_PID_BASE + r * RANK_PID_STRIDE + d, so a merged fleet trace
# (obs.fleet.merge_chrome_traces) never interleaves two ranks' device
# counter lanes under one pid. 4096 devices per process is far above
# any real per-host device count
RANK_PID_STRIDE = 1 << 12

# this process's rank identity (multi-process gangs: the supervisor
# hands each worker PADDLE_TPU_RANK). None = single-process, exports
# keep the historical os.getpid()/DEVICE_PID_BASE+id lanes exactly
_rank = None


def set_rank(rank):
    """Adopt a rank identity for trace exports: host spans land on
    pid=rank (a stable lane a merged fleet trace can line up, unlike
    OS pids that recycle across elastic relaunches) and device counter
    lanes shift into the rank's namespace slice."""
    global _rank
    _rank = None if rank is None else int(rank)


def current_rank():
    return _rank

_enabled = False
# a record is [name, ts µs, dur µs, tid, attrs, id, parent]; only
# ``parent`` is ever written again (adoption, in ``record``)
_events: collections.deque = collections.deque(maxlen=DEFAULT_CAPACITY)
_phases: list = []
_ids = itertools.count(1)   # next() is atomic under the GIL
# per-device counter samples (obs.spmd.update_device_gauges feeds this):
# (device_id, name, ts µs, value); bounded like the span ring
_device_samples: collections.deque = collections.deque(maxlen=16384)
_device_labels: dict = {}  # device_id -> lane label for the trace meta
# one perf-counter epoch per process: every span's ts is an offset from
# here, so spans from different threads land on one comparable timeline
_EPOCH = time.perf_counter()

_NULL = contextlib.nullcontext()  # stateless + reentrant: safe to share


class _Open(threading.local):
    """This thread's open records, innermost last, as ``[id, closed]``:
    ``closed`` holds the records that closed directly under it, in the
    order they ended, for a record written after the fact to adopt.
    ``levels[0]`` stands for no open record at all."""

    def __init__(self):
        self.levels = [[None, []]]


_open = _Open()
_CLOSED_KEPT = 4096   # of one level's closed records, the newest


def _keep(rec, is_phase):
    # list.append and deque.append are atomic under the GIL: no lock on
    # the record path
    if not is_phase:
        _events.append(rec)
    elif len(_phases) < PHASE_CAPACITY:
        _phases.append(rec)
    closed = _open.levels[-1][1]
    closed.append(rec)
    if len(closed) > _CLOSED_KEPT:
        del closed[:_CLOSED_KEPT // 2]


class _Span:
    __slots__ = ("name", "attrs", "is_phase", "dur_us", "_id", "_parent",
                 "_t0", "_annotation")

    def __init__(self, name, attrs, is_phase=False):
        self.name = name
        self.attrs = attrs
        self.is_phase = is_phase

    def __enter__(self):
        import jax.profiler as jp

        # the same span on the profiler's clock (inert unless a profile is
        # being taken); a step_num marks a step for XProf's grouping
        kind = jp.StepTraceAnnotation if "step_num" in self.attrs \
            else jp.TraceAnnotation
        self._annotation = kind(self.name, **self.attrs)
        self._annotation.__enter__()
        levels = _open.levels
        self._id, self._parent = next(_ids), levels[-1][0]
        levels.append([self._id, []])
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        levels = _open.levels
        if len(levels) > 1:   # a span closed on another thread than it
            levels.pop()      # opened on must not take the ground level
        self._annotation.__exit__(exc_type, exc, tb)
        self.dur_us = (t1 - self._t0) * 1e6
        _keep([self.name,
               (self._t0 - _EPOCH) * 1e6,  # ts µs
               self.dur_us,
               threading.get_ident(),
               self.attrs, self._id, self._parent], self.is_phase)
        return False


def span(name, **attrs):
    """Context manager timing one named span. A no-op (shared null
    context) unless tracing is enabled."""
    if not _enabled:
        return _NULL
    return _Span(name, attrs)


def phase(name, shared=None, /, **attrs):
    """Context manager timing one phase record: work done once a process
    or once a compile, recorded whether or not span tracing is on. Never
    round anything a steady step runs: that is ``span``'s. ``shared`` is a
    dict of attributes the caller keeps: what a later stage learns (a
    digest) it adds there, and every record made with it holds it."""
    return _Span(name, attrs if shared is None else shared, is_phase=True)


def record(name, start, end, /, **attrs):
    """Write one phase record after the fact, as the work ends, from two
    readings of ``time.perf_counter``. Its parent is the innermost record
    open on this thread, and the records that closed under that parent
    since ``start`` (by their midpoints: jax stamps its durations on
    another clock, and what one thread does is nested or apart) become its
    children. (No annotation in a profile being taken: the profiler's
    clock has moved on.)"""
    ts = (start - _EPOCH) * 1e6
    parent, closed = _open.levels[-1]
    rid = next(_ids)
    k = len(closed)
    while k and closed[k - 1][1] + closed[k - 1][2] / 2 >= ts:
        k -= 1
        closed[k][6] = rid
    del closed[k:]
    _keep([name, ts, (end - start) * 1e6, threading.get_ident(), attrs,
           rid, parent], True)


def to_perf_counter(ts):
    """A record's ``ts`` (or ``ts + dur``), in µs, as the reading
    ``time.perf_counter()`` gave at that moment, in seconds."""
    return _EPOCH + ts * 1e-6


def enable_tracing(capacity=None):
    """Turn span recording on; ``capacity`` resizes (and clears) the
    ring buffer."""
    global _enabled, _events
    if capacity is not None and capacity != _events.maxlen:
        _events = collections.deque(maxlen=int(capacity))
    _enabled = True


def disable_tracing():
    """Stop recording; already-recorded spans stay exportable."""
    global _enabled
    _enabled = False


def tracing_enabled():
    return _enabled


def clear_trace():
    _events.clear()
    del _phases[:]
    _device_samples.clear()
    _device_labels.clear()


def device_counter(device_id, name, value, label=None):
    """Record one per-device counter sample (e.g. HBM bytes in use) for
    the Chrome trace's per-device pid lanes. A no-op when tracing is
    disabled — callers on a hot path should gate on
    ``tracing_enabled()`` themselves (``obs.spmd.update_device_gauges``
    does)."""
    if not _enabled:
        return
    if label is not None:
        _device_labels[int(device_id)] = label
    _device_samples.append((int(device_id), name,
                            (time.perf_counter() - _EPOCH) * 1e6,
                            float(value)))


def flow_start(name, flow_id, pid, tid, ts_us, **args):
    """One Chrome-trace flow-start event ("s"): the tail of an arrow
    Perfetto draws between two slices — possibly on different pid
    lanes. Pair with :func:`flow_finish` under the same ``flow_id``
    (``obs.reqtrace`` uses these to draw a requeued request crossing
    from the victim replica's lane to the re-dispatched one's)."""
    return {"ph": "s", "cat": "req", "name": str(name),
            "id": int(flow_id), "pid": pid, "tid": tid,
            "ts": float(ts_us), "args": dict(args)}


def flow_finish(name, flow_id, pid, tid, ts_us, **args):
    """The matching flow-finish ("f") for :func:`flow_start`.
    ``bp="e"`` binds the arrowhead to the ENCLOSING slice at this
    timestamp rather than the next slice to start — the binding that
    keeps the arrow on the re-dispatch segment itself."""
    return {"ph": "f", "bp": "e", "cat": "req", "name": str(name),
            "id": int(flow_id), "pid": pid, "tid": tid,
            "ts": float(ts_us), "args": dict(args)}


def _records():
    return sorted(list(_phases) + list(_events), key=lambda rec: rec[1])


def trace_events():
    """Snapshot of the recorded phases and spans as dicts, by start time
    (spans newest-capped by the ring, phases oldest-capped)."""
    return [{"name": n, "ts": ts, "dur": dur, "tid": tid, "args": attrs,
             "id": rid, "parent": parent}
            for n, ts, dur, tid, attrs, rid, parent in _records()]


def export_chrome_trace(path):
    """Write the phase records and the span buffer as Chrome trace-event
    JSON (load in chrome://tracing or https://ui.perfetto.dev). Returns the
    number of records exported. With a rank identity set (:func:`set_rank`
    / env ``PADDLE_TPU_RANK``) the host lane is pid=rank and device lanes are
    rank-namespaced, so per-rank exports fuse collision-free."""
    rank = _rank
    pid = os.getpid() if rank is None else rank
    host_name = "paddle_tpu" if rank is None \
        else f"paddle_tpu rank {rank:02d}"
    events = [{"ph": "X", "pid": pid, "tid": tid, "name": n,
               "ts": ts, "dur": dur, "args": attrs,
               "span_id": rid, "parent_id": parent}
              for n, ts, dur, tid, attrs, rid, parent in _records()]
    events.append({"ph": "M", "pid": pid, "name": "process_name",
                   "args": {"name": host_name}})
    # per-device pid lanes: counter samples (HBM gauges) render as one
    # Chrome-trace "process" per device, below the host span lane —
    # inside this rank's namespace slice of the device pid band
    dev_base = DEVICE_PID_BASE + (rank or 0) * RANK_PID_STRIDE
    lanes = set()
    for dev_id, name, ts, value in list(_device_samples):
        lane = dev_base + dev_id
        lanes.add((lane, dev_id))
        events.append({"ph": "C", "pid": lane, "name": name, "ts": ts,
                       "args": {"value": value}})
    for lane, dev_id in sorted(lanes):
        label = _device_labels.get(dev_id, f"device {dev_id}")
        if rank is not None:
            label = f"rank {rank:02d} {label}"
        events.append({"ph": "M", "pid": lane, "name": "process_name",
                       "args": {"name": label}})
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        # default=str: span attrs may carry shapes/dtypes/paths — never
        # let an exotic attr make the whole export unserializable
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f,
                  default=str)
    return sum(1 for e in events if e["ph"] == "X")


if os.environ.get("PADDLE_TPU_TRACE", "").lower() not in ("", "0", "false"):
    enable_tracing()

# a supervised gang worker inherits its rank from the launcher
# (GangSupervisor / dist.launch hand each worker PADDLE_TPU_RANK)
try:
    set_rank(int(os.environ["PADDLE_TPU_RANK"]))
except (KeyError, ValueError):
    pass
