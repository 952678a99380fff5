"""RunJournal: an append-only JSONL flight recorder for one training run.

PR 3 gave the process instruments (``obs.metrics`` / ``obs.trace``);
this ties them to *a run*: a durable `journal.jsonl` under a run
directory (env ``PADDLE_TPU_RUN_DIR`` or an explicit path) holding

- one ``run_start`` header (backend, device count, env knobs, argv),
- a ``step`` record per training step (loss/fetches summary, step_ms,
  examples/sec, dataloader queue depth + consumer-wait delta, jit-cache
  hit/miss delta, FLOPs when known),
- discrete ``event`` records (compile, checkpoint save/load/fallback,
  resilience retry/skip/rollback/degrade, chaos activation,
  dataloader worker restarts),
- ``anomaly`` records from the detectors (``obs.anomaly``) evaluated
  on every step, and
- one ``run_end`` summary: MFU/goodput accounting (``obs.mfu``).

Write path: records buffer in memory (bounded) and flush every
``flush_every`` records or ``flush_interval_s`` seconds — a line is
written whole, so a reader never sees a torn record from a clean
writer. The file rotates at ``max_bytes`` (``journal.jsonl`` is always
the live tail; rotated parts are ``journal.<n>.jsonl``). On interpreter
exit (``atexit``) an unclosed journal flushes and writes its summary;
an exception exiting the ``with`` block (or an explicit
``postmortem()``) additionally dumps ``postmortem.json`` — the last-K
step records, recent events, the exception, a metrics snapshot — and a
Chrome trace when span tracing is on.

Hook contract (the established chaos/obs pattern): every production
hook is ``if _journal.ACTIVE is not None: ...`` — with no journal
configured the step path performs a single None check, no call, no
allocation, no host sync. With a journal active, summarizing an eager
loss costs one scalar device->host read per step (standard logging
cost; the static Executor path summarizes already-fetched host arrays,
and its lazy/async fetch paths — ``return_numpy=False`` /
``fetch_async=True`` — journal metadata-only summaries so logging
never re-introduces the host sync the caller opted out of). A fused
``run_steps`` window journals as ONE record with ``steps_fused=K``.
"""
from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from collections import deque

from . import lockdep as _lockdep
from . import metrics as _metrics
from . import trace as _trace
from .anomaly import AnomalyEngine, default_detectors
from .mfu import MFUAccounting, peak_flops

__all__ = ["RunJournal", "ACTIVE", "start_run", "end_run", "active",
           "JOURNAL_FILE", "POSTMORTEM_FILE", "TRACE_FILE",
           "RANK_ENV", "SUPERVISOR_DIR", "ROUTER_DIR", "rank_subdir",
           "env_rank"]

JOURNAL_FILE = "journal.jsonl"
POSTMORTEM_FILE = "postmortem.json"
TRACE_FILE = "trace.json"
# the rank identity a gang launcher (resilience.elastic.GangSupervisor,
# dist.launch) hands each worker, alongside a per-rank run dir
RANK_ENV = "PADDLE_TPU_RANK"
# where a gang supervisor's own events land under the fleet run dir —
# ONE constant shared by the writer (resilience.elastic) and the reader
# (obs.fleet); a rename on either side would silently orphan the record
SUPERVISOR_DIR = "supervisor"
# likewise for the serve-fleet router's own journal (writer:
# serving.fleet.Router / drill; reader: obs.fleet.router_summary)
ROUTER_DIR = "router"

# The active journal every hook checks (mirrors resilience.inject.ACTIVE:
# None => hooks are a single None check and nothing else).
ACTIVE = None


def active():
    return ACTIVE


def rank_subdir(rank):
    """One naming convention for per-rank journal dirs
    (``rank_00``, ``rank_01``, ...): the writer (RunJournal), the gang
    launchers and the reader (``obs.fleet``) must all agree on it."""
    return f"rank_{int(rank):02d}"


def env_rank(env=None):
    """This process's rank from ``PADDLE_TPU_RANK``, or None outside a
    supervised gang (or on an unparseable value — identity must never
    break journaling)."""
    v = (env if env is not None else os.environ).get(RANK_ENV)
    if v in (None, ""):
        return None
    try:
        return int(v)
    except ValueError:
        return None


def _env_knobs():
    return {k: v for k, v in sorted(os.environ.items())
            if k.startswith(("PADDLE_TPU_", "JAX_", "XLA_"))}


def _backend_info():
    """Backend identity, read with the first step record — by then the
    run has initialized its backend, so this never is the call that
    creates one (start() runs at import under PADDLE_TPU_RUN_DIR, before
    the user's own platform config)."""
    try:
        import jax

        devs = jax.devices()
        kinds = {}
        for d in devs:
            kinds[d.device_kind] = kinds.get(d.device_kind, 0) + 1
        out = {"backend": devs[0].platform,
               "platform": devs[0].platform,
               "ndev": len(devs), "device_count": len(devs),
               "device_kind": devs[0].device_kind,
               "device_kinds": kinds}
        if len(devs) <= 64:  # keep the event record bounded on big pods
            out["devices"] = [
                {"id": int(d.id), "kind": d.device_kind,
                 "process": int(getattr(d, "process_index", 0))}
                for d in devs]
        return out
    except Exception as e:  # journal must work before/without a backend
        return {"backend": None, "ndev": None,
                "backend_error": f"{type(e).__name__}: {e}"}


def _summarize_value(v, sync=True):
    """Small, JSON-safe summary of one fetched value: size-1 numerics
    inline as a float, everything else as shape/dtype metadata. Only a
    SIZE-1 value is ever materialized (one scalar read); larger arrays
    are summarized from metadata alone, so a lazy device fetch
    (``return_numpy=False``) is never synced wholesale.

    ``sync=False`` forbids even that scalar read for DEVICE values
    (host numpy stays readable — it costs nothing): the async fetch
    path (``Executor.run(fetch_async=True)`` / lazy Tensors) must not
    pay a hidden per-step device->host block just for logging."""
    import numpy as np

    v = getattr(v, "_data", v)
    shape, dtype = getattr(v, "shape", None), getattr(v, "dtype", None)
    if shape is None or dtype is None:
        if isinstance(v, (bool, int, float)):
            return float(v)
        return {"repr": repr(v)[:80]}
    size = 1
    for s in shape:
        size *= int(s)
    readable = sync or isinstance(v, (np.ndarray, np.generic))
    try:
        if size == 1 and readable and np.dtype(dtype).kind in "fiub":
            return float(np.asarray(v).reshape(()))
    except (TypeError, ValueError):
        pass
    return {"shape": [int(s) for s in shape], "dtype": str(dtype)}


class RunJournal:
    """One run's flight recorder. Usable three ways:

    - process-wide via env: ``PADDLE_TPU_RUN_DIR=/runs/exp7`` auto-starts
      a journal at import and every instrumented site feeds it;
    - explicitly: ``j = obs.start_run("/runs/exp7")`` ... ``obs.end_run()``;
    - scoped: ``with RunJournal("/runs/exp7") as j:`` — an exception
      leaving the block writes the postmortem before closing.

    Rank identity (multi-process gangs): with ``rank=`` (or env
    ``PADDLE_TPU_RANK``, which GangSupervisor / ``dist.launch`` set per
    worker) the journal writes under ``<run_dir>/rank_NN/`` — each rank
    owns its file, so N workers journaling into one run dir can never
    tear each other's lines. ``obs.fleet`` aggregates the rank subdirs
    back into one cross-rank view.
    """

    def __init__(self, run_dir=None, *, rank=None, flush_every=32,
                 flush_interval_s=5.0, max_bytes=64 << 20,
                 postmortem_steps=64, detectors=None,
                 anomaly_callback=None, peak=None, compute_flops=None):
        run_dir = run_dir or os.environ.get("PADDLE_TPU_RUN_DIR")
        if not run_dir:
            raise ValueError(
                "RunJournal needs a run directory: pass run_dir or set "
                "PADDLE_TPU_RUN_DIR")
        self.rank = env_rank() if rank is None else int(rank)
        if self.rank is not None and os.path.basename(
                os.path.normpath(str(run_dir))) != rank_subdir(self.rank):
            # a launcher that already handed us our per-rank subdir
            # (basename matches) must not get a second nesting level
            run_dir = os.path.join(str(run_dir), rank_subdir(self.rank))
        self.run_dir = str(run_dir)
        self.flush_every = max(1, int(flush_every))
        self.flush_interval_s = float(flush_interval_s)
        self.max_bytes = int(max_bytes)
        if compute_flops is None:
            # default on, env-defeatable: the lazy per-entry FLOPs
            # attribution pays a BACKGROUND analysis compile per entry —
            # free wall-clock normally, but real CPU contention inside a
            # worker racing a heartbeat watchdog on a loaded host
            # (PADDLE_TPU_JOURNAL_FLOPS=0 is how gang drills quiet it)
            compute_flops = os.environ.get(
                "PADDLE_TPU_JOURNAL_FLOPS", "").lower() not in \
                ("0", "false", "off")
        self.compute_flops = bool(compute_flops)
        # leaf lock: record/event paths are called from under the
        # scheduler/engine/prefetcher locks, so nothing may be
        # acquired while THIS is held (lockdep enforces it)
        self._lock = _lockdep.rlock("obs.journal")
        self._buf = []
        self._file = None
        self._bytes = 0
        self._part = 0
        self._last_flush = time.monotonic()
        self._closed = True
        self._step = 0
        self._t_start = None
        self._last_timer_ms = None
        self._last_steps = deque(maxlen=int(postmortem_steps))
        self._last_events = deque(maxlen=int(postmortem_steps))
        self._postmortem_written = False
        self._backend_written = False
        self.accounting = MFUAccounting(peak=peak)
        if detectors is None:
            try:
                detectors = default_detectors()
            except Exception as e:
                # a typo'd PADDLE_TPU_ANOMALY spec must cost the
                # detectors, not the whole flight recorder
                import warnings

                warnings.warn(
                    f"anomaly detectors disabled — bad PADDLE_TPU_ANOMALY "
                    f"spec? ({type(e).__name__}: {e})", RuntimeWarning)
                detectors = []
        self.anomalies = AnomalyEngine(detectors,
                                       callback=anomaly_callback)
        # metrics baselines for per-step deltas (interned refs stay live
        # across obs.metrics.reset())
        self._m_hits = _metrics.counter("executor.jit_cache.hits")
        self._m_misses = _metrics.counter("executor.jit_cache.misses")
        self._m_queue = _metrics.gauge("dataloader.queue_depth")
        self._m_wait = _metrics.histogram("dataloader.consumer_wait_ms")
        self._hits0 = self._mis0 = 0
        self._wait0 = 0.0

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        with self._lock:
            if not self._closed:
                return self
            os.makedirs(self.run_dir, exist_ok=True)
            self._file = open(self._path(), "a", encoding="utf-8")
            self._bytes = self._file.tell()
            # resume-safe rotation: continue numbering after any parts a
            # previous run into this dir already rotated out, or
            # os.replace would silently clobber journal.1.jsonl
            for fn in os.listdir(self.run_dir):
                if fn.startswith("journal.") and fn.endswith(".jsonl") \
                        and fn != JOURNAL_FILE:
                    try:
                        self._part = max(self._part,
                                         int(fn.split(".")[1]))
                    except ValueError:
                        pass
            self._closed = False
            self._t_start = time.monotonic()
            self._hits0 = self._m_hits.value
            self._mis0 = self._m_misses.value
            self._wait0 = self._m_wait.sum
            atexit.register(self._atexit)
        # NOTE: no backend info / peak-FLOPs probe here — start() runs at
        # import when PADDLE_TPU_RUN_DIR is set, and touching
        # jax.devices() would pin the platform before the user's own
        # config. A "backend" event is emitted lazily with the first
        # step record instead.
        rec = {
            "t": "run_start", "ts": time.time(), "pid": os.getpid(),
            "argv": list(sys.argv), "run_dir": self.run_dir,
            "env": _env_knobs()}
        if self.rank is not None:
            rec["rank"] = self.rank
        self._write(rec)
        return self

    def close(self, exc=None):
        """Write the run_end summary and release the file. ``exc`` (an
        exception instance) additionally writes the postmortem first."""
        with self._lock:
            if self._closed:
                return
            if exc is not None:
                self.postmortem(exc)
            elif _trace.tracing_enabled() and not self._postmortem_written:
                # clean close with tracing on: leave the per-run Chrome
                # trace next to the journal (per-rank exports are what
                # obs.fleet.merge_chrome_traces fuses into fleet lanes)
                try:
                    _trace.export_chrome_trace(
                        os.path.join(self.run_dir, TRACE_FILE))
                except Exception:
                    pass
            self._write({"t": "run_end", "ts": time.time(),
                         "summary": self.summary()}, _locked=True)
            self._flush_locked()
            self._file.close()
            self._file = None
            self._closed = True
            try:
                atexit.unregister(self._atexit)
            except Exception:
                pass
        global ACTIVE
        if ACTIVE is self:
            ACTIVE = None

    def _atexit(self):
        try:
            self.close()
        except Exception:
            pass

    def _adopt_trace_rank(self):
        """Becoming the PROCESS-WIDE journal with a rank identity also
        adopts that rank for trace exports (one process = one rank), so
        per-rank Chrome traces fuse collision-free. A standalone
        (non-installed) journal never mutates global trace state —
        test fixtures build many ranks in one process."""
        if self.rank is not None and _trace.current_rank() is None:
            _trace.set_rank(self.rank)

    def __enter__(self):
        """Scoped use installs the journal process-wide for the block —
        the hooks all read ``journal.ACTIVE``, so a non-installed
        journal would record nothing."""
        global ACTIVE
        self._prev_active = ACTIVE
        self.start()
        ACTIVE = self
        self._adopt_trace_rank()
        return self

    def __exit__(self, exc_type, exc, tb):
        global ACTIVE
        self.close(exc=exc)
        prev = getattr(self, "_prev_active", None)
        if ACTIVE is None and prev is not None and not prev.closed:
            ACTIVE = prev
        return False

    @property
    def closed(self):
        return self._closed

    # -- write path ----------------------------------------------------------
    def _path(self):
        return os.path.join(self.run_dir, JOURNAL_FILE)

    def _write(self, rec, _locked=False):
        line = json.dumps(rec, default=str)
        lock = self._lock
        if _locked:
            self._buf.append(line)
            self._maybe_flush_locked(len(line))
            return
        with lock:
            if self._closed:
                return
            self._buf.append(line)
            self._maybe_flush_locked(len(line))

    def _maybe_flush_locked(self, nbytes):
        self._bytes += nbytes + 1
        now = time.monotonic()
        if len(self._buf) >= self.flush_every or \
                now - self._last_flush >= self.flush_interval_s:
            self._flush_locked()

    def _flush_locked(self):
        if self._buf and self._file is not None:
            self._file.write("\n".join(self._buf) + "\n")
            self._file.flush()
            self._buf.clear()
        self._last_flush = time.monotonic()
        if self._bytes >= self.max_bytes and self._file is not None:
            self._file.close()
            self._part += 1
            os.replace(self._path(), os.path.join(
                self.run_dir, f"journal.{self._part}.jsonl"))
            self._file = open(self._path(), "a", encoding="utf-8")
            self._bytes = 0

    def flush(self):
        with self._lock:
            if not self._closed:
                self._flush_locked()

    # -- recording -----------------------------------------------------------
    def record_step(self, loss=None, fetches=None, step_ms=None,
                    examples=None, flops=None, skipped=False,
                    nonfinite=False, source=None, comm=None, **extra):
        """Append one per-step record. ``loss`` must already be a host
        scalar (or None); ``fetches`` a list of host-side values."""
        import math

        # host-side value summarization stays OUTSIDE the lock (it may
        # read a scalar off-device); all shared mutation — step counter,
        # metric baselines, accounting, detectors, buffers — happens
        # under ONE lock hold so concurrent steppers can't lose counts
        # or mutate a detector window mid-iteration
        if loss is not None:
            try:
                loss = float(loss)
            except (TypeError, ValueError):
                loss = None
        if loss is not None and not math.isfinite(loss):
            nonfinite = True
        fetch_summary = extra.pop("_fetch_summary", None)
        if fetch_summary is None and fetches:
            fetch_summary = [_summarize_value(v) for v in fetches[:4]]
        with self._lock:
            if self._closed:
                return None
            if not self._backend_written:
                # deferred from start(): by the first recorded step a
                # real run has initialized its backend, so this probe is
                # a metadata read, never a backend-creating side effect
                self._backend_written = True
                self.event("backend", peak_flops_per_s=peak_flops(),
                           **_backend_info())
            self._step += 1
            step = self._step
            hits, misses = self._m_hits.value, self._m_misses.value
            dhits, dmis = hits - self._hits0, misses - self._mis0
            self._hits0, self._mis0 = hits, misses
            wait = self._m_wait.sum
            dwait, self._wait0 = wait - self._wait0, wait
            if step_ms is None:
                step_ms, self._last_timer_ms = self._last_timer_ms, None
            rec = {"t": "step", "step": step, "ts": time.time(),
                   "loss": loss, "step_ms": step_ms}
            if fetch_summary:
                rec["fetches"] = fetch_summary
            if examples:
                rec["examples"] = int(examples)
                if step_ms:
                    rec["examples_per_s"] = examples / (step_ms / 1e3)
            if flops:
                rec["flops"] = float(flops)
            if dhits or dmis:
                rec["jit_cache"] = {"hits": dhits, "misses": dmis}
            qd = self._m_queue.value
            if qd:
                rec["queue_depth"] = qd
            if dwait > 0:
                rec["dl_wait_ms"] = dwait
            if comm:
                rec["comm"] = comm
            if skipped:
                rec["skipped"] = True
            if nonfinite:
                rec["nonfinite"] = True
            if source:
                rec["source"] = source
            rec.update(extra)
            self.accounting.record(
                step_ms=step_ms, flops=flops, examples=examples,
                productive=not (skipped or nonfinite),
                comm_bytes=(comm or {}).get("total_bytes"),
                wire_bytes=(comm or {}).get("wire_bytes"),
                # a fused window is ONE record but K optimizer steps:
                # goodput / productive-step counts weight by it
                weight=rec.get("steps_fused") or 1)
            self._last_steps.append(rec)
            self._write(rec, _locked=True)
            for fired in self.anomalies.observe(rec):
                self._write({"t": "anomaly", "ts": time.time(), **fired},
                            _locked=True)
        return rec

    def record_request(self, rid, state=None, arrival_t=None,
                       admit_t=None, first_token_t=None, finish_t=None,
                       prompt_tokens=None, output_tokens=None,
                       pages_peak=None, preemptions=0, **extra):
        """Append one per-request serving record (the decode analog of
        a training step record): the request's lifecycle timestamps in
        the SERVING clock (the engine's injectable clock, so tests are
        exact), derived TTFT/TPOT/e2e/queue latencies in ms, and the
        KV-page + preemption footprint. Per-phase ms fields ride
        ``extra`` (the engine passes ``prefill_ms``/``preempt_ms``/
        ``decode_ms`` from its preempt/resume stamps; with the derived
        ``queue_ms`` they telescope exactly to ``e2e_ms`` — the
        ``obs.reqtrace`` attribution invariant).
        ``tools/run_report.py`` summarizes these into p50/p99
        columns."""
        rec = {"t": "request", "rid": rid, "ts": time.time()}
        if state is not None:
            rec["state"] = state
        for k, v in (("arrival_t", arrival_t), ("admit_t", admit_t),
                     ("first_token_t", first_token_t),
                     ("finish_t", finish_t)):
            if v is not None:
                rec[k] = float(v)
        if prompt_tokens is not None:
            rec["prompt_tokens"] = int(prompt_tokens)
        if output_tokens is not None:
            rec["output_tokens"] = int(output_tokens)
        if pages_peak is not None:
            rec["pages_peak"] = int(pages_peak)
        if preemptions:
            rec["preemptions"] = int(preemptions)
        if arrival_t is not None and admit_t is not None:
            rec["queue_ms"] = (admit_t - arrival_t) * 1e3
        if arrival_t is not None and first_token_t is not None:
            rec["ttft_ms"] = (first_token_t - arrival_t) * 1e3
        if arrival_t is not None and finish_t is not None:
            rec["e2e_ms"] = (finish_t - arrival_t) * 1e3
        if first_token_t is not None and finish_t is not None and \
                output_tokens and output_tokens > 1:
            rec["tpot_ms"] = (finish_t - first_token_t) * 1e3 / \
                (output_tokens - 1)
        rec.update(extra)
        with self._lock:
            if self._closed:
                return None
            self._write(rec, _locked=True)
        return rec

    def event(self, kind, **fields):
        """Append one discrete event record (compile, checkpoint,
        resilience recovery, chaos activation, ...)."""
        with self._lock:
            if self._closed:
                return None
            if kind.startswith("resilience.retry"):
                self.accounting.note_retry()
            elif kind in ("resilience.skipped", "resilience.rollbacks") \
                    and fields.get("source") == "guarded_executor":
                # ONLY the static guard discards a step AFTER the
                # executor hook recorded it as productive: reclassify
                # that record. The eager GuardedStep records its own
                # skipped steps (its event says source="guarded_step"),
                # and without the source check it would misreclassify an
                # unrelated earlier executor step (e.g. an eval pass).
                # The step's JSONL line is already flushed, so the
                # correction is carried ON THIS EVENT
                # (reclassified_step) — readers (tools/run_report.py)
                # apply it when loading.
                last = self._last_steps[-1] if self._last_steps else None
                if last is not None and last.get("source") == "executor" \
                        and not (last.get("skipped")
                                 or last.get("nonfinite")):
                    last["skipped"] = True
                    self.accounting.reclassify_skip()
                    fields = dict(fields,
                                  reclassified_step=last["step"])
            rec = {"t": "event", "kind": kind, "ts": time.time(),
                   "step": self._step, **fields}
            self._last_events.append(rec)
            self._write(rec, _locked=True)
        return rec

    def record_plan(self, plan, **fields):
        """One ``plan`` event per auto-parallel compile
        (``fleet.auto_parallel`` / ``auto_parallel_step``): the mesh
        shape, per-axis roles, canonical axes, and the planner's
        predicted vs HLO-measured collective wire bytes (mismatch is
        their relative delta; None until ``fleet.verify_plan`` ran).
        One payload shape for both the static and eager paths —
        ``tools/run_report.py`` renders it and gates on the mismatch
        in ``--diff``."""
        return self.event("plan", **plan.event_fields(), **fields)

    def record_memory(self, compiled=None, analysis=None,
                      predicted_bytes=None, per_device_bytes=None,
                      measured_bytes=None, **fields):
        """One ``memory`` event per compiled entry: the static
        peak-HBM prediction (``analysis.memory.estimate_entry``,
        attached by ``Executor._build``) and — once the entry's lazy
        analysis has landed — the executable's own
        ``memory_analysis()`` total, with ``drift`` their relative
        delta. Emitted twice per entry like ``plan`` events: once at
        compile (predicted only) and once measured; readers
        (``tools/run_report.py``) take the measured record. ``drift``
        compares the per-device prediction on mesh entries (XLA
        reports per-device allocations) and the total otherwise.
        Synthetic callers (self-tests) pass the byte fields
        directly."""
        sharded = False
        if compiled is not None:
            pm = getattr(compiled, "predicted_memory", None) or {}
            if predicted_bytes is None:
                predicted_bytes = pm.get("peak_bytes")
            if per_device_bytes is None:
                per_device_bytes = pm.get("per_device_bytes")
            sharded = bool(getattr(compiled, "mesh_axes", None))
            fields.setdefault("entry_uid",
                              getattr(compiled, "program_uid", None))
            fields.setdefault("version",
                              getattr(compiled, "program_version", None))
            if getattr(compiled, "steps", None):
                fields.setdefault("steps_fused", compiled.steps)
            if analysis is not None and measured_bytes is None:
                mem = analysis.get("memory") or None
                if mem:
                    from ..analysis.memory import measured_peak_bytes

                    measured_bytes = measured_peak_bytes(mem)
        drift = None
        ref = per_device_bytes if (sharded and per_device_bytes) \
            else predicted_bytes
        if ref and measured_bytes:
            drift = abs(ref - measured_bytes) / measured_bytes
        return self.event(
            "memory", predicted_peak_bytes=predicted_bytes,
            per_device_bytes=per_device_bytes,
            measured_peak_bytes=measured_bytes, drift=drift, **fields)

    def note_step_ms(self, ms):
        """StepTimer feed: remember the latest timed step so the next
        ``record_step`` without an explicit ``step_ms`` uses it."""
        self._last_timer_ms = float(ms)

    def sync_step(self, global_step):
        """Align the journal's step numbering with the trainer's OWN
        global step: the next recorded step gets number
        ``global_step``. Elastic workers call this once per loop
        iteration so a relaunched incarnation's records continue at
        its resume step instead of restarting at 1 — which is what
        lets ``obs.fleet.align_steps`` line records up across ranks
        AND attempts by global step."""
        with self._lock:
            self._step = int(global_step) - 1

    def _entry_flops_comm(self, compiled):
        """Non-blocking per-entry FLOPs + collective attribution (a
        background thread pays the analysis compile; early steps carry
        None)."""
        flops = comm = None
        if self.compute_flops:
            from .mfu import entry_analysis_nowait

            analysis = entry_analysis_nowait(compiled)
            if analysis is not None:
                if not getattr(compiled, "_memory_journaled", False):
                    # the measured half of the per-entry memory event:
                    # memory_analysis() landed with the lazy analysis,
                    # so journal predicted-vs-measured ONCE per entry
                    compiled._memory_journaled = True
                    try:
                        self.record_memory(compiled, analysis=analysis)
                    except Exception:
                        pass
                flops = float((analysis["cost"] or {}).get("flops")
                              or 0) or None
                prof = analysis.get("collectives")
                if prof and prof.get("n_ops"):
                    # the entry's per-execution collective volume IS the
                    # step's comm delta (one executable run per step)
                    comm = {
                        "total_bytes": prof["total_bytes"],
                        "wire_bytes": prof["wire_bytes"],
                        "quant_wire_bytes":
                            prof.get("quant_wire_bytes", 0),
                        "all_reduce_bytes":
                            prof["bytes"].get("all-reduce", 0),
                        "n_ops": prof["n_ops"],
                    }
        return flops, comm

    # called from the Executor run hook: everything here is host-side
    # metadata — the FLOPs/comm lookup is non-blocking (a background
    # thread pays the entry's analysis compile; early steps carry
    # flops=None and no comm attribution). ``synced=False`` (lazy /
    # async fetches) keeps even the size-1 loss summary off the device.
    def record_executor_run(self, compiled, fetches, run_ms, synced=True,
                            source="executor", examples=None):
        flops, comm = self._entry_flops_comm(compiled)
        # summarize ONCE and reuse: with lazy fetches
        # (return_numpy=False) each size-1 summary is a scalar device
        # read, and doing it twice would double the step's logging sync
        summary = [_summarize_value(v, sync=synced)
                   for v in fetches[:4]] if fetches else None
        loss = summary[0] if summary and isinstance(summary[0], float) \
            else None
        if examples is None:
            # entry-shape fallback; a batch-bucketed caller (the
            # Predictor pads to its bucket) passes the TRUE count so
            # examples/s never counts padding
            examples = getattr(compiled, "examples_hint", None)
        return self.record_step(
            loss=loss, step_ms=run_ms, examples=examples,
            flops=flops, comm=comm, source=source,
            _fetch_summary=summary)

    def record_fused_run(self, compiled, fetches, run_ms, steps,
                         synced=True):
        """One fused ``Executor.run_steps`` dispatch = ONE step record
        carrying ``steps_fused=K`` (not K records: the flight recorder
        mirrors dispatches, and fan-out would fabricate K identical
        timings from one measurement). ``loss`` is the LAST microbatch's
        (the trajectory endpoint the anomaly detectors should track);
        ``examples`` covers all K microbatches, and the entry's FLOPs /
        collective volumes already describe the whole K-step executable,
        so MFU and comm accounting stay exact."""
        import numpy as np

        steps = int(steps)
        flops, comm = self._entry_flops_comm(compiled)
        summary = [_summarize_value(v, sync=synced)
                   for v in fetches[:4]] if fetches else None
        loss = None
        if fetches and synced:
            try:  # stacked (K,) trajectory -> endpoint scalar
                arr = np.asarray(getattr(fetches[0], "_data", fetches[0]))
                if arr.shape == (steps,) and arr.dtype.kind in "fiub":
                    loss = float(arr[-1])
            except (TypeError, ValueError):
                pass
        hint = getattr(compiled, "examples_hint", None)
        return self.record_step(
            loss=loss, step_ms=run_ms,
            examples=hint * steps if hint else None,
            flops=flops, comm=comm, source="executor",
            steps_fused=steps, _fetch_summary=summary)

    # -- summaries -----------------------------------------------------------
    def summary(self):
        out = self.accounting.summary()
        out["steps"] = self._step  # records (= dispatches), unchanged
        # optimizer steps weight fused windows by K (steps_fused): the
        # number a sequential run of the same training is comparable to
        opt_steps = self.accounting.productive + self.accounting.skipped
        out["optimizer_steps"] = opt_steps
        if self._t_start is not None:
            wall = time.monotonic() - self._t_start
            out["wall_s"] = wall
            if wall > 0 and self._step:
                out["steps_per_s"] = self._step / wall
            if wall > 0 and opt_steps:
                out["optimizer_steps_per_s"] = opt_steps / wall
        out["anomalies_fired"] = len(self.anomalies.fired)
        return out

    def postmortem(self, exc=None, note=None):
        """Dump ``postmortem.json``: run header context, the last-K step
        records and events, the exception (if any), a metrics snapshot,
        and — when span tracing is on — a Chrome trace next to it."""
        with self._lock:
            dump = {
                "ts": time.time(), "run_dir": self.run_dir,
                "note": note, "summary": self.summary(),
                "last_steps": list(self._last_steps),
                "last_events": list(self._last_events),
                "anomalies": list(self.anomalies.fired),
                "metrics": _metrics.snapshot(),
            }
            if exc is not None:
                import traceback

                dump["exception"] = {
                    "type": type(exc).__name__, "message": str(exc),
                    "traceback": traceback.format_exception(
                        type(exc), exc, exc.__traceback__),
                }
            path = os.path.join(self.run_dir, POSTMORTEM_FILE)
            os.makedirs(self.run_dir, exist_ok=True)
            if _trace.tracing_enabled():
                # export BEFORE the dump is serialized, so the
                # postmortem actually carries the trace pointer
                try:
                    trace_path = os.path.join(self.run_dir, TRACE_FILE)
                    _trace.export_chrome_trace(trace_path)
                    dump["trace_file"] = trace_path
                except Exception:
                    pass
            with open(path, "w", encoding="utf-8") as f:
                json.dump(dump, f, default=str, indent=1)
            self._postmortem_written = True
            if not self._closed:
                self.event("postmortem", path=path,
                           error=(f"{type(exc).__name__}: {exc}"
                                  if exc is not None else note))
                self._flush_locked()
        return path


def start_run(run_dir=None, **kw):
    """Create, start, and install the process-wide journal (replacing
    any previous one after closing it). ``run_dir`` defaults to env
    ``PADDLE_TPU_RUN_DIR``."""
    global ACTIVE
    if ACTIVE is not None:
        ACTIVE.close()
    j = RunJournal(run_dir, **kw).start()
    ACTIVE = j
    j._adopt_trace_rank()
    return j


def end_run(exc=None):
    """Close and uninstall the process-wide journal (no-op without
    one). Returns the final summary dict, or None."""
    global ACTIVE
    j, ACTIVE = ACTIVE, None
    if j is None:
        return None
    out = j.summary()
    j.close(exc=exc)
    return out


if os.environ.get("PADDLE_TPU_RUN_DIR"):
    try:
        start_run()
    except Exception as _e:  # an unwritable dir must not poison import —
        ACTIVE = None        # but a silently-missing flight record is a
        import warnings      # debugging trap, so say it happened

        warnings.warn(
            f"PADDLE_TPU_RUN_DIR is set but the run journal failed to "
            f"start ({type(_e).__name__}: {_e}); no flight record will "
            "be written", RuntimeWarning)
