"""MFU / goodput accounting: turn step timings into utilization numbers.

The MLPerf TPU-pod scaling work and the Gemma-on-Cloud-TPU comparisons
treat three numbers as table stakes for operating a training stack:
step time, model-FLOPs-utilization (achieved FLOP/s over the chip's
peak), and goodput (how much of the wall clock went into steps that
actually advanced the model). The reference keeps these in scattered
VLOG output; here they are a small accounting layer the run journal
(``obs.journal``) feeds and summarizes.

FLOPs come from XLA's own ``cost_analysis`` on the compiled executable
(via ``utils.stats.compiled_stats``), cached per Executor cache entry —
no analytical per-layer formula to drift out of date. Peak FLOP/s comes
from the one table below, keyed by the device kind jax reports; a
device with no row is an error, and on the host CPU MFU is ``None``
rather than a made-up number.
"""
from __future__ import annotations

import threading

__all__ = [
    "PEAK_FLOPS_BY_KIND", "peak_flops",
    "executable_flops", "entry_flops", "entry_flops_nowait",
    "entry_analysis", "entry_analysis_nowait", "MFUAccounting", "goodput",
]

# Per-chip peak dense bf16 FLOP/s, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud TPU documentation, the "System architecture" page
# of each generation ("TPU v5e": 197 TFLOP/s bf16 per chip). The only
# table of peaks in the program (the benchmark's is benchmark/peaks.json).
PEAK_FLOPS_BY_KIND = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # what a v5e chip reports
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # what a v6e (Trillium) chip reports
}


def peak_flops(device_kind=None):
    """Peak bf16 FLOP/s of one chip of ``device_kind`` (default: this
    process's first device). An unknown kind raises: a default would
    invent a utilization. ``None`` only for the host-CPU backend, where
    MFU is not a metric."""
    if device_kind is None:
        import jax

        dev = jax.devices()[0]
        if dev.platform == "cpu":
            return None
        device_kind = dev.device_kind
    if device_kind not in PEAK_FLOPS_BY_KIND:
        raise KeyError(
            f"no peak FLOP/s known for device_kind {device_kind!r}: add "
            "it, with its source, to obs.mfu.PEAK_FLOPS_BY_KIND")
    return PEAK_FLOPS_BY_KIND[device_kind]


def executable_flops(fn, *example_args):
    """FLOPs of one invocation of ``fn`` per XLA's cost analysis, or
    ``None`` when the backend doesn't report it."""
    from ..utils.stats import compiled_stats

    try:
        cost = compiled_stats(fn, *example_args)["cost"]
    except Exception:
        return None
    v = cost.get("flops")
    return float(v) if v else None


def entry_analysis(compiled):
    """Lazy memory/cost/collective attribution for one Executor cache
    entry (``static_/executor.py`` ``_Compiled``). Lowers the entry's
    jitted fn against the arg structs captured at build time and reads
    XLA's ``memory_analysis`` / ``cost_analysis`` plus the executable's
    HLO text for the CollectiveProfile (``obs.spmd``); the result
    (fields possibly None when the backend reports nothing) is cached
    on the entry so the compile cost is paid once."""
    cached = getattr(compiled, "_entry_analysis", None)
    if cached is not None:
        return cached
    out = {"memory": None, "cost": None, "collectives": None}
    structs = getattr(compiled, "arg_structs", None)
    if structs is not None:
        from ..utils.stats import _analysis_dict, _cost_dict

        try:
            # an AOT-hydrated entry's fn IS already a jax.stages.
            # Compiled (runtime.aot) — analyze the actual executable
            # instead of paying a re-lower+compile
            c = compiled.fn if not hasattr(compiled.fn, "lower") \
                else compiled.fn.lower(*structs).compile()
        except Exception:
            c = None
        if c is not None:
            try:
                ma = c.memory_analysis()
                if ma is not None:
                    mem = _analysis_dict(ma, (
                        "argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "alias_size_in_bytes",
                        "generated_code_size_in_bytes"))
                    out["memory"] = mem or None
            except Exception:
                pass
            try:
                cost = _cost_dict(c.cost_analysis())
                out["cost"] = cost or None
            except Exception:
                pass
            try:
                from . import spmd as _spmd

                mesh = None
                axes = getattr(compiled, "mesh_axes", None)
                if axes is not None:
                    mesh = (axes, getattr(compiled, "mesh_device_ids",
                                          None))
                out["collectives"] = _spmd.collective_profile(
                    c.as_text(), mesh=mesh)
            except Exception:
                pass
    compiled._entry_analysis = out
    return out


def entry_flops(compiled):
    """FLOPs per run of one Executor cache entry (lazy, cached), or
    ``None``. BLOCKING: may pay the entry's analysis compile — fine for
    ``cache_stats(per_entry=True)``, never call it on the step path."""
    cost = entry_analysis(compiled)["cost"]
    v = (cost or {}).get("flops")
    return float(v) if v else None


_pending_lock = threading.Lock()
_pending_threads: list = []
_shutting_down = False


def _drain_analysis_threads(timeout_s=5.0):
    """Interpreter-exit guard for the background analysis compiles: a
    daemon thread still INSIDE an XLA compilation when Python
    finalizes tears down the C++ compile thread pool under it —
    ``terminate called without an active exception``, SIGABRT — which
    turns a clean worker exit into a spurious crash (a supervised gang
    would burn a restart on it). Refuse new analyses and give in-flight
    ones a bounded window to land; short-lived journaled processes (CI
    drills, preempted workers) exit clean, and a multi-second real-TPU
    compile still can't stall a preemption exit past the budget."""
    import time

    global _shutting_down
    _shutting_down = True
    deadline = time.monotonic() + float(timeout_s)
    with _pending_lock:
        threads = list(_pending_threads)
    for t in threads:
        try:
            t.join(max(0.0, deadline - time.monotonic()))
        except RuntimeError:
            pass  # never-started thread (start() itself failed)


def _analysis_worker(compiled):
    try:
        if not _shutting_down:
            entry_analysis(compiled)
    finally:
        with _pending_lock:
            if threading.current_thread() in _pending_threads:
                _pending_threads.remove(threading.current_thread())


def entry_analysis_nowait(compiled):
    """Non-blocking ``entry_analysis`` for the journal's step path:
    returns the cached analysis dict when it has landed, otherwise
    kicks the lower+compile off ONCE in a daemon thread and returns
    None — the step path must never stall behind a second XLA
    compilation (tens of seconds on a real chip). Early steps of each
    entry simply carry no flops/comm attribution; the MFU accounting
    already scopes achieved-FLOP/s to the steps that do. In-flight
    threads are drained at interpreter exit (see
    :func:`_drain_analysis_threads`)."""
    cached = getattr(compiled, "_entry_analysis", None)
    if cached is not None:
        return cached
    if _shutting_down:
        return None
    with _pending_lock:
        if getattr(compiled, "_entry_analysis_pending", False):
            return None
        compiled._entry_analysis_pending = True
        t = threading.Thread(target=_analysis_worker, args=(compiled,),
                             daemon=True)
        _pending_threads.append(t)
    t.start()
    return None


import atexit  # noqa: E402  (registration belongs next to the hook)

atexit.register(_drain_analysis_threads)


def entry_flops_nowait(compiled):
    """Non-blocking FLOPs for one entry (see
    ``entry_analysis_nowait``); None until the analysis lands."""
    cached = entry_analysis_nowait(compiled)
    if cached is None:
        return None
    return float((cached["cost"] or {}).get("flops") or 0) or None


def goodput(productive, skipped=0, retried=0):
    """Fraction of attempted step work that advanced the model:
    ``productive / (productive + skipped + retried)``. Skipped steps
    (nonfinite discard/rollback) and transient retries both burned a
    step's wall time without contributing. ``None`` with no steps."""
    total = productive + skipped + retried
    if total <= 0:
        return None
    return productive / float(total)


class MFUAccounting:
    """Accumulates per-step (step_ms, flops, examples) and renders the
    run-level summary: achieved FLOP/s, MFU vs the configured peak, and
    goodput from productive/skipped/retried counts."""

    def __init__(self, peak=None):
        self._peak = peak
        self.productive = 0
        self.skipped = 0
        self.retried = 0
        self._timed_ms = 0.0
        self._timed_steps = 0
        self._flop_ms = 0.0   # step_ms summed only where flops known
        self._flops = 0.0
        self._examples = 0
        self._comm_bytes = 0.0  # collective payload, steps where known
        self._wire_bytes = 0.0
        self._comm_steps = 0
        self._comm_flops = 0.0  # flops summed on comm-attributed steps

    def record(self, step_ms=None, flops=None, examples=None,
               productive=True, comm_bytes=None, wire_bytes=None,
               weight=1):
        """``weight`` is the number of optimizer steps this record
        covers — 1 normally, K for a fused ``run_steps`` window (whose
        step_ms/flops/examples/comm already describe the whole window,
        so only the step COUNTS need the weight)."""
        weight = max(1, int(weight))
        if productive:
            self.productive += weight
        else:
            self.skipped += weight
        if step_ms is not None and step_ms > 0:
            self._timed_ms += step_ms
            self._timed_steps += 1
            if flops:
                self._flops += float(flops)
                self._flop_ms += step_ms
        if comm_bytes:
            self._comm_bytes += float(comm_bytes)
            self._wire_bytes += float(wire_bytes or comm_bytes)
            self._comm_steps += 1
            if flops:
                self._comm_flops += float(flops)
        if examples:
            self._examples += int(examples)

    def note_retry(self, n=1):
        self.retried += n

    def reclassify_skip(self):
        """A step already recorded as productive turned out discarded
        (the static guard detects nonfinite AFTER the executor's step
        record): move one step from productive to skipped."""
        if self.productive > 0:
            self.productive -= 1
            self.skipped += 1

    def summary(self):
        achieved = (self._flops / (self._flop_ms / 1e3)
                    if self._flop_ms > 0 else None)
        peak = self._peak
        if peak is None and achieved is not None:
            # FLOPs were recorded, so steps ran and the backend exists:
            # the table lookup is a metadata read, never a backend init
            peak = peak_flops()
        out = {
            "productive_steps": self.productive,
            "skipped_steps": self.skipped,
            "retries": self.retried,
            "goodput": goodput(self.productive, self.skipped, self.retried),
            "mean_step_ms": (self._timed_ms / self._timed_steps
                             if self._timed_steps else None),
            "achieved_flops_per_s": achieved,
            "peak_flops_per_s": peak,
            "mfu": (achieved / peak if achieved and peak else None),
        }
        if self._examples and self._timed_ms > 0:
            out["examples_per_s"] = self._examples / (self._timed_ms / 1e3)
        if self._comm_steps:
            # compute-vs-comm roofline over the comm-attributed steps
            # (obs.spmd): None fields when no ICI bandwidth is known
            from .spmd import comm_roofline

            out["comm_bytes_per_step"] = self._comm_bytes / self._comm_steps
            rl = comm_roofline(
                {"total_bytes": self._comm_bytes / self._comm_steps,
                 "wire_bytes": self._wire_bytes / self._comm_steps},
                flops=(self._comm_flops / self._comm_steps
                       if self._comm_flops else None),
                peak=peak)
            out["comm_share"] = rl["comm_share"]
            out["comm_bound"] = rl["bound"]
        return out
