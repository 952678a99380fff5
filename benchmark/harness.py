"""What every loop kind and every per-layer reader shares: finding a cell's
files by name, the device, the traced steps, the result line."""
import contextlib
import dataclasses
import importlib.util
import json
import os
import re
import shutil
import tempfile
import time
from typing import Any

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# ---- files by name ---------------------------------------------------------
def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``<kind>/<name>.py`` by path, since a metric's name may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name, man=None):
    """The cell's entry of BENCHMARK.json joined with its own file, its
    configuration's and its traffic mix's."""
    man = man or manifest()
    entries = [w for w in man["workloads"] if w["name"] == name]
    if not entries:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = {**load_json("workloads", name + ".json"), **entries[0]}
    files = {c["name"]: c["file"] for c in man["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        cell["config"] = json.load(f)
    cell["traffic"] = load_json("traffic", cell["traffic"] + ".json")
    return cell


def end_to_end_of(man, cell_name):
    """The end-to-end metrics this cell reports: every one, but for those
    that list their cells under ``workloads``."""
    return [m for m in man["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def layer_readers(man, cell):
    """(entry, reader) of every per-layer metric this cell reports: those
    that move an end-to-end metric of the cell, unless the reader's own rule
    on the cell's fields (``reports(cell)``, say ``chips > 1``) leaves the
    cell out. The rule is the reader's, so that a new cell picks up every
    metric that fits it; BENCHMARK.json's ``workloads`` key repeats its
    outcome for the driver, and the manifest test holds the two together."""
    reported = {m["name"] for m in end_to_end_of(man, cell["name"])}
    out = []
    for m in man["per_layer"]:
        reader = load_module("layer_metrics", m["name"])
        if m["moves"] in reported and \
                getattr(reader, "reports", lambda cell: True)(cell):
            out.append((m, reader))
    return out


def peaks(device_kind):
    table = load_json("peaks.json")["by_device_kind"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         "benchmark/peaks.json: add it with its source")
    return table[device_kind]


# ---- the device ------------------------------------------------------------
def require_tpu(chips):
    """The devices, or a non-zero exit before any work: no CPU mode."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); jax found "
            f"platform={devices[0].platform} kind={devices[0].device_kind} "
            f"count={len(devices)}")
    return devices


MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_MOSAIC_CALL = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+?)(?:\.\d+)? = [^\n]*" + MOSAIC_TARGET, re.M)


def mosaic_calls(hlo_text):
    """Pallas (Mosaic) custom calls in a compiled step's HLO."""
    return hlo_text.count(MOSAIC_TARGET)


def mosaic_kernels(hlo_text):
    """The kernel names of a compiled step's Mosaic calls: each call's
    instruction name up to its ``.N``, which is the ``name=`` the program
    gave the ``pallas_call`` (``scope_reduce.kernel_of`` reads the same name
    from a trace)."""
    return set(_MOSAIC_CALL.findall(hlo_text))


def missing_kernels(hlo_text, prefixes):
    """Of the kernel families a cell file lists under ``kernels`` (prefixes
    of the program's ``pallas_call`` names: ``flash_``, ``softmax_ce_``),
    those of which the compiled step holds no Mosaic call."""
    held = mosaic_kernels(hlo_text)
    return [p for p in prefixes if not any(k.startswith(p) for k in held)]


def step_bytes(compiled):
    """Bytes one device needs to run the compiled step: its arguments and
    its temporaries, by the executable's own accounting."""
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


def device_report(compiled, trace):
    """``device`` of the result line. The runtime's ``peak_bytes_in_use``
    tracks live arrays and not a step's temporaries (PERF.md section 7), so
    the peak on the fullest chip is the larger of that reading and what the
    compiled step declares."""
    import jax

    devices = jax.devices()
    live = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": int(max(live, step_bytes(compiled))),
           "peak_bytes_in_use": int(live)}
    if trace is not None:
        busy_s, window_s, _ = trace_reduce.busy_and_idle(trace.ops)
        out.update(busy_s=busy_s, window_s=window_s)
    return out


# ---- what a loop hands to the readers --------------------------------------
@dataclasses.dataclass
class Trace:
    ops: dict       # device plane -> [(op name, start_ns, end_ns)]
    host: list      # the loop's own spans on the same clock
    steps: int


@dataclasses.dataclass
class Window:
    cell: dict
    family: Any
    compiled: Any
    compiled_text: str
    spans: list         # (name, start_s, end_s) of the timed window
    stamps: list        # host time of every step completion in the window
    steps: int
    seconds: float
    first_step_s: float
    compiles_in_window: int
    trace: Trace = None

    def mean_span_ms(self, name):
        spans = [e - s for n, s, e in self.spans if n == name]
        return 1e3 * sum(spans) / self.steps if spans else None


def traced_steps(loop, n, say):
    """Profile ``n`` more steady steps of the same loop; the loop's three
    spans go into the profiler's own trace, on the device's clock."""
    import jax

    directory = tempfile.mkdtemp(prefix="bench_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    loop.drain()
    try:
        jax.profiler.start_trace(directory, profiler_options=options)
        loop.annotate = jax.profiler.TraceAnnotation
        t = time.perf_counter()
        for _ in range(n):
            loop.one_step()
        loop.drain()
        elapsed = time.perf_counter() - t
        loop.annotate = contextlib.nullcontext
        jax.profiler.stop_trace()
        trace = trace_reduce.read_xplane(directory)
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    ops = trace_reduce.device_ops(trace)
    if not ops:
        raise RuntimeError(f"the trace holds no device ops; planes: "
                           f"{sorted(trace)}")
    host = [(name[len("bench."):], s, e)
            for events in trace.get(trace_reduce.HOST_PLANE, {}).values()
            for name, s, e in events if name.startswith("bench.")]
    say(f"[trace] {n} steps in {elapsed:.3f}s, "
        f"{sum(map(len, ops.values()))} device ops on {len(ops)} device(s), "
        f"{len(host)} host spans")
    return Trace(ops=ops, host=host, steps=n)


def breakdown(trace, top=10):
    """The device ops that took most time (first device) and the idle time
    by what the host was doing."""
    first = next(iter(trace.ops.values()))
    lo, hi = trace_reduce.window_of(trace.ops)
    busy = trace_reduce.union(trace_reduce.spans_of(first))
    idle = trace_reduce.gaps(busy, lo, hi)
    return {"device_ops": [[n, t] for n, t in
                           trace_reduce.time_by_name(first)[:top]],
            "idle_gaps": [[n, t] for n, t in
                          trace_reduce.label_gaps(idle, trace.host)[:top]]}
