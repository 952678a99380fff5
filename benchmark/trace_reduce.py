"""From a profiler trace to numbers: pure functions over intervals, so that
every PR computes busy time, idle share, kernel share and exposed collective
time the same way. Checked on ``tests``' recorded fixture.

A trace here is ``{plane: {line: [(name, start_ns, end_ns), ...]}}``, which
``read_xplane`` makes from the ``.xplane.pb`` that ``jax.profiler`` writes.
On a TPU each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds
one event per executed HLO instruction, one at a time (asynchronous copies
run beside them on ``Async XLA Ops`` and are not counted as busy time); the
host's threads, with the loop's own annotations, are lines of ``/host:CPU`` on
the same clock.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(-start|-done)?(\.\d+)?$")
MOSAIC = "custom-call:tpu_custom_call"


def label(event_name):
    """``"<instruction> <opcode>"`` from the whole HLO instruction that the
    TPU's trace gives as an op's name (``%fusion.3 = bf16[..] fusion(..),
    kind=kLoop``); a Mosaic kernel's opcode reads
    ``custom-call:tpu_custom_call``. Any other name is returned as it is."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name
    rest = rest.lstrip()
    if rest.startswith("("):   # a tuple type: skip to its closing bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.partition(" ")[2]
    op = rest.partition("(")[0].strip()
    if op == "custom-call" and 'custom_call_target="tpu_custom_call"' in rest:
        op = MOSAIC
    return f"{head.lstrip('%')} {op}"


def read_xplane(directory):
    """The newest ``*.xplane.pb`` under ``directory`` as a trace."""
    import jax

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    trace = {}
    for plane in data.planes:
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (label(e.name), int(e.start_ns),
                 int(e.start_ns + e.duration_ns)) for e in line.events)
    return trace


def device_ops(trace):
    """{device plane: its XLA-op events}, for the planes that are chips."""
    return {name: lines[OPS_LINE] for name, lines in sorted(trace.items())
            if DEVICE_PLANE.match(name) and lines.get(OPS_LINE)}


def union(intervals):
    """Disjoint sorted (start, end) covering the same points."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        elif end > start:
            out.append([start, end])
    return [tuple(x) for x in out]


def total(intervals):
    return sum(end - start for start, end in intervals)


def subtract(a, b):
    """The part of union(a) that union(b) does not cover."""
    out, b = [], union(b)
    for start, end in union(a):
        for bs, be in b:
            if be <= start or bs >= end:
                continue
            if bs > start:
                out.append((start, bs))
            start = max(start, be)
            if start >= end:
                break
        if start < end:
            out.append((start, end))
    return out


def gaps(busy, lo, hi):
    """The idle stretches of [lo, hi] that the disjoint ``busy`` leaves."""
    return subtract([(lo, hi)], busy)


def spans_of(events):
    return [(s, e) for _, s, e in events]


def window_of(ops_by_device):
    """[first op's start, last op's end] over all devices."""
    starts = [min(s for _, s, _ in ev) for ev in ops_by_device.values()]
    ends = [max(e for _, _, e in ev) for ev in ops_by_device.values()]
    return min(starts), max(ends)


def busy_and_idle(ops_by_device):
    """(mean busy seconds over devices, window seconds, worst device's idle
    share in %) of the traced window."""
    lo, hi = window_of(ops_by_device)
    busy = [total(union(spans_of(ev))) for ev in ops_by_device.values()]
    window = hi - lo
    return (sum(busy) / len(busy) / 1e9, window / 1e9,
            100.0 * (1.0 - min(busy) / window))


def is_collective(name):
    """By the instruction's name or by its opcode."""
    return any(COLLECTIVE.match(part) for part in name.split(" "))


def is_mosaic(name):
    return name.endswith(" " + MOSAIC)


def exposed_collective_ns(events):
    """Time in which a collective op runs on this device and no other op
    does."""
    coll = [(s, e) for n, s, e in events if is_collective(n)]
    rest = [(s, e) for n, s, e in events if not is_collective(n)]
    return total(subtract(coll, rest))


def time_by_name(events):
    """[(name, seconds)] of summed op time, largest first."""
    by = {}
    for name, s, e in events:
        by[name] = by.get(name, 0) + (e - s)
    return sorted(((n, t / 1e9) for n, t in by.items()),
                  key=lambda x: -x[1])


def share_of(events, chosen):
    """% of the device-busy time of ``events`` inside the ops whose name
    ``chosen`` accepts."""
    busy = total(union(spans_of(events)))
    mine = total(union([(s, e) for n, s, e in events if chosen(n)]))
    return 100.0 * mine / busy if busy else 0.0


def label_gaps(idle, host_spans):
    """[(label, seconds)] of idle time by the host span (name, start, end)
    that covers most of each gap; ``outside`` where none does."""
    by = {}
    for gs, ge in idle:
        best, cover = "outside", 0
        for name, s, e in host_spans:
            c = min(e, ge) - max(s, gs)
            if c > cover:
                best, cover = name, c
        by[best] = by.get(best, 0) + (ge - gs)
    return sorted(((n, t / 1e9) for n, t in by.items()), key=lambda x: -x[1])
