"""SPMD observability: collective accounting, sharding introspection,
per-device telemetry.

The reference's ParallelExecutor ran its NCCL all-reduces blind — the
only comm visibility was NCCL debug logs. Here the collectives are
*compiled into* the executable by GSPMD, which means the executable's
own HLO text is the ground truth for what the step moves over ICI:
every all-reduce / all-gather / reduce-scatter / collective-permute /
all-to-all appears with its payload shape and replica groups. This
module turns that text into numbers:

- ``parse_hlo_collectives`` / ``collective_profile`` — per-executable
  **CollectiveProfile**: op counts and byte volumes per collective
  kind, attributed to mesh axes by matching each op's replica groups
  against the device mesh (EQuARX, arXiv:2506.17615, treats exactly
  this accounting as the lever for distributed-XLA speedups).
- ``comm_roofline`` — compose collective bytes with the chip's ICI
  bandwidth (env ``PADDLE_TPU_ICI_BW`` or the per-chip table) and the
  step's FLOPs vs peak (``obs.mfu``) into a compute-vs-comm breakdown —
  the comm/compute-overlap attribution the MLPerf TPU-pod scaling
  study (arXiv:1909.09756) identifies as where scaling losses live.
- ``sharding_report`` — **ShardingReport** for one Executor cache
  entry: feed / persistable / fetch → mesh axes + per-device byte
  footprint (what the fleet layer's per-rank log spew never totaled).
- ``device_memory_stats`` / ``update_device_gauges`` — live per-device
  HBM gauges from ``device.memory_stats()`` where the backend exposes
  them (TPU does; host CPU reports None), including the high-water
  device; samples land in ``obs.metrics`` gauges and — when span
  tracing is on — per-device pid lanes in the Chrome trace.

Byte convention: an op's ``bytes`` is the byte size of its HLO result
shape (tuple results of sync multi-operand ops summed; async ``-start``
tuples pick the result element) — the payload each participant holds
after the op. ``wire_bytes`` applies the standard ring-algorithm
factors to the FULL payload moved through the group (all-reduce
``2(n-1)/n``, all-gather/all-to-all ``(n-1)/n`` — their result IS the
full payload; reduce-scatter ``(n-1)/n`` of ``result x group_size``,
since its result is one shard; collective-permute ``1``) so the
roofline reflects actual link traffic.

Everything here is off the step path: parsing runs inside the lazy
``obs.mfu.entry_analysis`` (daemon-thread, cached per cache entry), and
the journal hooks follow the ``if ACTIVE is None`` zero-overhead
contract.
"""
from __future__ import annotations

import os
import re

import numpy as np

__all__ = [
    "COLLECTIVE_KINDS", "parse_hlo_collectives", "collective_profile",
    "merge_profiles", "ICI_BW_BY_KIND", "ici_bandwidth", "comm_roofline",
    "sharding_report", "sharding_summary", "device_memory_stats",
    "update_device_gauges", "mesh_info", "wire_factor",
]

# canonical collective kinds (HLO op mnemonics); async forms appear as
# <kind>-start / <kind>-done pairs — -start carries the payload, -done
# is bookkeeping and must not double count
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "s4": 1, "u4": 1,
}

# one HLO instruction: "%name = TYPE opkind(", where TYPE is either a
# single "f32[128,64]{1,0}" shape or a tuple "(f32[..], f32[..])"
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|\w+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")

# sub-byte/byte integer payloads = a quantized exchange is on the wire
# (dist.gradcomm int8 all-reduce, int4 weight gathers); bf16/f16 are
# reduced-precision but not "quantized" in this accounting
_QUANT_DTYPES = frozenset(("s8", "u8", "s4", "u4"))
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[^}]*(?:\},\{[^}]*)*\}\}|"
                        r"\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)")
_PAIRS_RE = re.compile(r"source_target_pairs=\{([^}]*(?:\},\{[^}]*)*)\}")

# ring-algorithm wire-traffic factors per participant, as a multiple of
# the op's RESULT bytes (n = group size). all-gather/all-to-all results
# are the full gathered payload; a reduce-scatter's result is one shard
# of it, so the (n-1)/n factor applies to result*n = (n-1) — without
# that, a ZeRO/FSDP-style reduce-scatter-dominated step would read ~n x
# too cheap on the roofline
_WIRE_FACTOR = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n if n > 1 else 0.0,
    "all-gather": lambda n: (n - 1) / n if n > 1 else 0.0,
    "reduce-scatter": lambda n: float(n - 1),
    "all-to-all": lambda n: (n - 1) / n if n > 1 else 0.0,
    "collective-permute": lambda n: 1.0,
}


def wire_factor(kind, group_size):
    """Public read of the ring-algorithm wire-traffic factor for one
    collective kind at one group size — the SAME convention
    ``collective_profile`` measures by, so a predictor (fleet.planner)
    that prices with this factor is directly comparable to the
    HLO-measured profile."""
    return _WIRE_FACTOR[kind](int(group_size))


def _shape_bytes(type_str, kind=None, is_async=False):
    """Byte size of one HLO result type ("f32[4,4]{1,0}" or a tuple
    "(f32[4], bf16[8,2])"). Sync tuple results (multi-operand
    all-to-all) sum — together they are the payload. Async ``-start``
    results are (operand, result[, context...]) bundles: summing would
    double-count, so pick the element playing the result role — the
    largest (all-gather grows, all-reduce/permute are same-shape, the
    u32 context scalars lose), except reduce-scatter, whose result is
    the SMALLEST non-scalar element. Unknown dtypes count 4 bytes."""
    sizes = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        sizes.append(n * _HLO_DTYPE_BYTES.get(dt, 4))
    if not sizes:
        return 0
    if is_async and len(sizes) > 1:
        if kind == "reduce-scatter":
            tensors = [s for s in sizes if s > 8] or sizes
            return min(tensors)
        return max(sizes)
    return sum(sizes)


def _is_quantized(type_str):
    """Whether the op's tensor payload is integer-quantized (s8/u8/
    s4/u4): every non-scalar element of the result type is a quantized
    dtype. Scalar elements (async context tokens) are ignored; an op
    with no non-scalar payload is not quantized."""
    dts = [dt for dt, dims in _SHAPE_RE.findall(type_str) if dims]
    return bool(dts) and all(dt in _QUANT_DTYPES for dt in dts)


def _iota_groups(spec):
    """Expand the iota replica-group form "[G,S]<=[d0,d1,..]T(p..)" into
    explicit groups: reshape iota(prod(dims)) by dims, transpose by the
    optional permutation, then reshape to (G, S)."""
    m = re.match(r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", spec)
    if m is None:
        raise ValueError(f"unparseable replica_groups {spec!r}")
    gshape = [int(x) for x in m.group(1).split(",")]
    dims = [int(x) for x in m.group(2).split(",")]
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if m.group(3):
        ids = ids.transpose([int(x) for x in m.group(3).split(",")])
    return ids.reshape(gshape).tolist()


def _parse_groups(attr):
    """Explicit "{{0,1},{2,3}}" or iota "[2,4]<=[8]T(..)" replica groups
    -> list of lists of device ids."""
    if attr.startswith("{"):
        return [[int(x) for x in g.split(",") if x.strip()]
                for g in re.findall(r"\{([0-9,\s]*)\}", attr[1:-1])]
    return _iota_groups(attr)


def mesh_info(mesh):
    """Normalize a ``jax.sharding.Mesh`` (or an (axes, ids) pair already
    in this form) to ``(axes_dict, device_id_array)`` — the inputs the
    replica-group attribution needs. Returns (None, None) for None."""
    if mesh is None:
        return None, None
    if isinstance(mesh, tuple) and len(mesh) == 2:
        axes, ids = mesh
        return dict(axes), (None if ids is None else np.asarray(ids))
    axes = dict(mesh.shape)
    ids = np.vectorize(lambda d: int(d.id))(mesh.devices)
    return axes, ids


def _axis_groups(axes, ids, subset):
    """Expected replica groups for a collective over the mesh-axis
    ``subset``: devices sharing every coordinate OUTSIDE the subset form
    one group."""
    names = list(axes)
    keep = [i for i, n in enumerate(names) if n not in subset]
    move = [i for i, n in enumerate(names) if n in subset]
    perm = keep + move
    arr = np.transpose(ids.reshape([axes[n] for n in names]), perm)
    gsz = int(np.prod([axes[names[i]] for i in move])) if move else 1
    return arr.reshape(-1, gsz)


def _attribute_axes(groups, axes, ids):
    """Match one op's replica groups against every mesh-axis subset;
    returns the '+'-joined axis names ('data', 'model+sp', ...) or None
    when the groups match no axis combination (or no mesh is known)."""
    if axes is None or ids is None or not groups:
        return None
    want = frozenset(frozenset(g) for g in groups)
    names = list(axes)
    # smallest subsets first so a 1-axis collective is named by its axis
    for size in range(1, len(names) + 1):
        from itertools import combinations

        for subset in combinations(names, size):
            expect = _axis_groups(axes, ids, set(subset))
            if frozenset(frozenset(g.tolist()) for g in expect) == want:
                return "+".join(subset)
    return None


def parse_hlo_collectives(hlo_text, mesh=None):
    """Scan optimized HLO text for collective ops. Returns a list of
    ``{"kind", "bytes", "group_size", "n_groups", "axes"}`` dicts — one
    per instruction (async -start/-done pairs counted once, on -start).

    ``mesh`` (a jax Mesh, or an ``(axes_dict, device_id_array)`` pair)
    enables mesh-axis attribution via replica groups; without it
    ``axes`` is None.
    """
    axes, ids = mesh_info(mesh)
    ndev = int(np.prod(list(axes.values()))) if axes else None
    ops = []
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m is None:
            continue
        type_str, kind, async_part = m.group(1), m.group(2), m.group(3)
        if async_part == "-done":
            continue  # payload already counted on the -start
        groups = None
        gm = _GROUPS_RE.search(line)
        if gm is not None:
            try:
                groups = _parse_groups(gm.group(1))
            except ValueError:
                groups = None
        elif kind == "collective-permute":
            pm = _PAIRS_RE.search(line)
            if pm is not None:
                # pairs aren't groups; the permute ring spans the set of
                # participating devices
                devs = sorted({int(x) for x in
                               re.findall(r"\d+", pm.group(1))})
                groups = [devs] if devs else None
        if groups and not groups[0]:
            groups = None
        gsize = len(groups[0]) if groups else (ndev or 1)
        ops.append({
            "kind": kind,
            "bytes": _shape_bytes(type_str, kind=kind,
                                  is_async=async_part == "-start"),
            "group_size": gsize,
            "n_groups": len(groups) if groups else None,
            "axes": _attribute_axes(groups, axes, ids),
            "quant": _is_quantized(type_str),
        })
    return ops


def collective_profile(hlo_text, mesh=None):
    """The **CollectiveProfile** of one compiled executable: per-kind
    op counts and byte volumes, total/wire bytes, and a per-mesh-axis
    byte breakdown. All byte figures are per execution of the
    executable (one training step for an Executor entry)."""
    ops = parse_hlo_collectives(hlo_text, mesh=mesh)
    counts, bytes_, by_axis = {}, {}, {}
    wire = quant = quant_wire = 0.0
    for op in ops:
        k = op["kind"]
        counts[k] = counts.get(k, 0) + 1
        bytes_[k] = bytes_.get(k, 0) + op["bytes"]
        w = op["bytes"] * _WIRE_FACTOR[k](op["group_size"])
        wire += w
        if op.get("quant"):
            quant += op["bytes"]
            quant_wire += w
        ax = op["axes"] or "?"
        by_axis[ax] = by_axis.get(ax, 0) + op["bytes"]
    return {
        "n_ops": len(ops),
        "counts": counts,
        "bytes": bytes_,
        "total_bytes": sum(bytes_.values()),
        "wire_bytes": int(round(wire)),
        # the integer-payload (s8/u8/s4/u4) share of the above — the
        # dist.gradcomm int8 exchange's wire footprint, rendered as the
        # shard_report roofline's "quantized wire bytes" column
        "quant_bytes": int(round(quant)),
        "quant_wire_bytes": int(round(quant_wire)),
        "by_axis": by_axis,
    }


def merge_profiles(profiles):
    """Sum several CollectiveProfiles (e.g. one per microbatch phase)
    into one; Nones are skipped. Returns None when nothing to merge."""
    profiles = [p for p in profiles if p]
    if not profiles:
        return None
    out = {"n_ops": 0, "counts": {}, "bytes": {}, "total_bytes": 0,
           "wire_bytes": 0, "quant_bytes": 0, "quant_wire_bytes": 0,
           "by_axis": {}}
    for p in profiles:
        out["n_ops"] += p.get("n_ops", 0)
        out["total_bytes"] += p.get("total_bytes", 0)
        out["wire_bytes"] += p.get("wire_bytes", 0)
        out["quant_bytes"] += p.get("quant_bytes", 0)
        out["quant_wire_bytes"] += p.get("quant_wire_bytes", 0)
        for field in ("counts", "bytes", "by_axis"):
            for k, v in (p.get(field) or {}).items():
                out[field][k] = out[field].get(k, 0) + v
    return out


# -- comm roofline -----------------------------------------------------------

# per-chip aggregate ICI bandwidth, bytes/s (published per-chip interconnect
# figures: v4 2400 Gb/s, v5e 1600 Gb/s, v5p 4800 Gb/s, v6e 3584 Gb/s)
ICI_BW_BY_KIND = {
    "TPU v4": 2400e9 / 8,
    "TPU v5e": 1600e9 / 8,
    "TPU v5 lite": 1600e9 / 8,
    "TPU v5p": 4800e9 / 8,
    "TPU v6e": 3584e9 / 8,
}


def ici_bandwidth():
    """ICI bytes/s for the roofline: env ``PADDLE_TPU_ICI_BW`` wins,
    else the per-chip table keyed on the backend's device kind. ``None``
    when nothing is known (host CPU)."""
    env = os.environ.get("PADDLE_TPU_ICI_BW", "")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    import jax

    kind = jax.devices()[0].device_kind
    for k, v in ICI_BW_BY_KIND.items():
        if k.lower() in kind.lower():
            return v
    return None


def comm_roofline(profile, flops=None, peak=None, bw=None):
    """Compute-vs-comm step breakdown from a CollectiveProfile and the
    step's FLOPs: ideal comm time (wire bytes / ICI bandwidth), ideal
    compute time (FLOPs / peak), the comm share of the step under
    perfect overlap-free execution, and which resource bounds the step.
    Missing inputs (no bandwidth known, no FLOPs yet) yield None fields
    rather than made-up numbers."""
    from .mfu import peak_flops

    bw = bw if bw is not None else ici_bandwidth()
    peak = peak if peak is not None else peak_flops()
    wire = (profile or {}).get("wire_bytes", 0)
    comm_s = (wire / bw) if (bw and wire) else (0.0 if not wire else None)
    compute_s = (flops / peak) if (flops and peak) else None
    out = {"comm_bytes": (profile or {}).get("total_bytes", 0),
           "wire_bytes": wire, "ici_bw": bw,
           "comm_time_s": comm_s, "compute_time_s": compute_s,
           "comm_share": None, "bound": None}
    if comm_s is not None and compute_s is not None:
        total = comm_s + compute_s
        out["comm_share"] = comm_s / total if total > 0 else 0.0
        out["bound"] = "comm" if comm_s > compute_s else "compute"
    return out


# -- sharding introspection --------------------------------------------------


def _spec_str(sharding):
    """Render a NamedSharding's PartitionSpec compactly; replicated
    placements render as 'replicated'."""
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return "replicated"
    parts = [("+".join(p) if isinstance(p, tuple) else str(p))
             for p in spec if p is not None]
    return ",".join(parts) if parts else "replicated"


def _devices_spanned(sharding, axes):
    """How many devices one shard's bytes divide across."""
    spec = getattr(sharding, "spec", None)
    if spec is None or axes is None:
        return 1
    n = 1
    for p in spec:
        for name in (p if isinstance(p, tuple) else (p,)):
            if name is not None:
                n *= axes.get(name, 1)
    return n


def _struct_bytes(struct):
    n = 1
    for s in struct.shape:
        n *= int(s)
    return n * np.dtype(struct.dtype).itemsize


def sharding_report(compiled):
    """The **ShardingReport** of one Executor cache entry: mesh axes,
    and per variable (feed / updated-persistable / frozen-persistable /
    fetch) the partition spec, total bytes, and per-device byte
    footprint. Built from metadata captured at ``_build`` — no device
    transfer, no XLA work."""
    axes = getattr(compiled, "mesh_axes", None)
    feed_sh = getattr(compiled, "feed_shardings", None)
    structs = getattr(compiled, "arg_structs", None)
    rows = []

    def row(name, role, struct, sharding):
        total = _struct_bytes(struct) if struct is not None else None
        span = _devices_spanned(sharding, axes)
        rows.append({
            "name": name, "role": role,
            "shape": (list(struct.shape) if struct is not None else None),
            "dtype": (str(np.dtype(struct.dtype))
                      if struct is not None else None),
            "spec": _spec_str(sharding) if sharding is not None
            else "replicated",
            "bytes": total,
            "per_device_bytes": (total // span if total is not None
                                 else None),
        })

    feed_structs = structs[0] if structs else []
    for i, name in enumerate(getattr(compiled, "feed_names", ()) or ()):
        st = feed_structs[i] if i < len(feed_structs) else None
        sh = feed_sh[i] if feed_sh is not None and i < len(feed_sh) else None
        row(name, "feed", st, sh)
    upd_structs = structs[1] if structs else []
    for i, name in enumerate(getattr(compiled, "updated", ()) or ()):
        row(name, "persistable:updated",
            upd_structs[i] if i < len(upd_structs) else None, None)
    frz_structs = structs[2] if structs else []
    for i, name in enumerate(getattr(compiled, "frozen", ()) or ()):
        row(name, "persistable:frozen",
            frz_structs[i] if i < len(frz_structs) else None, None)
    for name in getattr(compiled, "fetch_names", ()) or ():
        # fetches replicate (executor out_shardings); shapes are only
        # known post-lowering, so bytes stay None here
        rows.append({"name": name, "role": "fetch", "shape": None,
                     "dtype": None, "spec": "replicated", "bytes": None,
                     "per_device_bytes": None})
    known = [r["bytes"] for r in rows if r["bytes"] is not None]
    per_dev = [r["per_device_bytes"] for r in rows
               if r["per_device_bytes"] is not None]
    return {
        "program_uid": getattr(compiled, "program_uid", None),
        "program_version": getattr(compiled, "program_version", None),
        "mesh": axes,
        "vars": rows,
        "total_bytes": sum(known) if known else None,
        "per_device_bytes": sum(per_dev) if per_dev else None,
    }


def sharding_summary(compiled, max_vars=16):
    """Bounded summary of ``sharding_report`` for the journal's
    per-compile ``sharding`` event: mesh axes, aggregate footprints, and
    the ``max_vars`` largest variables (by bytes) with their specs."""
    rep = sharding_report(compiled)
    rows = sorted(rep["vars"], key=lambda r: -(r["bytes"] or 0))
    return {
        "program_uid": rep["program_uid"],
        "program_version": rep["program_version"],
        "mesh": rep["mesh"],
        "n_vars": len(rep["vars"]),
        "total_bytes": rep["total_bytes"],
        "per_device_bytes": rep["per_device_bytes"],
        "vars": [{"name": r["name"], "role": r["role"], "spec": r["spec"],
                  "bytes": r["bytes"],
                  "per_device_bytes": r["per_device_bytes"]}
                 for r in rows[:max_vars]],
    }


# -- per-device telemetry ----------------------------------------------------


def device_memory_stats():
    """Per-device memory stats where the backend exposes them. Returns
    a list of ``{"id", "kind", "bytes_in_use", "peak_bytes_in_use",
    "bytes_limit"}`` (missing fields None — host CPU reports no stats at
    all, which yields all-None entries)."""
    import jax

    devs = jax.local_devices()
    out = []
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        out.append({
            "id": int(d.id), "kind": d.device_kind,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    return out


def update_device_gauges():
    """Sample per-device memory into ``obs.metrics`` gauges
    (``device.<id>.bytes_in_use`` / ``.peak_bytes_in_use``) and — when
    span tracing is enabled — per-device counter lanes in the Chrome
    trace. Returns ``(stats, high_water)`` where ``high_water`` is the
    device dict with the largest ``bytes_in_use`` (None when the
    backend reports nothing)."""
    from . import metrics as _metrics
    from . import trace as _trace

    stats = device_memory_stats()
    high = None
    for d in stats:
        if d["bytes_in_use"] is None:
            continue
        _metrics.gauge(f"device.{d['id']}.bytes_in_use").set(
            d["bytes_in_use"])
        if d["peak_bytes_in_use"] is not None:
            _metrics.gauge(f"device.{d['id']}.peak_bytes_in_use").set(
                d["peak_bytes_in_use"])
        if _trace.tracing_enabled():
            _trace.device_counter(d["id"], "bytes_in_use",
                                  d["bytes_in_use"],
                                  label=f"device {d['id']} ({d['kind']})")
        if high is None or d["bytes_in_use"] > high["bytes_in_use"]:
            high = d
    return stats, high
