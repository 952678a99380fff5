#!/usr/bin/env python
"""perf_gate: CPU-runnable structure checks over compiled HLO.

Counts that a program's compiled HLO shows on any backend, kept as
checks the tier-1 tests load. They are structure, not speed: no count
here is a time, a rate or a utilization, and none stands in for one
(speed is ``benchmark/`` on the chip and the ledger; PERF.md) —

- **donation**: how many input buffers the executable aliases to
  outputs (``input_output_alias``) — a donated persistable updates
  in-place in HBM; a regression here doubles parameter memory traffic.
- **op shape**: per-kind instruction counts from the optimized HLO
  (``fusion``, ``while``, ``dot``, collectives, ...) — a fused
  multi-step entry must contain exactly one ``while`` loop (the scan),
  not K unrolled bodies.
- **collective bytes**: per-step communication volume via
  ``obs.spmd.collective_profile`` — the PR-5 comm accounting, now
  assertable as a ceiling.
- **compiled-call counts**: executor compiles (jit-cache misses) and
  dispatches — the fused ``run_steps`` path must compile once and
  dispatch once per K-step window where the sequential path dispatches
  K times.

Usage:
    python tools/perf_gate.py --self-test   # canned-HLO fixtures with
        # hand-computed donation/fusion counts + a live 8-fake-device
        # scan-vs-loop compiled-call-count check
    python tools/perf_gate.py --entry-report   # live MLP demo: build,
        # run fused, print the invariant report

In-process (the way tests/test_perf_gates.py uses it):
    from tools.perf_gate import (entry_hlo, donation_stats, op_counts,
                                 check_entry, executor_call_counts)
    failures = check_entry(compiled, min_donated=2, max_while=1)

Wired into tier-1 via tests/test_tooling.py (lint/chaos/obs/run/shard
_report pattern).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _ensure_fake_devices(n=8):
    """Standalone runs need the fake-device CPU platform configured
    BEFORE jax initializes; under pytest the conftest already did."""
    if "jax" not in sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
    import jax

    return len(jax.devices())


# -- HLO parsing --------------------------------------------------------------

# one alias entry inside the input_output_alias header attribute:
#   {1}: (1, {}, may-alias)   /   {0, 2}: (3, {0})
_ALIAS_ENTRY_RE = re.compile(
    r"\{([0-9,\s]*)\}:\s*\(([0-9]+),\s*\{[0-9,\s]*\}"
    r"(?:,\s*(may-alias|must-alias))?\)")

# one HLO instruction: "%name = TYPE opkind(" where TYPE is a shape or a
# tuple; group(2) is the op mnemonic (fusion, while, dot, all-reduce...)
_INSTR_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"([a-z][a-z0-9-]*)\(")

# a NAMED instruction inside a computation body — the schedule-order
# parse for overlap checks needs the %name to pair -start with -done
_NAMED_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
    r"(\([^)]*\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"([a-z][a-z0-9-]*)\((.*)$")

# ops that represent real device compute for overlap purposes (an
# all-reduce separated from its -done only by bitcasts/copies hides
# nothing)
COMPUTE_OPS = frozenset(("fusion", "dot", "convolution", "reduce",
                         "while", "scatter", "sort"))

_SYNC_COLLECTIVES = frozenset(("all-reduce", "all-gather",
                               "reduce-scatter", "all-to-all",
                               "collective-permute"))


def _alias_attr(hlo_text):
    """The raw ``input_output_alias={...}`` attribute body of the entry
    module header, or None. Brace-balanced scan: the body nests braces
    ({output index} / {param path})."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return None
    i = start + len("input_output_alias={")
    depth = 1
    while i < len(hlo_text) and depth:
        if hlo_text[i] == "{":
            depth += 1
        elif hlo_text[i] == "}":
            depth -= 1
        i += 1
    return hlo_text[start + len("input_output_alias={"):i - 1]


def donation_stats(hlo_text):
    """Donated-buffer accounting from the module header's
    ``input_output_alias`` attribute: ``count`` aliased (donated)
    buffers and the ``aliases`` list of
    ``(output_index, param_number, kind)``. An executable that donates
    nothing returns count 0 (and that IS a meaningful gate failure for
    a training step: its parameter updates round-trip HBM)."""
    attr = _alias_attr(hlo_text)
    if attr is None:
        return {"count": 0, "aliases": []}
    aliases = [
        (tuple(int(x) for x in out.split(",") if x.strip()), int(param),
         kind or "must-alias")
        for out, param, kind in _ALIAS_ENTRY_RE.findall(attr)]
    return {"count": len(aliases), "aliases": aliases}


def op_counts(hlo_text, kinds=None):
    """Instruction counts per op mnemonic over the optimized HLO text
    (entry + nested computations). ``kinds`` filters to the named ops,
    reporting explicit zeros for absent ones — a gate asserting
    ``while == 1`` needs the 0, not a missing key."""
    counts = {}
    for m in _INSTR_RE.finditer(hlo_text):
        k = m.group(2)
        counts[k] = counts.get(k, 0) + 1
    if kinds is None:
        return counts
    return {k: counts.get(k, 0) for k in kinds}


def schedule_ops(hlo_text):
    """The ENTRY computation's instruction sequence as ordered
    ``(name, kind, args)`` tuples. Optimized HLO is emitted
    ``is_scheduled=true``, so textual order IS the execution schedule —
    the property the overlap gate reasons over. Falls back to the whole
    text when no ENTRY block is present (canned single-computation
    fixtures)."""
    lines = hlo_text.splitlines()
    start = next((i for i, ln in enumerate(lines)
                  if ln.lstrip().startswith("ENTRY ")), None)
    if start is not None:
        block = []
        for ln in lines[start + 1:]:
            if ln.strip() == "}":
                break
            block.append(ln)
        lines = block
    out = []
    for ln in lines:
        m = _NAMED_INSTR_RE.match(ln)
        if m is not None:
            out.append((m.group(1), m.group(3), m.group(4)))
    return out


def overlap_stats(hlo_text):
    """Comm/compute overlap structure of one scheduled HLO module —
    the CPU-runnable proof that a gradient exchange can hide behind
    compute (dist.gradcomm's reverse-topological bucket ordering):

    - ``async_pairs`` / ``async_overlapped``: ``<kind>-start`` /
      ``-done`` collective pairs, and how many have at least one real
      compute op (COMPUTE_OPS) scheduled BETWEEN start and done — the
      async backend's explicit overlap window. (XLA's CPU backend
      lowers collectives synchronously, so live CPU entries usually
      show 0 pairs; the canned fixtures pin the parse.)
    - ``interleaved``: collectives (sync or -start) with at least one
      compute op scheduled AFTER them — the overlap-enabling placement
      a sync schedule still proves: the exchange is not pushed to the
      tail where nothing could ever hide it.
    - ``collectives`` / ``compute_ops``: totals for context.
    """
    sched = schedule_ops(hlo_text)
    compute_at = [i for i, (_, kind, _) in enumerate(sched)
                  if kind in COMPUTE_OPS]
    colls = []   # (index, name, kind, is_start)
    for i, (name, kind, _) in enumerate(sched):
        if kind in _SYNC_COLLECTIVES:
            colls.append((i, name, kind, False))
        elif kind.endswith("-start") and \
                kind[:-6] in _SYNC_COLLECTIVES:
            colls.append((i, name, kind[:-6], True))
    pairs = overlapped = 0
    for i, name, kind, is_start in colls:
        if not is_start:
            continue
        # exact operand match: "%ar-start.1" must not bind to
        # "%ar-start.10"'s done
        name_re = re.compile("%" + re.escape(name) + r"(?![\w.\-])")
        done = next(
            (j for j, (_, k, args) in enumerate(sched[i + 1:], i + 1)
             if k == kind + "-done" and name_re.search(args)), None)
        if done is None:
            continue
        pairs += 1
        if any(i < c < done for c in compute_at):
            overlapped += 1
    last_compute = compute_at[-1] if compute_at else -1
    interleaved = sum(1 for i, _, _, _ in colls if i < last_compute)
    return {"collectives": len(colls), "compute_ops": len(compute_at),
            "async_pairs": pairs, "async_overlapped": overlapped,
            "interleaved": interleaved}


def entry_hlo(compiled):
    """Optimized HLO text of one Executor cache entry, lowered from the
    arg structs captured at build time. BLOCKING (pays one XLA compile)
    on first call per entry; cached on the entry thereafter. None when
    lowering fails."""
    cached = getattr(compiled, "_perf_gate_hlo", None)
    if cached is not None:
        return cached
    structs = getattr(compiled, "arg_structs", None)
    if structs is None:
        return None
    try:
        # an AOT-hydrated entry (paddle_tpu.runtime.aot) holds the
        # jax.stages.Compiled directly — its as_text() IS the hydrated
        # executable's HLO, which is exactly what the donation gate
        # must verify survived the serialize round-trip
        text = compiled.fn.as_text() \
            if not hasattr(compiled.fn, "lower") \
            else compiled.fn.lower(*structs).compile().as_text()
    except Exception:
        return None
    compiled._perf_gate_hlo = text
    return text


# -- gates --------------------------------------------------------------------


def check_hlo(hlo_text, *, min_donated=None, max_donated=None,
              min_fusion=None, max_while=None, min_while=None,
              max_collective_bytes=None, mesh=None,
              max_all_reduce=None, min_async_overlapped=None,
              min_interleaved=None):
    """Check one HLO module against invariant bounds; returns the list
    of failure strings (empty = gate passes). Only the bounds given are
    checked — a gate file states exactly what it pins."""
    failures = []
    don = donation_stats(hlo_text)["count"]
    ops = op_counts(hlo_text)
    if min_donated is not None and don < min_donated:
        failures.append(f"donated buffers {don} < required {min_donated}")
    if max_donated is not None and don > max_donated:
        failures.append(f"donated buffers {don} > allowed {max_donated}")
    if min_fusion is not None and ops.get("fusion", 0) < min_fusion:
        failures.append(
            f"fusion ops {ops.get('fusion', 0)} < required {min_fusion}")
    n_while = ops.get("while", 0)
    if max_while is not None and n_while > max_while:
        failures.append(f"while loops {n_while} > allowed {max_while} "
                        "(scan body unrolled or duplicated?)")
    if min_while is not None and n_while < min_while:
        failures.append(f"while loops {n_while} < required {min_while} "
                        "(fused path did not lower to a scan)")
    if max_all_reduce is not None:
        n_ar = ops.get("all-reduce", 0) + ops.get("all-reduce-start", 0)
        if n_ar > max_all_reduce:
            failures.append(
                f"all-reduce ops {n_ar} > allowed {max_all_reduce} "
                "(bucketing regressed to per-parameter exchanges?)")
    if min_async_overlapped is not None or min_interleaved is not None:
        ov = overlap_stats(hlo_text)
        if min_async_overlapped is not None and \
                ov["async_overlapped"] < min_async_overlapped:
            failures.append(
                f"async-overlapped collectives {ov['async_overlapped']} "
                f"< required {min_async_overlapped} "
                f"(pairs={ov['async_pairs']}: comm not hidden behind "
                "compute)")
        if min_interleaved is not None and \
                ov["interleaved"] < min_interleaved:
            failures.append(
                f"interleaved collectives {ov['interleaved']} < required "
                f"{min_interleaved} (every exchange scheduled after the "
                "last compute op — nothing can hide it)")
    if max_collective_bytes is not None:
        from paddle_tpu.obs import spmd

        prof = spmd.collective_profile(hlo_text, mesh=mesh)
        if prof["total_bytes"] > max_collective_bytes:
            failures.append(
                f"collective bytes {prof['total_bytes']} > allowed "
                f"{max_collective_bytes} ({prof['counts']})")
    return failures


def check_entry(compiled, **bounds):
    """``check_hlo`` over one Executor cache entry (lowering it on
    demand); the entry's own mesh feeds collective attribution."""
    hlo = entry_hlo(compiled)
    if hlo is None:
        return ["entry HLO unavailable (lowering failed)"]
    axes = getattr(compiled, "mesh_axes", None)
    mesh = None
    if axes is not None:
        mesh = (axes, getattr(compiled, "mesh_device_ids", None))
    return check_hlo(hlo, mesh=mesh, **bounds)


def executor_call_counts(exe):
    """Compiled-call accounting for one Executor: ``compiles`` (jit
    cache misses — one per distinct executable built) and
    ``dispatches`` (compiled-fn invocations across run/run_steps). The
    fused-path gate: K steps through ``run_steps`` must show
    compiles == 1 and dispatches == 1 where the sequential loop shows
    dispatches == K."""
    stats = exe.cache_stats()
    return {"compiles": stats["misses"], "dispatches": exe.dispatches,
            "cache_hits": stats["hits"], "entries": stats["size"]}


def journal_gates(exe, **bounds):
    """Gate every compiled entry of ``exe`` and record the verdicts in
    the active run journal (one ``perf_gate`` event per entry, with the
    failure strings and the donation/while/call-count evidence), so
    ``tools/run_report.py --diff`` can surface a gate regression as a
    run regression. Inactive journal = pure check (no side effects).
    Returns the combined failure list."""
    from paddle_tpu.obs import journal as J

    all_failures = []
    calls = executor_call_counts(exe)
    for compiled in exe._cache.values():
        failures = check_entry(compiled, **bounds)
        all_failures += failures
        if J.ACTIVE is not None:
            hlo = entry_hlo(compiled)
            don = donation_stats(hlo)["count"] if hlo else None
            ops = op_counts(hlo, kinds=("while", "fusion")) if hlo else {}
            J.ACTIVE.event(
                "perf_gate", entry_uid=compiled.program_uid,
                steps_fused=getattr(compiled, "steps", None),
                donated=don, while_ops=ops.get("while"),
                fusion_ops=ops.get("fusion"),
                failures=failures, passed=not failures,
                compiles=calls["compiles"], dispatches=calls["dispatches"])
    return all_failures


# -- donation-coverage sweep --------------------------------------------------

# model-zoo legs for the coverage sweep: (name, builder) where builder
# returns (program, startup, loss) — small shapes so the sweep runs in
# tier-1 CI. Every leg trains through run_steps and must donate its
# persistable carry on the fused entry.


def _sweep_mlp():
    return _build_mlp(batch=8)


def _sweep_lenet():
    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.vision import LeNet

    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[8, 1, 28, 28])
        y = pt.static.data("y", [8], "int64")
        loss = F.cross_entropy(LeNet()(x), y)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return prog, startup, loss


def _sweep_ngram():
    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.nlp.word2vec import NGramLM

    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        w = pt.static.data("w", [8, 4], "int64")
        y = pt.static.data("y", [8], "int64")
        loss = F.cross_entropy(
            NGramLM(vocab_size=64, embed_dim=8, hidden=16)(w), y)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return prog, startup, loss


SWEEP_MODELS = (("mlp", _sweep_mlp), ("lenet", _sweep_lenet),
                ("ngram_lm", _sweep_ngram))


def _sweep_feed(prog, rng):
    """One synthetic feed matching the program's data vars."""
    feed = {}
    for v in prog.global_block.vars.values():
        if not v.is_data or v.name.startswith("@"):
            continue
        shape = tuple(int(d) for d in v._data.shape)
        if not shape:
            continue
        if "int" in str(v._data.dtype):
            feed[v.name] = rng.randint(0, 10, shape).astype(
                str(v._data.dtype))
        else:
            feed[v.name] = rng.randn(*shape).astype("float32")
    return feed


def donation_sweep(models=SWEEP_MODELS, steps=2):
    """Donation-coverage sweep over the model zoo: every model trains a
    fused ``run_steps`` window and its compiled entry must (a) donate
    EVERY updated persistable (the scan carry stays in HBM) and (b)
    lower to exactly one while loop. Returns
    ``(coverage_rows, failures)`` — one row per model with the counts a
    CI log can table."""
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid

    rows, failures = [], []
    pt.enable_static()
    try:
        for name, build in models:
            pt.seed(0)
            prog, startup, loss = build()
            exe = fluid.Executor()
            exe.run(startup)
            rng = np.random.RandomState(0)
            feeds = [_sweep_feed(prog, rng) for _ in range(steps)]
            exe.run_steps(prog, feeds=feeds, fetch_list=[loss])
            entry = next(iter(exe._cache.values()))
            n_persist = len(entry.updated)
            hlo = entry_hlo(entry)
            donated = donation_stats(hlo)["count"] if hlo else 0
            # min_while only: conv/embedding models legally carry extra
            # while loops inside the step body on this CPU lowering —
            # the sweep pins donation coverage and the scan's existence
            entry_fails = check_entry(entry, min_donated=n_persist,
                                      min_while=1)
            rows.append({"model": name, "persistables": n_persist,
                         "donated": donated,
                         "coverage": (donated / n_persist
                                      if n_persist else None),
                         "ok": not entry_fails})
            failures += [f"{name}: {f}" for f in entry_fails]
    finally:
        pt.disable_static()
    return rows, failures


def render_sweep(rows):
    lines = [f"{'model':<12} {'persistables':>12} {'donated':>8} "
             f"{'coverage':>9}  ok"]
    for r in rows:
        cov = "?" if r["coverage"] is None else f"{r['coverage']:.0%}"
        lines.append(f"{r['model']:<12} {r['persistables']:>12} "
                     f"{r['donated']:>8} {cov:>9}  {r['ok']}")
    return "\n".join(lines)


# -- self-test ----------------------------------------------------------------

# canned HLO fixtures with HAND-COMPUTED expectations (no backend needed)
CANNED_HLO = [
    {
        "name": "training step: 2 donated params, 3 fusions, no loop",
        "hlo": "HloModule jit_step, is_scheduled=true, "
               "input_output_alias={ {1}: (1, {}, may-alias), "
               "{2}: (2, {}, may-alias) }, "
               "entry_computation_layout={(f32[16,8]{1,0}, f32[8,8]{1,0}, "
               "f32[8]{0})->(f32[], f32[8,8]{1,0}, f32[8]{0})}\n"
               "%f1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %p1), kind=kLoop\n"
               "%f2 = f32[8]{0} fusion(f32[8]{0} %p2), kind=kLoop\n"
               "%f3 = f32[] fusion(f32[16,8]{1,0} %p0), kind=kOutput\n"
               "%d = f32[16,8]{1,0} dot(f32[16,8]{1,0} %p0, "
               "f32[8,8]{1,0} %f1)",
        "donated": 2, "fusion": 3, "while": 0, "dot": 1,
        "aliases": [((1,), 1, "may-alias"), ((2,), 2, "may-alias")],
    },
    {
        "name": "fused scan entry: 1 while, donated carry",
        "hlo": "HloModule jit_fused, is_scheduled=true, "
               "input_output_alias={ {1}: (1, {}, may-alias) }, "
               "entry_computation_layout={(f32[4,16,8]{2,1,0}, "
               "f32[8,8]{1,0})->(f32[4]{0}, f32[8,8]{1,0})}\n"
               "%w = (s32[], f32[8,8]{1,0}, f32[4]{0}) while("
               "(s32[], f32[8,8]{1,0}, f32[4]{0}) %init), "
               "condition=%cond, body=%body\n"
               "%f1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %x), kind=kLoop",
        "donated": 1, "fusion": 1, "while": 1, "dot": 0,
        "aliases": [((1,), 1, "may-alias")],
    },
    {
        "name": "inference executable: nothing donated, no loop",
        "hlo": "HloModule jit_fwd, is_scheduled=true, "
               "entry_computation_layout={(f32[16,8]{1,0})->(f32[16])}\n"
               "%d = f32[16]{0} dot(f32[16,8]{1,0} %p0, f32[8]{0} %c)",
        "donated": 0, "fusion": 0, "while": 0, "dot": 1,
        "aliases": [],
    },
]


# hand-computed overlap structure fixtures: the schedule-order parse +
# start/done pairing the comm-overlap gate rests on (XLA CPU lowers
# collectives synchronously, so the async form is pinned here)
CANNED_OVERLAP = [
    {
        "name": "async all-reduce hidden behind fusion+dot",
        "hlo": "HloModule jit_step, is_scheduled=true\n"
               "ENTRY %main {\n"
               "  %p0 = f32[64]{0} parameter(0)\n"
               "  %ar-start.1 = (f32[64]{0}, f32[64]{0}) "
               "all-reduce-start(f32[64]{0} %p0), "
               "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add\n"
               "  %f1 = f32[64]{0} fusion(f32[64]{0} %p0), kind=kLoop\n"
               "  %d1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %f1, "
               "f32[8,8]{1,0} %f1)\n"
               "  %ar-done.1 = f32[64]{0} all-reduce-done("
               "(f32[64]{0}, f32[64]{0}) %ar-start.1)\n"
               "  %f2 = f32[64]{0} fusion(f32[64]{0} %ar-done.1), "
               "kind=kLoop\n"
               "  ROOT %t = (f32[64]{0}) tuple(f32[64]{0} %f2)\n"
               "}",
        # fusion+dot between start/done -> overlapped; f2 after the
        # start -> interleaved
        "stats": {"collectives": 1, "compute_ops": 3, "async_pairs": 1,
                  "async_overlapped": 1, "interleaved": 1},
    },
    {
        "name": "back-to-back start/done pair hides nothing",
        "hlo": "HloModule jit_step, is_scheduled=true\n"
               "ENTRY %main {\n"
               "  %p0 = f32[64]{0} parameter(0)\n"
               "  %f1 = f32[64]{0} fusion(f32[64]{0} %p0), kind=kLoop\n"
               "  %ar-start.2 = (f32[64]{0}, f32[64]{0}) "
               "all-reduce-start(f32[64]{0} %f1), "
               "replica_groups={{0,1}}, to_apply=%add\n"
               "  %ar-done.2 = f32[64]{0} all-reduce-done("
               "(f32[64]{0}, f32[64]{0}) %ar-start.2)\n"
               "  ROOT %t = (f32[64]{0}) tuple(f32[64]{0} %ar-done.2)\n"
               "}",
        "stats": {"collectives": 1, "compute_ops": 1, "async_pairs": 1,
                  "async_overlapped": 0, "interleaved": 0},
    },
    {
        "name": "sync bucketed exchange interleaved with backward",
        "hlo": "HloModule jit_raw, is_scheduled=true\n"
               "ENTRY %main {\n"
               "  %f1 = f32[64]{0} fusion(f32[64]{0} %p0), kind=kLoop\n"
               "  %ar.1 = f32[64]{0} all-reduce(f32[64]{0} %f1), "
               "replica_groups=[1,8]<=[8], to_apply=%add\n"
               "  %f2 = f32[32]{0} fusion(f32[64]{0} %f1), kind=kLoop\n"
               "  %ar.2 = f32[32]{0} all-reduce(f32[32]{0} %f2), "
               "replica_groups=[1,8]<=[8], to_apply=%add\n"
               "  %f3 = f32[32]{0} fusion(f32[32]{0} %ar.2), kind=kLoop\n"
               "  ROOT %t = (f32[32]{0}) tuple(f32[32]{0} %f3)\n"
               "}",
        # both sync all-reduces precede the last compute op (f3)
        "stats": {"collectives": 2, "compute_ops": 3, "async_pairs": 0,
                  "async_overlapped": 0, "interleaved": 2},
    },
    {
        # ".1" must pair with %ar-done.1, not %ar-start.10's done (a
        # substring match binds .1 -> done.10 and loses the overlap)
        "name": "start/done pairing is exact-name, not prefix",
        "hlo": "HloModule jit_step, is_scheduled=true\n"
               "ENTRY %main {\n"
               "  %p0 = f32[64]{0} parameter(0)\n"
               "  %ar-start.1 = (f32[64]{0}, f32[64]{0}) "
               "all-reduce-start(f32[64]{0} %p0), "
               "replica_groups={{0,1}}, to_apply=%add\n"
               "  %ar-start.10 = (f32[64]{0}, f32[64]{0}) "
               "all-reduce-start(f32[64]{0} %p0), "
               "replica_groups={{0,1}}, to_apply=%add\n"
               "  %ar-done.10 = f32[64]{0} all-reduce-done("
               "(f32[64]{0}, f32[64]{0}) %ar-start.10)\n"
               "  %f1 = f32[64]{0} fusion(f32[64]{0} %p0), kind=kLoop\n"
               "  %ar-done.1 = f32[64]{0} all-reduce-done("
               "(f32[64]{0}, f32[64]{0}) %ar-start.1)\n"
               "  ROOT %t = (f32[64]{0}) tuple(f32[64]{0} %ar-done.1)\n"
               "}",
        # only .1's window spans f1; .10's closes before it
        "stats": {"collectives": 2, "compute_ops": 1, "async_pairs": 2,
                  "async_overlapped": 1, "interleaved": 2},
    },
]


def _check(failures, cond, msg):
    if not cond:
        failures.append(msg)


def _build_mlp(batch=16):
    import paddle_tpu.fluid as fluid

    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[batch, 8])
        y = fluid.data(name="y", shape=[batch, 1])
        h = fluid.layers.fc(x, size=16, act="relu")
        out = fluid.layers.fc(h, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(out, y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return prog, startup, loss


def _live_scan_vs_loop(ndev):
    """The acceptance gate, live: K=8 microbatches through run_steps
    must (a) produce a BITWISE-identical loss trajectory to 8
    sequential run() calls, (b) compile once and dispatch once where
    the loop dispatches 8 times, (c) donate the persistable carry, and
    (d) lower to exactly one while loop."""
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid

    failures = []
    K = 8
    pt.enable_static()
    try:
        rng = np.random.RandomState(0)
        feeds = [{"x": rng.randn(16, 8).astype(np.float32),
                  "y": rng.randn(16, 1).astype(np.float32)}
                 for _ in range(K)]

        pt.seed(0)
        prog, startup, loss = _build_mlp()
        exe = fluid.Executor()
        exe.run(startup)
        seq = [exe.run(prog, feed=f, fetch_list=[loss])[0] for f in feeds]
        calls = executor_call_counts(exe)
        _check(failures, calls["compiles"] == 1 and calls["dispatches"] == K,
               f"sequential loop: expected 1 compile / {K} dispatches, "
               f"got {calls}")

        pt.seed(0)
        prog2, startup2, loss2 = _build_mlp()
        exe2 = fluid.Executor()
        exe2.run(startup2)
        (traj,) = exe2.run_steps(prog2, feeds=feeds, fetch_list=[loss2])
        calls2 = executor_call_counts(exe2)
        _check(failures,
               calls2["compiles"] == 1 and calls2["dispatches"] == 1,
               f"fused run_steps: expected 1 compile / 1 dispatch for "
               f"{K} steps, got {calls2}")
        _check(failures, traj.shape == (K,),
               f"fused trajectory shape {traj.shape} != ({K},)")
        bitwise = all(
            np.asarray(s).tobytes() == np.asarray(traj[k]).tobytes()
            for k, s in enumerate(seq))
        _check(failures, bitwise,
               f"fused loss trajectory is not bitwise-identical to the "
               f"sequential one: {[float(np.asarray(s)) for s in seq]} vs "
               f"{[float(v) for v in traj]}")

        entry = next(iter(exe2._cache.values()))
        n_persist = len(entry.updated)
        _check(failures, n_persist > 0,
               "MLP entry has no updated persistables?")
        failures += [f"fused entry: {f}" for f in check_entry(
            entry, min_donated=n_persist, min_while=1, max_while=1)]
        # the sequential entry must donate too, and contain NO loop
        entry1 = next(iter(exe._cache.values()))
        failures += [f"step entry: {f}" for f in check_entry(
            entry1, min_donated=n_persist, max_while=0)]
    finally:
        pt.disable_static()
    return failures


def _live_inference_gates():
    """Inference coverage (ROADMAP item 3 leftover): the Predictor's
    compiled entries must gate like Executor entries (no loop, nothing
    donated — weights are shared across calls), and the serving decode
    step must DONATE its KV pool buffers (the invariant that keeps one
    resident pool copy across every decode step)."""
    import tempfile

    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid

    failures = []
    pt.enable_static()
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data(name="x", shape=[8, 8])
            out = fluid.layers.fc(x, size=4)
        exe = fluid.Executor()
        exe.run(startup)
        with tempfile.TemporaryDirectory() as d:
            from paddle_tpu.inference import Predictor

            prefix = os.path.join(d, "m")
            pt.framework.io.save_inference_model(
                prefix, ["x"], [out], program=main)
            pred = Predictor(prefix)
            pred.run({"x": np.zeros((8, 8), np.float32)})
            stats = pred.cache_stats()
            _check(failures, stats == {"hits": 0, "misses": 1, "size": 1},
                   f"predictor call accounting: {stats}")
            for entry in pred._compiled.values():
                # inference entry: pure fn — no while loop, and NOTHING
                # donated (a donated weight would be consumed by the
                # first call; predictors share weights across calls)
                failures += [f"predictor entry: {f}" for f in
                             check_entry(entry, max_while=0,
                                         max_donated=0)]
    finally:
        pt.disable_static()

    from paddle_tpu.serving import PagedKVCache, ServeEngine, TinyLM

    eng = ServeEngine(TinyLM(num_heads=2, head_dim=8),
                      PagedKVCache(16, 4, 2, 8))
    entry = eng.decode_entry(2)
    hlo = entry_hlo(entry)
    if hlo is None:
        failures.append("serving decode entry failed to lower")
    else:
        don = donation_stats(hlo)
        _check(failures, don["count"] >= 2,
               f"paged decode step donates {don['count']} < 2 buffers "
               "(KV pool round-trips HBM every token!)")
        params = {p for _, p, _ in don["aliases"]}
        _check(failures, {0, 1} <= params,
               f"decode donation misses a KV pool (params {params}, "
               "k_pages=0 v_pages=1)")
        failures += [f"serving decode entry: {f}" for f in
                     check_entry(entry, min_donated=2)]
    return failures


def self_test():
    ndev = _ensure_fake_devices(8)
    failures = []
    for case in CANNED_HLO:
        don = donation_stats(case["hlo"])
        _check(failures, don["count"] == case["donated"],
               f"{case['name']}: donated {don['count']} != "
               f"{case['donated']}")
        _check(failures, don["aliases"] == case["aliases"],
               f"{case['name']}: aliases {don['aliases']} != "
               f"{case['aliases']}")
        ops = op_counts(case["hlo"], kinds=("fusion", "while", "dot"))
        for k in ("fusion", "while", "dot"):
            _check(failures, ops[k] == case[k],
                   f"{case['name']}: {k} count {ops[k]} != {case[k]}")
        # the bound-checker must agree with the raw counts
        _check(failures,
               check_hlo(case["hlo"], min_donated=case["donated"],
                         max_donated=case["donated"],
                         min_fusion=case["fusion"],
                         min_while=case["while"],
                         max_while=case["while"]) == [],
               f"{case['name']}: check_hlo rejects its own ground truth")
        _check(failures,
               check_hlo(case["hlo"],
                         min_donated=case["donated"] + 1) != [],
               f"{case['name']}: check_hlo missed a donation regression")

    for case in CANNED_OVERLAP:
        got = overlap_stats(case["hlo"])
        _check(failures, got == case["stats"],
               f"{case['name']}: overlap stats {got} != {case['stats']}")
    # the bound checks must accept ground truth and catch regressions
    ok = CANNED_OVERLAP[0]["hlo"]
    _check(failures,
           check_hlo(ok, min_async_overlapped=1, min_interleaved=1) == [],
           "overlap check_hlo rejects the overlapped fixture")
    _check(failures, check_hlo(CANNED_OVERLAP[1]["hlo"],
                               min_async_overlapped=1) != [],
           "overlap check_hlo missed the back-to-back pair")
    _check(failures,
           check_hlo(CANNED_OVERLAP[2]["hlo"], max_all_reduce=1) != [],
           "max_all_reduce missed the 2-all-reduce fixture")

    if ndev < 2:
        failures.append(f"need >=2 fake devices, have {ndev}")
    else:
        failures += _live_scan_vs_loop(ndev)
    failures += _live_inference_gates()

    for line in failures:
        print(f"  FAILED — {line}")
    if failures:
        print(f"self-test FAILED: {len(failures)} check(s)")
        return 1
    print("self-test passed: canned-HLO donation/fusion/while counts "
          "match hand-computed values, bound checks catch seeded "
          "regressions, the overlap parse pins hand-computed async-"
          "pair/interleave structure, the live 8-fake-device K=8 "
          "scan-vs-loop check holds (bitwise loss trajectory, 1 compile "
          "+ 1 dispatch vs 8, persistable carry donated, exactly one "
          "while loop), and the inference gates hold (predictor entries "
          "loop-free with nothing donated, serving decode step donates "
          "both KV pool buffers)")
    return 0


def entry_report(exe=None):
    """Human-readable invariant report over an Executor's cache (the
    --entry-report demo builds a fused MLP run first)."""
    import paddle_tpu as pt
    import paddle_tpu.fluid as fluid

    if exe is None:
        import numpy as np

        pt.enable_static()
        try:
            pt.seed(0)
            prog, startup, loss = _build_mlp()
            exe = fluid.Executor()
            exe.run(startup)
            rng = np.random.RandomState(0)
            feeds = [{"x": rng.randn(16, 8).astype(np.float32),
                      "y": rng.randn(16, 1).astype(np.float32)}
                     for _ in range(4)]
            exe.run_steps(prog, feeds=feeds, fetch_list=[loss])
        finally:
            pt.disable_static()
    lines = [f"calls        {json.dumps(executor_call_counts(exe))}"]
    for key, compiled in exe._cache.items():
        hlo = entry_hlo(compiled)
        if hlo is None:
            lines.append(f"entry uid={compiled.program_uid}: "
                         "HLO unavailable")
            continue
        don = donation_stats(hlo)
        ops = op_counts(hlo, kinds=("fusion", "while", "dot",
                                    "all-reduce"))
        lines.append(
            f"entry uid={compiled.program_uid} "
            f"steps_fused={getattr(compiled, 'steps', None)}  "
            f"donated={don['count']}  ops={json.dumps(ops)}")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--self-test", action="store_true",
                    help="canned-HLO donation/fusion accounting + live "
                         "scan-vs-loop compiled-call-count gate")
    ap.add_argument("--entry-report", action="store_true",
                    help="build + fuse a demo MLP and print its "
                         "invariant report")
    ap.add_argument("--donation-sweep", action="store_true",
                    help="train every model-zoo sweep leg through a "
                         "fused run_steps window and report per-model "
                         "donation coverage; exit 1 when any carry is "
                         "not donated")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    if args.entry_report:
        _ensure_fake_devices(8)
        print(entry_report())
        return 0
    if args.donation_sweep:
        _ensure_fake_devices(8)
        rows, failures = donation_sweep()
        print(render_sweep(rows))
        for line in failures:
            print(f"  FAILED — {line}")
        return 1 if failures else 0
    ap.error("pass --self-test, --entry-report, or --donation-sweep")


if __name__ == "__main__":
    sys.exit(main())
