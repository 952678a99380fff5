"""On the chip: where a cell's step spends its device time, by the names the
program gives its work.

    python3 benchmark/tools/op_table.py --workload <cell> --seed <n> [--fixture <path>]

Sets the cell up as a run does, drives the checked and warm-up steps, profiles
``loops/train.py:TRACE_STEPS`` steady steps and prints device milliseconds a
step on the first device by phase, by program op and phase, and by kernel
(``benchmark/scope_reduce.py``), then the longest unscoped ops. This is the
table of PERF.md section 5. ``--fixture`` also records what the reduction
read (the entry computation's instruction lines without their
``backend_config``, and the first device's ops) as the gzipped JSON that
``tests/benchmark/test_scope_reduce.py`` checks it on.
"""
import argparse
import gzip
import json
import os
import re
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def entry_lines(hlo_text):
    """The entry computation's instruction lines, each cut before its
    ``backend_config`` (a Mosaic call's holds the whole kernel)."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}") + 2]
    return [re.sub(r", backend_config=.*$", "", line)
            for line in entry.splitlines()[2:-1]]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fixture", default="")
    args = ap.parse_args()

    from benchmark import harness, scope_reduce, trace_reduce
    from benchmark.loops import train

    cell = harness.load_cell(args.workload)
    devices = harness.require_tpu(cell["chips"])

    def say(msg):
        print(f"[{devices[0].device_kind} x{len(devices)} {cell['name']}] "
              f"t={time.perf_counter() - T_START:5.1f}s {msg}", flush=True)

    import paddle_tpu as pt
    from paddle_tpu.ops import OP_REGISTRY

    say(f"compile cache: {pt.set_compilation_cache()}")
    su = train.set_up(cell, args.seed)
    for _ in range(train.CHECKED_STEPS + train.WARMUP_STEPS):
        su.loop.one_step()
    trace = harness.traced_steps(su.loop, train.TRACE_STEPS, say)
    text = su.step.compiled().as_text()
    events = next(iter(trace.ops.values()))
    table = scope_reduce.rows(*scope_reduce.instructions(text), events,
                              trace.steps, set(OP_REGISTRY))
    busy = sum(r.ms for r in table)
    say(f"busy {busy:.3f} ms a step on the first device, "
        f"{len(table)} distinct ops")

    def show(title, grouped):
        print(f"\n{title}")
        for key, ms in sorted(grouped.items(), key=lambda x: -x[1]):
            print(f"  {ms:9.3f} ms {100 * ms / busy:6.2f}%  {key}")

    show("by phase", scope_reduce.by(table, lambda r: r.phase or "unscoped"))
    show("by program op and phase", scope_reduce.by(
        table, lambda r: f"{r.program_op or '-'} / {r.phase or 'unscoped'}"))
    show("by kernel", scope_reduce.by(
        [r for r in table if r.kernel], lambda r: r.kernel))
    show("collectives", scope_reduce.by(
        [r for r in table if trace_reduce.is_collective(r.label)],
        lambda r: f"{r.label} / {r.phase or 'unscoped'}"))
    show("named by data flow (no path of their own), by opcode and phase",
         scope_reduce.by([r for r in table if r.by_data_flow], lambda r:
                         f"{r.label.partition(' ')[2]} / {r.phase or 'unscoped'}"))
    show("longest unscoped ops", {
        r.label: r.ms for r in [r for r in table if not r.phase][:15]})
    show("longest ops", {
        f"{r.label} / {r.program_op or '-'} / {r.phase or 'unscoped'} "
        f"x{r.calls:g}": r.ms for r in table[:25]})

    if args.fixture:
        os.makedirs(os.path.dirname(args.fixture) or ".", exist_ok=True)
        with gzip.open(args.fixture, "wt") as f:
            json.dump({"cell": cell["name"], "seed": args.seed,
                       "device_kind": devices[0].device_kind,
                       "steps": trace.steps,
                       "entry": entry_lines(text), "ops": events}, f)
        say(f"fixture: {args.fixture} "
            f"{os.path.getsize(args.fixture) / 1e3:.0f} kB")


if __name__ == "__main__":
    main()
