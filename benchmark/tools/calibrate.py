"""Read, on the chip, the numbers a cell's ``limits`` are set from.

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,... [--control-seeds 1,2,3] [--half-seeds 1,2,3] [--witness-seeds 1,2,3] [--until SECONDS]

For every seed: the program's first three steps (through the loop's own
call, as a run takes them) against the float32 reference: the sound
readings. For every control seed: the reference computed in fp8 in the
program's place against the same float32 reference: the readings a limit
must catch. For every half seed: the float32 reference over the first half
of each row's tokens alone, the mean taken over those (the fault "half of
the batch left out" where a batch is one packed row), in the program's place.
For every witness seed: the reference with bfloat16 operands in the
program's place, which shows what the stated precision alone does to a number.
Needs no measured window; one process. Prints one line per seed with the
three leaves that read the widest gap in each worst-leaf number, writes every
leaf's norms to chiprun_out/calibrate/<cell>_<seed>.json, and prints last the
largest sound and the smallest control and fault reading of each number with
their ratio. The benchmark's own runs do not run this; PERF.md section 2
holds what it printed.
"""
import argparse
import gc
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _leaves(readings):
    """What a seed's file keeps of one side: the losses, each leaf's two
    norms, and its sampled gradient's squared norm."""
    return {"losses": readings["losses"],
            "grad_norms": readings["grad_norms"],
            "delta_norms": readings["delta_norms"],
            "sample_sq": {k: float((v.astype("float64") ** 2).sum())
                          for k, v in readings["grad_sample"].items()}}


def _widest(got, want, key, n=3):
    """The ``n`` leaves whose norm lies farthest from the reference's, by
    ``correct.worst_leaf_gap``'s measure."""
    floor = statistics.median(want[key].values())
    gaps = sorted(((abs(got[key][k] - w) / max(w, floor, 1e-30), k, w / floor)
                   for k, w in want[key].items()), reverse=True)[:n]
    return ", ".join(f"{k} {g:.4g} (norm {r:.3g} medians)"
                     for g, k, r in gaps)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--half-seeds", default="")
    ap.add_argument("--witness-seeds", default="")
    ap.add_argument("--until", type=float, default=float("inf"),
                    help="start no further seed after this many seconds")
    args = ap.parse_args()

    from benchmark import correct, harness
    from benchmark.loops import train

    cell = harness.load_cell(args.workload)
    devices = harness.require_tpu(cell["chips"])
    tag = f"[{devices[0].platform} {devices[0].device_kind} x{len(devices)}]"

    import paddle_tpu as pt

    pt.set_compilation_cache()
    import jax

    jax.config.update("jax_compilation_cache_max_size", -1)  # as run.py
    cfg, batch = cell["config"], cell["traffic"]["batch"]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    halves = [int(s) for s in args.half_seeds.split(",") if s]
    witnesses = [int(s) for s in args.witness_seeds.split(",") if s]
    sound, control, half, witness = {}, {}, {}, {}
    out_dir = os.path.join(harness.ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    t_start = time.perf_counter()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        if t - t_start > args.until:
            print(f"{tag} {args.until:.0f}s are up: seed {seed} and those "
                  f"after it not read", flush=True)
            break
        su = train.set_up(cell, seed)
        got = train.program_readings(su.loop, su.model, su.step.optimizer,
                                     su.names, su.weights, su.index,
                                     cfg["recipe"]["beta1"])
        got.pop("first_step_s")
        family, weights, index = su.family, su.weights, su.index
        batches = su.first_batches(train.CHECKED_STEPS, batch)
        del su
        gc.collect()
        # the reference empties the weights it is given: the others' copies
        copies = [dict(weights) for s in (controls, halves, witnesses)
                  if seed in s]
        want = train.reference_readings(family, cell, weights, batches, index,
                                        "float32")
        rows = [("sound", sound, got)]
        if seed in controls:
            rows.append(("control", control, train.reference_readings(
                family, cell, copies.pop(), batches, index, "fp8")))
        if seed in halves:
            rows.append(("half", half, train.reference_readings(
                family, cell, copies.pop(),
                [tuple(a[:, :a.shape[1] // 2] for a in b) for b in batches],
                index, "float32")))
        if seed in witnesses:
            rows.append(("bf16", witness, train.reference_readings(
                family, cell, copies.pop(), batches, index, "bfloat16")))
        dump = {"want": _leaves(want)}
        for kind, into, readings in rows:
            compared = correct.compare(readings, want)
            numbers = {k: v for k, (v, _) in compared.items()}
            for k, v in numbers.items():
                into.setdefault(k, []).append(v)
            dump[kind] = _leaves(readings)
            dump[kind]["sample_diff_sq"] = {
                k: float(((readings["grad_sample"][k].astype("float64")
                           - w) ** 2).sum())
                for k, w in want["grad_sample"].items()}
            print(f"{tag} {kind} seed={seed} "
                  f"{time.perf_counter() - t:.0f}s {json.dumps(numbers)} "
                  f"losses={readings['losses']} "
                  f"grad: {_widest(readings, want, 'grad_norms')} "
                  f"delta: {_widest(readings, want, 'delta_norms')}",
                  flush=True)
        with open(os.path.join(out_dir, f"{cell['name']}_{seed}.json"),
                  "w") as f:
            json.dump(dump, f)
    for k in sound:
        hi = max(sound[k])
        line = f"{tag} {k}: sound max {hi:.6g} over {len(sound[k])} seeds"
        for kind, into in (("control", control), ("half", half),
                           ("bf16", witness)):
            if into:
                lo = min(into[k])
                line += (f"; {kind} min {lo:.6g} max {max(into[k]):.6g} "
                         f"over {len(into[k])} seeds, ratio {lo / hi:.3g}")
        print(line, flush=True)


if __name__ == "__main__":
    main()
