"""Read, on the chip, the numbers a cell's ``limits`` are set from.

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,... [--control-seeds 1,2,3]

For every seed: the program's first three steps (through the loop's own
call, as a run takes them) against the float32 reference: the sound
readings. For every control seed: the reference computed in fp8 in the
program's place against the same float32 reference: the readings a limit
must catch. Needs no measured window; one process, so the step compiles
once. Prints one line per seed and, last, the largest sound and the smallest
control reading of each number with their ratio. The benchmark's own runs do
not run this; PERF.md section 2 holds what it printed.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()

    from benchmark import correct, harness
    from benchmark.loops import train

    cell = harness.load_cell(args.workload)
    devices = harness.require_tpu(cell["chips"])
    tag = f"[{devices[0].platform} {devices[0].device_kind} x{len(devices)}]"

    import paddle_tpu as pt

    pt.set_compilation_cache()
    cfg, batch = cell["config"], cell["traffic"]["batch"]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    sound, control = {}, {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        su = train.set_up(cell, seed)
        got = train.program_readings(su.loop, su.model, su.step.optimizer,
                                     su.names, su.weights, su.index,
                                     cfg["recipe"]["beta1"])
        got.pop("first_step_s")
        family, weights, index = su.family, su.weights, su.index
        batches = su.first_batches(train.CHECKED_STEPS, batch)
        del su
        gc.collect()
        # the reference empties the weights it is given: the control's copy
        want = train.reference_readings(
            family, cell, dict(weights) if seed in controls else weights,
            batches, index, "float32")
        rows = [("sound", sound, got)]
        if seed in controls:
            rows.append(("control", control, train.reference_readings(
                family, cell, weights, batches, index, "fp8")))
        for kind, into, readings in rows:
            numbers = {k: v for k, (v, _) in
                       correct.compare(readings, want).items()}
            for k, v in numbers.items():
                into.setdefault(k, []).append(v)
            print(f"{tag} {kind} seed={seed} "
                  f"{time.perf_counter() - t:.0f}s {json.dumps(numbers)} "
                  f"losses={readings['losses']}", flush=True)
    for k in sound:
        hi = max(sound[k])
        line = f"{tag} {k}: sound max {hi:.6g} over {len(sound[k])} seeds"
        if control:
            lo = min(control[k])
            line += (f"; control min {lo:.6g} over {len(control[k])} seeds; "
                     f"ratio {lo / hi:.3g}")
        print(line, flush=True)


if __name__ == "__main__":
    main()
