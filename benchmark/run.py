"""The benchmark's entry: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on the machine it is started on; finds a TPU with the cell's
chips or exits non-zero (there is no CPU mode); names the device on every
line; prints the contract's one JSON object as the last line of stdout.
With ``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones; its last key, ``checks``, holds every
number compared for ``correct`` with its limit, and standard error ends with
them. benchmark/README.md says how cells, configurations, traffic mixes, loop
kinds and per-layer metrics are added as files.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchmark import harness

    man = harness.manifest()
    cell = harness.load_cell(args.workload, man)
    devices = harness.require_tpu(cell["chips"])
    tag = (f"[{devices[0].platform} {devices[0].device_kind} "
           f"x{len(devices)} {cell['name']}]")

    def say(msg):
        print(f"{tag} t={time.perf_counter() - T_START:5.1f}s {msg}",
              flush=True)

    import paddle_tpu as pt

    # JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.xla_cache
    say(f"compile cache: {pt.set_compilation_cache()}")
    # and no ceiling on that directory's size: under one (the chip machines
    # set JAX_COMPILATION_CACHE_MAX_SIZE to 192 MiB) jax drops the entries
    # used longest ago at every write, a large cell's programs pass it
    # together, and every run compiles its reference and its model's set-up
    # programs again (PERF.md section 6, PR 31)
    import jax

    jax.config.update("jax_compilation_cache_max_size", -1)
    end_to_end = harness.end_to_end_of(man, cell["name"])

    def read_layers(window):
        values = {}
        for m, reader in harness.layer_readers(man, cell):
            value = reader.read(window)
            if value is not None:
                values[m["name"]] = {"value": value, "unit": m["unit"]}
        return values

    loop = harness.load_module("loops", cell["loop"])
    result = loop.run(cell, args, T_START, say, read_layers)

    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "device": result["device"],
            "reference_s": result["reference_s"]}
    if args.trace:
        line["metrics"] = result["per_layer"]
        line["breakdown"] = result["breakdown"]
    else:
        e2e = result["end_to_end"]
        line["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in end_to_end}
    for name, m in line["metrics"].items():
        say(f"metric {name} = {m['value']} {m['unit']}")
    # every number compared for ``correct`` beside its limit: the result
    # line's last key and the last lines on standard error (the driver's
    # record of a run that is not correct keeps the end of each)
    line["checks"] = result["checks"]
    for name, c in line["checks"].items():
        print(f"check {name} value={c['value']:.6g} limit={c['limit']:.6g}"
              + (f" where={c['where']}" if "where" in c else ""),
              file=sys.stderr)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
