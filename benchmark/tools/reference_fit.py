"""Does the plain reference fit a chip beside a large model's share? On the
chip, ``reference/_common.py:train_steps`` over the GPT-2 reference at a size
no cell has (36 x 1280, 20 heads: 774 M parameters; a size for this tool,
not a configuration), three steps, and what the device held at its fullest.

    python3 benchmark/tools/reference_fit.py --rows 4 --length 1024 [--layers 36 --width 1280 --heads 20]

One process follows one shape, because a process's peak never falls: run it
once for rows of 1,024 tokens and once for rows of 4,096. A batch is two of
the reference's micro-batches (``loops/train.py:micro_rows``), so that the
gradient summed so far and one micro-batch's are both alive, as in a cell.
Prints one line: the parameters, the device's ``peak_bytes_in_use``, the
bytes a parameter that is, and the seconds; or, where the device cannot hold
it, the runtime's refusal, and exits non-zero. PERF.md section 2 holds what
it printed on the parent of PR 26 and on the change.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--length", type=int, required=True)
    ap.add_argument("--layers", type=int, default=36)
    ap.add_argument("--width", type=int, default=1280)
    ap.add_argument("--heads", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 26)
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    import numpy as np

    from benchmark import generate, harness
    from benchmark.families import _recipe
    from benchmark.loops import train
    from benchmark.reference import _common as ref_common, gpt2

    device = harness.require_tpu(1)[0]
    cfg = harness.load_json("configs", "gpt2-small.json")
    cfg.update(n_layer=args.layers, n_embd=args.width, n_head=args.heads,
               n_positions=args.length)
    specs = gpt2.param_specs(cfg)
    n = _recipe.n_params(specs)
    micro = train.micro_rows(args.length)
    ids = generate.zipf_tokens(
        np.random.default_rng(args.seed), args.steps * args.rows * args.length,
        0, cfg["vocab_size"], 1.0).reshape(args.steps, args.rows, args.length)
    batches = [(row, np.roll(row, -1, axis=1)) for row in ids]
    tag = (f"[{device.platform} {device.device_kind}] reference_fit "
           f"{args.layers} x {args.width}: {n} parameters, batch {args.rows} x "
           f"{args.length} in micro-batches of {micro} row(s), {args.steps} "
           f"steps:")
    t = time.perf_counter()
    try:
        got = ref_common.train_steps(
            gpt2.loss_part(cfg), gpt2.denominators,
            ref_common.init_weights(specs, args.seed), batches, cfg["recipe"],
            ref_common.sample_index(specs), micro=micro)
    except Exception as e:  # the runtime's refusal is the result
        stats = device.memory_stats()
        print(f"{tag} CANNOT, {type(e).__name__}: {str(e)[:400]!r}; "
              f"bytes_limit {stats['bytes_limit']}", flush=True)
        raise SystemExit(1)
    stats = device.memory_stats()
    peak = stats["peak_bytes_in_use"]
    print(f"{tag} peak_bytes_in_use {peak} of {stats['bytes_limit']} = "
          f"{peak / n:.2f} bytes a parameter, losses {got['losses']}, "
          f"{time.perf_counter() - t:.0f}s", flush=True)


if __name__ == "__main__":
    main()
