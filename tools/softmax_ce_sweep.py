"""On the chip: device time of the two fused softmax-CE kernels by block size.

    python3 tools/softmax_ce_sweep.py [--shape N,V,dtype ...]
        [--blocks 256x2048,512x1024,...] [--impl <file.py>]

For every shape and every (bn, bv) it sets the block rule's target
(``softmax_ce._TARGET``) and lifts the rule's VMEM budget, so that the blocks
asked for are the blocks that run wherever Mosaic itself takes them (a
refusal is printed as the line's ``error``); compiles forward + backward,
profiles a few calls and prints the mean device milliseconds of
``softmax_ce_fwd`` and ``softmax_ce_bwd`` from the trace (``flash_sweep``'s
reader), with the share of the HBM peak that the bytes they must move come to.
``rule`` in place of the blocks measures what :func:`block_sizes` chooses
under its own budget. ``--impl`` loads another version of the kernel file
(the parent commit's, say) and measures it under the same shapes, blocks
ignored where it has no ``_TARGET``. This is the table of PERF.md's sweep
(PR 28); it needs a TPU and falls back to nothing.
"""
import argparse
import importlib
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.flash_sweep import CALLS, kernel_ms, load_impl    # noqa: E402

KERNELS = ("softmax_ce_fwd", "softmax_ce_bwd")
SHAPES = ["16384,50304,bfloat16", "4096,16384,bfloat16"]
BLOCKS = ",".join(f"{n}x{v}" for n in (128, 256, 512)
                  for v in (1024, 2048, 4096))


def measure(ce, N, V, dtype):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (N, V), dtype)
    labels = jax.random.randint(keys[1], (N,), 0, V, jnp.int32)
    w = jax.random.uniform(keys[2], (N,), jnp.float32)

    def loss(x):        # a fresh function: the blocks are read at trace time
        return jnp.sum(ce.softmax_cross_entropy(x, labels, -100, False) * w)

    step = jax.jit(jax.grad(loss))
    jax.block_until_ready(step(x))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(CALLS):
                out = step(x)
            jax.block_until_ready(out)
        return kernel_ms(tmp, KERNELS)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append")
    ap.add_argument("--blocks", default=BLOCKS)
    ap.add_argument("--impl", default="")
    ap.add_argument("--out", default="chiprun_out/softmax_ce_sweep.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        sys.exit("softmax_ce_sweep measures device time: it needs a TPU")
    from benchmark import harness

    hbm_peak = harness.peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    ce = load_impl(args.impl) if args.impl else importlib.import_module(
        "paddle_tpu.ops.pallas.softmax_ce")
    rule = getattr(ce, "_TARGET", None), getattr(ce, "VMEM_BUDGET", None)
    pairs = [None]
    if rule[0] is not None:
        pairs += [tuple(int(x) for x in b.split("x"))
                  for b in args.blocks.split(",")]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for text in args.shape or SHAPES:
        N, V, dtype = text.split(",")
        N, V, dtype = int(N), int(V), jnp.dtype(dtype)
        # forward reads the logits, backward reads them and writes their
        # gradient; the (N,) operands are noise beside them
        must_move = 3 * N * V * dtype.itemsize
        for blocks in pairs:
            t = time.perf_counter()
            if rule[0] is not None:
                ce._TARGET, ce.VMEM_BUDGET = (blocks, 1 << 40) if blocks \
                    else rule
            try:
                ms = measure(ce, N, V, dtype)
            except Exception as e:      # Mosaic refused the blocks: say so
                ms = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            row = {"impl": args.impl or "tree", "shape": text,
                   "asked": blocks or "rule",
                   "blocks": ce.block_sizes(N, V, dtype.itemsize)
                   if hasattr(ce, "block_sizes") else None, **ms}
            if "error" not in ms and all(ms.values()):
                row["sum_ms"] = sum(ms[k] for k in KERNELS)
                row["hbm_peak_pct"] = 100 * must_move / hbm_peak / (
                    row["sum_ms"] / 1e3)
            row["wall_s"] = round(time.perf_counter() - t, 1)
            print(json.dumps(row), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
