"""On the chip: the rotary kernel alone, by tile.

    python3 tools/rope_sweep.py [--shape n,L,d,r,offset ...]
        [--tiles rule,512x8x64,1024x8x64,...] [--dense]

For every shape (``n`` heads of ``L`` rows by ``d`` bfloat16 columns, of which
the ``r`` from ``offset`` rotate) and every ``ROWSxHEADSxBAND`` it sets the
tile rule's numbers (``ops.pallas.rotary``: rows and heads a grid step, rows
in flight; the rule's ceiling on a block's bytes doubled), runs the rotation forward and backward (one ``rope_*`` call each),
profiles a few calls and prints the mean device milliseconds a call of the
kernel (``flash_sweep``'s reader), its share of the HBM peak for the heads
read once and written once, the device time of the whole call, and whether
result and gradient are the jnp body's to the bit. ``rule`` measures the
module's own numbers; ``--dense`` adds the jnp body
(``ops.pallas.set_enabled(False)``), forward and backward, every op of it.
This is the table of PERF.md's sweep (PR 43); it needs a TPU and falls back to
nothing.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.flash_sweep import CALLS, kernel_ms    # noqa: E402

SHAPES = ["80,8192,128,128,0", "56,8192,128,64,0", "32,8192,192,64,128",
          "32,4096,192,64,128", "1,8192,64,64,0"]


def measure(n, length, d, r, offset, kernel):
    """Device ms of forward + backward, and the results."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import decoder as D

    cos, sin = (jnp.asarray(t) for t in D.rotary_cos_sin(length, r, 1e4))
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(keys[0], (1, n, length, d), jnp.bfloat16)
    cot = jax.random.normal(keys[1], x.shape, jnp.bfloat16)

    def both(x, cot):      # a fresh function: the tiles are read at trace time
        out, vjp = jax.vjp(lambda t: D._rotary(t, cos, sin, offset=offset), x)
        return out, vjp(cot)[0]

    step = jax.jit(both)
    got = jax.block_until_ready(step(x, cot))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(CALLS):
                out = step(x, cot)
            jax.block_until_ready(out)
        row = {"call_ms": kernel_ms(tmp, None)["dense_ms"]}
        if kernel:
            row["rope_ms"] = kernel_ms(tmp, ("rope_",))["rope_"]
    return row, got


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", nargs="*", default=SHAPES)
    ap.add_argument("--tiles", default="rule")
    ap.add_argument("--dense", action="store_true")
    ap.add_argument("--out", default="chiprun_out/rope_sweep.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        sys.exit("rope_sweep measures device time: it needs a TPU")
    from benchmark import harness
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import rotary as ro

    hbm_peak = harness.peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    rule = ro.ROWS, ro.HEADS, ro.BAND, ro.BLOCK_BYTES
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def emit(row):
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")

    for shape in args.shape:
        n, length, d, r, offset = (int(v) for v in shape.split(","))
        pk.set_enabled(False)
        dense, want = measure(n, length, d, r, offset, False)
        pk.set_enabled(None)
        if args.dense:
            emit({"shape": shape, "path": "dense", **dense})
        for tile in args.tiles.split(","):
            if tile == "rule":
                ro.ROWS, ro.HEADS, ro.BAND, ro.BLOCK_BYTES = rule
            else:     # the tile asked for is the tile that runs
                ro.ROWS, ro.HEADS, ro.BAND = (int(v) for v in tile.split("x"))
                ro.BLOCK_BYTES = 8 * 2 ** 20
            jax.clear_caches()
            row = {"shape": shape, "tile": tile,
                   "tiles": [ro.tiles(n, length, d), ro.BAND]}
            started = time.time()
            try:
                got, have = measure(n, length, d, r, offset, True)
                row.update(got)
                # a call reads the heads once and writes them once
                row["hbm_pct"] = round(100 * 4 * n * length * d / hbm_peak /
                                       (got["rope_ms"] / 1e3), 1)
                row["bitwise"] = [bool(jnp.all(
                    a.view(jnp.uint16) == b.view(jnp.uint16)))
                    for a, b in zip(have, want)]
            except Exception as e:      # Mosaic's refusal is the finding
                row["error"] = f"{type(e).__name__}: {str(e)[-300:]}"
            row["seconds"] = round(time.time() - started, 1)
            emit(row)
        ro.ROWS, ro.HEADS, ro.BAND, ro.BLOCK_BYTES = rule


if __name__ == "__main__":
    main()
