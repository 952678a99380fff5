"""On the chip: the state-space scan's kernels alone.

    python3 tools/ssm_sweep.py [--shape B,L,H,P,N,chunk,dtype ...]
        [--impl <another version of ssm_scan.py> ...] [--dense]

For every shape (``B`` rows of ``L`` tokens, ``H`` heads of ``P`` over a state
of ``N``, chunks of ``chunk``; ``x``, ``B``, ``C`` in ``dtype``, ``A`` and
``Delta`` drawn as Mamba-2 draws them, so that state is carried over the
chunks) it runs ``ssm_chunk`` forward and backward through the kernels
(``ops.pallas.ssm_scan``, or each ``--impl``'s), profiles a few calls and
prints the mean device milliseconds a call of ``ssm_scan_fwd`` and
``ssm_scan_bwd`` (``flash_sweep``'s reader), the device time of the whole
call, and the largest error of the result and of each gradient against the
``lax.scan`` body's (``ops.pallas.set_enabled(False)``), as a share of the
largest value. ``--dense`` adds that body's own time. This is the table of
PERF.md's sweep (PR 45); it needs a TPU and falls back to nothing.
"""
import argparse
import importlib.util
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.flash_sweep import CALLS, kernel_ms    # noqa: E402

SHAPES = ["1,8192,64,64,128,256,bfloat16"]
KERNELS = ("ssm_scan_fwd", "ssm_scan_bwd")


def load_impl(path):
    """Another version of ``ssm_scan.py`` as a module of its package (its
    relative imports hold)."""
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.ops.pallas.sweep_impl", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def measure(shape, kernel):
    """Device ms of forward + backward, and the results in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.nn.functional import state_space as ss

    rows, length, h, p, n, chunk, dtype = shape
    rng = np.random.default_rng(0)
    x, b, c, cot = (jnp.asarray(rng.normal(size=s), dtype) for s in (
        (rows, length, h, p), (rows, length, n), (rows, length, n),
        (rows, length, h, p)))
    dt = jnp.exp(jnp.asarray(rng.uniform(np.log(0.001), np.log(0.1),
                                         (rows, length, h)), jnp.float32))
    a_log = jnp.log(jnp.asarray(rng.uniform(1, 16, h), jnp.float32))
    d = jnp.asarray(rng.normal(size=h), jnp.float32)

    def both(*operands):   # a fresh function: the route is read at trace time
        y, vjp = jax.vjp(lambda *o: ss._ssm_chunk(*o, chunk=chunk)[0],
                         *operands)
        return (y,) + vjp(cot)

    step = jax.jit(both)
    operands = (x, dt, a_log, b, c, d)
    got = jax.block_until_ready(step(*operands))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(CALLS):
                out = step(*operands)
            jax.block_until_ready(out)
        row = {"call_ms": round(kernel_ms(tmp, None)["dense_ms"], 3)}
        if kernel:
            row.update({k: round(v, 3)
                        for k, v in kernel_ms(tmp, KERNELS).items()})
    return row, [np.asarray(a, np.float32) for a in got]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", nargs="*", default=SHAPES)
    ap.add_argument("--impl", nargs="*", default=[])
    ap.add_argument("--dense", action="store_true")
    ap.add_argument("--out", default="chiprun_out/ssm_sweep.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        sys.exit("ssm_sweep measures device time: it needs a TPU")
    from paddle_tpu.ops import pallas as pk

    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def emit(row):
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")

    own = pk.ssm_scan
    for text in args.shape:
        *sizes, dtype = text.split(",")
        shape = tuple(int(v) for v in sizes) + (jnp.dtype(dtype),)
        pk.set_enabled(False)
        dense, want = measure(shape, False)
        pk.set_enabled(None)
        if args.dense:
            emit({"shape": text, "impl": "dense", **dense})
        for impl in [None] + args.impl:
            pk.ssm_scan = own if impl is None else load_impl(impl).ssm_scan
            jax.clear_caches()
            row = {"shape": text, "impl": impl or "ops.pallas.ssm_scan"}
            started = time.time()
            try:
                got, have = measure(shape, True)
                row.update(got)
                row["err"] = [float(f"{np.abs(a - b).max() / np.abs(b).max():.3g}")
                              for a, b in zip(have, want)]
            except Exception as e:      # Mosaic's refusal is the finding
                row["error"] = f"{type(e).__name__}: {str(e)[-600:]}"
            row["seconds"] = round(time.time() - started, 1)
            emit(row)
        pk.ssm_scan = own


if __name__ == "__main__":
    main()
