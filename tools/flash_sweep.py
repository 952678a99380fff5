"""On the chip: device time of the three flash-attention kernels by block size.

    python3 tools/flash_sweep.py [--shape BH,Lq,Lk,D[/Dv],dtype,causal[,mask]
        ...] [--blocks 512x512x256,1024x1024x128,...] [--impl <file.py>]
        [--dense] [--window W]

For every shape and every (bq, bk, sub) it sets the block rule's target
(``flash_attention._TARGET``; the rule may still shrink a block to its VMEM
budget, and the blocks it then gives are what the line prints), compiles
forward + backward, profiles a few calls and prints the mean device
milliseconds of ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` from the
trace, with their cost per million scores of the full ``Lq x Lk`` matrix.
``rule`` in place of the blocks measures what :func:`block_sizes` chooses.
``D`` as ``192/128`` gives queries and keys one head width and values another.
``mask`` (0 where left out) is the number of heads of a batch row: the call
then is ``(BH / mask, mask, L, D)`` with a padding mask as the kernels' key
bias, one row in ten padded to half its length or less. ``--dense`` adds a line
a shape with the device milliseconds of ``sdpa``'s dense path on the same
operands, forward and backward, every op of it: what the route's floor on a
grid step's scores (``flash_attention.MIN_STEP_SCORES``) is set against.
``--window W`` measures the sliding-window kernels (``swa_fwd``, ``swa_bwd_dq``,
``swa_bwd_dkv``: causal, a query sees its last W keys) in their place: a
``--blocks`` entry is then ``BxS``, the square block and the band
(``flash_attention._BAND_TARGET``), and ``--dense`` is the band-masked path.
``--impl`` loads another version of the kernel file (the parent commit's, say)
and measures it under the same shapes, blocks ignored. This is the table of
PERF.md's sweep; it needs a TPU and falls back to nothing.
"""
import argparse
import glob
import importlib.util
import inspect
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
SHAPES = ["192,1024,1024,64,bfloat16,1", "288,512,512,64,bfloat16,0",
          "288,512,512,64,bfloat16,0,12", "1536,128,128,64,bfloat16,0",
          "96,1024,1024,128,bfloat16,1"]
BLOCKS = ",".join(f"{q}x{k}x{s}" for q in (128, 256, 512, 1024)
                  for k in (128, 256, 512, 1024) for s in (128, 256, 512)
                  if s <= min(q, k))
CALLS = 4


def kernel_ms(directory, kernels=KERNELS):
    """{kernel: mean device ms a call} from the newest trace under
    ``directory``: the first device's ``XLA Ops`` events by kernel name
    (``kernels``: no name may hold another; ``tools/softmax_ce_sweep.py``
    reads its two through here). ``kernels`` None: ``{"dense_ms": ...}``,
    every op's time over ``CALLS`` calls."""
    import jax

    path = max(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    plane = next(p for p in jax.profiler.ProfileData.from_file(path).planes
                 if p.name == "/device:TPU:0")
    if kernels is None:
        return {"dense_ms": sum(e.duration_ns for line in plane.lines
                                if line.name == "XLA Ops"
                                for e in line.events) / 1e6 / CALLS}
    spent = {k: [] for k in kernels}
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            head = e.name.partition(" = ")[0]
            for k in kernels:
                if k in head:
                    spent[k].append(e.duration_ns / 1e6)
                    break
    if not any(spent.values()):
        raise RuntimeError(f"none of {kernels} among the device's ops: " + str(
            [e.name[:60] for ln in plane.lines for e in list(ln.events)[:3]]))
    return {k: sum(v) / len(v) if v else None for k, v in spent.items()}


def load_impl(path):
    """Another version of a kernel file (``--impl``) as a module."""
    spec = importlib.util.spec_from_file_location("sweep_impl", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def measure(fa, shape, dense=False, window=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    BH, Lq, Lk, (D, Dv), dtype, causal, heads = shape
    bound = None if hasattr(fa, "block_sizes") else 128   # the old signature
    B, H = (BH // heads, heads) if heads else (1, BH)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(key, (B, H, L, d), dtype)
                   for key, L, d in zip(keys, (Lq, Lk, Lk, Lq),
                                        (D, D, Dv, Dv)))
    bias = None
    if heads:       # every tenth row short, as the benchmark's BERT batches
        rows = np.arange(B)
        kept = np.where(rows % 10 == 3, Lk // 2 - rows % 7, Lk)
        bias = jnp.asarray(np.where(np.arange(Lk) < kept[:, None], 0.0,
                                    -1e30)[:, None], jnp.float32)

    def loss(q, k, v):      # a fresh function: the blocks are read at trace time
        if dense:
            from paddle_tpu.nn.functional.attention import _sdpa
            out = _sdpa(q, k, v, None if bias is None else bias[:, None],
                        None, scale=D ** -0.5, is_causal=causal,
                        dropout_p=0.0, **({"window": window} if window
                                          else {}))
        elif window:
            out = fa.window_attention(q, k, v, window, None, None, False)
        elif "bias" in inspect.signature(fa.flash_attention).parameters:
            out = fa.flash_attention(q, k, v, bias, causal, None, bound, False)
        else:
            out = fa.flash_attention(q, k, v, causal, None, bound, False)
        return jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, (0, 1, 2)))
    jax.block_until_ready(step(q, k, v))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(CALLS):
                out = step(q, k, v)
            jax.block_until_ready(out)
        return kernel_ms(tmp, None if dense else tuple(
            k.replace("flash", "swa") for k in KERNELS) if window else KERNELS)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append")
    ap.add_argument("--blocks", default=BLOCKS)
    ap.add_argument("--impl", default="")
    ap.add_argument("--dense", action="store_true")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/flash_sweep.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        sys.exit("flash_sweep measures device time: it needs a TPU")
    if args.impl:
        fa = load_impl(args.impl)
        pairs = [None]
    else:
        fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
        pairs = [None] + [tuple(int(x) for x in b.split("x"))
                          for b in args.blocks.split(",")]
    target = "_BAND_TARGET" if args.window else "_TARGET"
    rule = getattr(fa, target, None)     # before the sweep sets any
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    def emit(row):
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")

    for text in args.shape or SHAPES:
        BH, Lq, Lk, D, dtype, causal, heads = (text.split(",") + ["0"])[:7]
        widths = [int(d) for d in D.split("/")]
        shape = (int(BH), int(Lq), int(Lk), (widths[0], widths[-1]),
                 jnp.dtype(dtype), bool(int(causal)), int(heads))
        mscores = shape[0] * shape[1] * shape[2] / 1e6
        if args.dense:      # the dense path's mask is the one the route took
            from paddle_tpu.ops import pallas as pk
            pk.set_enabled(False)
            emit({"impl": "dense", "shape": text,
                  **measure(fa, shape, True, args.window)})
            pk.set_enabled(None)
        for blocks in pairs:
            if blocks and (blocks[0] > shape[1] or
                           blocks[1] > shape[1 if args.window else 2]):
                continue
            t = time.perf_counter()
            if rule is not None:
                setattr(fa, target, blocks or rule)
            try:
                ms = measure(fa, shape, window=args.window)
            except Exception as e:      # Mosaic refused the blocks: say so
                ms = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            got = None
            if args.window:
                got = fa.band_sizes(shape[1], args.window, shape[3][0],
                                    shape[4].itemsize, None, shape[3][1])
            elif hasattr(fa, "block_sizes"):
                got = fa.block_sizes(shape[1], shape[2], shape[3][0],
                                     shape[4].itemsize, None, shape[3][1]) \
                    if shape[3][0] != shape[3][1] else fa.block_sizes(
                        shape[1], shape[2], shape[3][0], shape[4].itemsize)
            row = {"impl": args.impl or "tree", "shape": text,
                   "asked": blocks or "rule", "blocks": got, **ms}
            if "error" not in ms and all(ms.values()):
                row["sum_ms"] = sum(ms.values())
                row["ms_per_mscore"] = row["sum_ms"] / mscores
            row["wall_s"] = round(time.perf_counter() - t, 1)
            emit(row)


if __name__ == "__main__":
    main()
