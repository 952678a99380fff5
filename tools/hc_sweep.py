"""On the chip: one sublayer of the multi-stream residual alone, by tile.

    python3 tools/hc_sweep.py [--shape n,T,C]
        [--tiles rule,128,256,mix_bwd=32+read_bwd=32] [--bands 16,32]
        [--chunks 512,1792,3584] [--dense]

The sublayer is ``hc_maps -> hc_read -> a stand-in layer -> hc_mix`` over
bfloat16 streams (n, 1, T, C) under ``jax.checkpoint``, forward + backward,
as a recomputed block of ``LatentMoE`` runs it: the forward kernels twice,
the backward ones once. For every (tile, band, chunk) it sets each pass's
target token tile (``hyper_connection._WANT``: one number for all, or
``pass=rows`` joined by ``+``), ``BAND`` and ``CHUNK``, lifts
the tile rule's VMEM budget so that the tile asked for is the tile that runs
wherever Mosaic takes it, profiles a few calls and prints the mean device
milliseconds a call of each ``hc_*`` kernel (``flash_sweep``'s reader), their
share of the HBM peak for the bytes they must move, and the device time of
the whole call. ``rule`` measures what ``token_tile`` chooses under its own
budget; ``--dense`` adds the jnp forms (``ops.pallas.set_enabled(False)``).
This is the table of PERF.md's sweep (PR 41); it needs a TPU and falls back
to nothing.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.flash_sweep import CALLS, kernel_ms    # noqa: E402

KERNELS = ("hc_maps_fwd", "hc_maps_bwd", "hc_read_bwd", "hc_mix_fwd",
           "hc_mix_bwd")


def kernel_bytes(n, tokens, c):
    """What each kernel must move, bytes a call: bf16 streams of ``tokens x
    c`` in and out; the maps and the projection's weights are under 1%."""
    stream = 2 * tokens * c
    return {"hc_maps_fwd": n * stream, "hc_maps_bwd": 2 * n * stream,
            "hc_read_bwd": (2 * n + 1) * stream,
            "hc_mix_fwd": (2 * n + 1) * stream,
            "hc_mix_bwd": (3 * n + 2) * stream}


def measure(n, tokens, c, kernels):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import decoder as D

    k = 2 * n + n * n
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (n, 1, tokens, c), jnp.bfloat16)
    phi = (0.02 * jax.random.normal(keys[1], (n * c, k))).astype(jnp.bfloat16)
    alpha = jnp.ones((3,), jnp.bfloat16)
    bias = (0.1 * jax.random.normal(keys[2], (k,))).astype(jnp.bfloat16)
    gain = jax.random.normal(keys[3], (c,), jnp.bfloat16)
    cot = jax.random.normal(keys[4], x.shape, jnp.bfloat16)

    @jax.checkpoint
    def sublayer(x, phi, alpha, bias, gain):
        pre, post, res = D._hc_maps(
            x, phi, alpha, bias, iters=20, eps=1e-6, clamp=(-30.0, 30.0),
            alpha_scale=0.01, res_offset=4.0, norm_eps=1e-6)
        y = jnp.tanh(D._hc_read(x, pre)) * gain
        return D._hc_mix(x, y, post, res)

    def loss(*args):       # a fresh function: the tiles are read at trace time
        return jnp.sum((sublayer(*args) * cot).astype(jnp.float32))

    # the value too: the gradient alone does not need the first mix
    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
    args = (x, phi, alpha, bias, gain)
    jax.block_until_ready(step(*args))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(CALLS):
                out = step(*args)
            jax.block_until_ready(out)
        row = {"call_ms": kernel_ms(tmp, None)["dense_ms"]}
        if kernels:
            row.update(kernel_ms(tmp, KERNELS))
    return row


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="4,4096,3584")
    ap.add_argument("--tiles", default="rule,128,256")
    ap.add_argument("--bands", default="16")
    ap.add_argument("--chunks", default="1792")
    ap.add_argument("--dense", action="store_true")
    ap.add_argument("--out", default="chiprun_out/hc_sweep.jsonl")
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        sys.exit("hc_sweep measures device time: it needs a TPU")
    from benchmark import harness
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import hyper_connection as hc

    hbm_peak = harness.peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    n, tokens, c = (int(v) for v in args.shape.split(","))
    need = kernel_bytes(n, tokens, c)
    rule = dict(hc._WANT), hc.VMEM_BUDGET, hc.VMEM_LIMIT
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def emit(row):
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")

    if args.dense:
        pk.set_enabled(False)
        emit({"shape": args.shape, "path": "dense",
              **measure(n, tokens, c, False)})
        pk.set_enabled(None)
    for tile in args.tiles.split(","):
        for band in (int(b) for b in args.bands.split(",")):
            for chunk in (int(v) for v in args.chunks.split(",")):
                hc._WANT = dict(rule[0])
                hc.VMEM_BUDGET, hc.VMEM_LIMIT = rule[1:3]
                if tile != "rule":     # "64", or "mix_bwd=32+read_bwd=32"
                    hc._WANT.update(
                        {p: int(tile) for p in rule[0]} if "=" not in tile
                        else {p: int(v) for p, v in (
                            kv.split("=") for kv in tile.split("+"))})
                    hc.VMEM_BUDGET, hc.VMEM_LIMIT = 96 * 2 ** 20, 110 * 2 ** 20
                hc.BAND, hc.CHUNK = band, chunk
                jax.clear_caches()
                row = {"shape": args.shape, "tile": tile, "band": band,
                       "chunk": chunk,
                       "tiles": {p: hc.token_tile(p, n, tokens, c)
                                 for p in hc._WANT}}
                started = time.time()
                try:
                    got = measure(n, tokens, c, True)
                    row.update(got)
                    row["hbm_pct"] = {
                        k: round(100 * need[k] / hbm_peak / (got[k] / 1e3), 1)
                        for k in KERNELS if got.get(k)}
                except Exception as e:      # Mosaic's refusal is the finding
                    row["error"] = f"{type(e).__name__}: {str(e)[-300:]}"
                row["seconds"] = round(time.time() - started, 1)
                emit(row)


if __name__ == "__main__":
    main()
