"""Compile each cell's step for the described ``v5e:2x2`` topology and print
what one device needs: no chip, no chip time.

    JAX_PLATFORMS=cpu python3 benchmark/tools/size_cells.py [cell ...] [--batches 8,16,24,32]

For each cell (all of BENCHMARK.json's by default) and each candidate batch
(the cell's own by default) it builds the model and ``pt.TrainStep`` on the
CPU, takes the step's pure function, gives every argument a
``ShapeDtypeStruct`` on the described devices (one chip, or the cell's mesh
with the batch over ``data``), lowers it through XLA:TPU and Mosaic and
prints ``memory_analysis()``. This is the run that found cell
bert_base_mlm_512's B (PERF.md section 4). A compile that passes is not a
chip run.
"""
import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding


def compile_step(cell, batch, topo):
    """memory_analysis() and HLO text of the cell's step at ``batch``."""
    from benchmark import generate, harness
    from paddle_tpu.core import random as prandom
    from paddle_tpu.dist.env import MeshGuard
    from paddle_tpu.dist.parallel import param_spec
    from paddle_tpu.ops import pallas as pk

    cfg, traffic = cell["config"], dict(cell["traffic"], batch=batch)
    family = harness.load_module("families", cfg["family"])
    shapes = {n: jnp.zeros(s, jnp.bfloat16)
              for n, s, _ in family.reference.param_specs(cfg)}
    pk.set_enabled(True)           # route the call sites as a TPU backend does
    pk.auto_interpret = lambda: False  # and lower the kernels through Mosaic
    _, step = family.build(cfg, shapes, None)   # plain TrainStep, on the CPU
    rows = generate.pool(dict(traffic, pool_batches=1), cfg["vocab_size"], 0)
    opt = step.optimizer
    args = ([p._data for p in step._trainable],
            [b._data for b in step._buffers],
            {p.name: opt._accumulators[p.name] for p in step._trainable},
            jnp.float32(opt.get_lr()), prandom.next_key(),
            [np.asarray(a) for a in rows], step._scaler_state)
    axes = cell.get("mesh")
    if axes:
        n = int(np.prod(list(axes.values())))
        mesh = jax.sharding.Mesh(
            np.array(topo.devices[:n]).reshape(tuple(axes.values())),
            tuple(axes))
        by_param = {p.name: NamedSharding(mesh, param_spec(p, mesh))
                    for p in step._trainable}
        rep, rows_sh = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

        def struct(a, sh):
            return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sh)

        structs = (
            [struct(a, by_param[p.name])
             for p, a in zip(step._trainable, args[0])],
            [struct(a, rep) for a in args[1]],
            {k: {s: struct(v, by_param[k] if v.shape else rep)
                 for s, v in slots.items()} for k, slots in args[2].items()},
            struct(args[3], rep), struct(args[4], rep),
            [struct(a, rows_sh) for a in args[5]],
            jax.tree_util.tree_map(lambda a: struct(a, rep), args[6]))
        guard = MeshGuard(mesh)
    else:
        one = SingleDeviceSharding(topo.devices[0])
        structs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=one), args)
        mesh = guard = None
    fn = jax.jit(step._make_pure(), donate_argnums=(0, 1, 2))
    if mesh is None:
        compiled = fn.lower(*structs).compile()
    else:
        with guard, mesh:
            compiled = fn.lower(*structs).compile()
    return compiled.memory_analysis(), compiled.as_text()


def main():
    from jax.experimental import topologies

    from benchmark import harness, hlo_count

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--batches", default="")
    args = ap.parse_args()
    man = harness.manifest()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    for name in args.cells or [w["name"] for w in man["workloads"]]:
        cell = harness.load_cell(name, man)
        batches = [int(b) for b in args.batches.split(",") if b] or \
            [cell["traffic"]["batch"]]
        for batch in batches:
            try:
                mem, text = compile_step(cell, batch, topo)
            except Exception as e:  # the compiler's refusal is the result
                print(f"{name} B={batch}: REFUSED {type(e).__name__}: "
                      f"{str(e)[:300]}", flush=True)
                continue
            need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
            print(f"{name} B={batch}: arguments "
                  f"{mem.argument_size_in_bytes / 1e9:.3f} GB + temporaries "
                  f"{mem.temp_size_in_bytes / 1e9:.3f} GB = {need / 1e9:.3f} "
                  f"GB per device (outputs {mem.output_size_in_bytes / 1e9:.3f}"
                  f", aliased {mem.alias_size_in_bytes / 1e9:.3f}); "
                  f"pallas_calls={harness.mosaic_calls(text)} collective MB="
                  f"{ {k: v / 1e6 for k, v in hlo_count.collective_bytes(text).items()} }",
                  flush=True)


if __name__ == "__main__":
    main()
