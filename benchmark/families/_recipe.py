"""The training recipe both families share, built through the program's
normal path: bfloat16 parameters, AdamW with float32 master weights, a
global-norm clip, one fused step (sharded over the mesh when there is one)."""
import math

import numpy as np


def load_weights(model, weights, name_map):
    """Give the program the benchmark's seeded weights (reference names) under
    the program's own structured names; every parameter must be covered."""
    from paddle_tpu.core.tensor import Tensor

    state = {prog: Tensor(weights[ref], _internal=True)
             for prog, ref in name_map.items()}
    missing, unexpected = model.set_state_dict(state)
    if missing or unexpected:
        raise RuntimeError(f"weights do not cover the model: missing "
                           f"{missing[:4]}, unexpected {unexpected[:4]}")


def train_step(model, loss_fn, recipe, mesh_axes):
    import paddle_tpu as pt
    from paddle_tpu import distributed as dist
    from paddle_tpu import optim

    if recipe["optimizer"] != "AdamW" or \
            recipe["parameter_dtype"] != "bfloat16":
        raise ValueError(f"recipe not known to this family: {recipe}")
    opt = optim.AdamW(
        parameters=model.parameters(), learning_rate=recipe["learning_rate"],
        beta1=recipe["beta1"], beta2=recipe["beta2"],
        epsilon=recipe["epsilon"], weight_decay=recipe["weight_decay"],
        multi_precision=recipe["master_weights"] == "float32",
        grad_clip=optim.ClipGradByGlobalNorm(recipe["clip_global_norm"]))
    if not mesh_axes:
        return pt.TrainStep(model, opt, loss_fn)
    import jax

    chips = math.prod(mesh_axes.values())
    mesh = dist.init_mesh(dict(mesh_axes), devices=jax.devices()[:chips])
    return dist.DistributedTrainStep(model, opt, loss_fn, mesh=mesh)


def n_params(specs):
    return sum(math.prod(shape) for _, shape, _ in specs)


def palm_flops_per_position(n, layers, hidden, length):
    """PaLM's appendix B: 6 N for the matrix products of forward and
    backward, 12 * layers * hidden * L for attention over the whole length.
    It counts full attention for a causal model too, and nothing that is
    recomputed."""
    return 6.0 * n + 12.0 * layers * hidden * length


def token_rows_step_flops(flops_per_position):
    """A family's ``step_flops(cfg, traffic)``, its count of one step's work
    over one batch of the cell's mix, where a row carries nothing but
    ``seq_len`` token positions (padding included: the device computes it):
    batch x seq_len x ``flops_per_position(cfg, seq_len)``. A family whose
    rows carry more (patches through a tower) writes its own and counts that
    from the mix's own keys."""
    def step_flops(cfg, traffic):
        return traffic["batch"] * traffic["seq_len"] * \
            flops_per_position(cfg, traffic["seq_len"])
    return step_flops


def full_rows(pool):
    """Every position of every row is a token trained on."""
    ids = pool[0]
    return np.full(len(ids), ids.shape[1], np.int64)
