"""What every plain reference shares: seeded weights, float32 building
blocks, and three steps of clip + AdamW written from the published
equations. Nothing here imports the program under test.

Precision. ``precision="float32"`` is the reference proper: every matrix
product under ``jax.default_matmul_precision("highest")``. ``precision="fp8"``
is the control of the benchmark's contract, the nearest precision below the
bfloat16 the configurations state, as fp8 training recipes have it: every
matrix product takes both operands rounded to float8_e4m3 with one scale per
tensor, and in the backward pass the incoming gradient rounded to float8_e5m2
against the same rounded operands. It stands in the program's place to show
that a cell's limits catch it. ``precision="bfloat16"`` is a witness, not a
control: every matrix product, forward and backward, takes its operands
rounded to the bfloat16 the configurations state and sums in float32, so it
shows what that precision alone does to a number, whatever the program does
(``tools/calibrate.py --witness-seeds``; no run and no test holds a limit
against it).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def seed_key(seed):
    """A PRNG key from any whole number up to 2**63 (the driver's seeds pass
    2**31, which one signed 32-bit word does not hold)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def init_weights(specs, seed, dtype=jnp.bfloat16):
    """All leaves from the seed, on the device, in one jitted call, in the
    type the program trains in. ``specs`` is ``[(name, shape, init)]`` with
    init ``("normal", std)``, ``("ones",)`` or ``("zeros",)``."""
    specs = tuple((n, tuple(s), tuple(i)) for n, s, i in specs)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, init) in enumerate(specs):
            if init[0] == "normal":
                w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * init[1]
            elif init[0] == "ones":
                w = jnp.ones(shape, jnp.float32)
            elif init[0] == "zeros":
                w = jnp.zeros(shape, jnp.float32)
            else:
                raise ValueError(f"unknown init {init!r} for {name}")
            out[name] = w.astype(dtype)
        return out

    return make(seed_key(seed))


# ---- building blocks -------------------------------------------------------
def _round_fp8(x, dtype, top):
    """x rounded to an fp8 format with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _f32_matmul(a, b):
    return jnp.matmul(a, b, precision="highest")


@jax.custom_vjp
def _fp8_matmul(a, b):
    return _f32_matmul(_round_fp8(a, jnp.float8_e4m3fn, E4M3_MAX),
                       _round_fp8(b, jnp.float8_e4m3fn, E4M3_MAX))


def _fp8_fwd(a, b):
    qa = _round_fp8(a, jnp.float8_e4m3fn, E4M3_MAX)
    qb = _round_fp8(b, jnp.float8_e4m3fn, E4M3_MAX)
    return _f32_matmul(qa, qb), (qa, qb)


def _fp8_bwd(operands, dy):
    _, vjp = jax.vjp(_f32_matmul, *operands)
    return vjp(_round_fp8(dy, jnp.float8_e5m2, E5M2_MAX))


_fp8_matmul.defvjp(_fp8_fwd, _fp8_bwd)


def _bf16_matmul(a, b):
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def matmul_of(precision):
    """``mm(a, b)`` for the stated precision (see the module's docstring)."""
    if precision == "float32":
        return _f32_matmul
    if precision == "bfloat16":
        return _bf16_matmul
    if precision == "fp8":
        return _fp8_matmul
    raise ValueError(f"unknown precision {precision!r}")


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def gelu_erf(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def attention(q, k, v, mask, mm):
    """softmax(q k^T / sqrt(d) where mask) v over (B, H, L, d); ``mask`` is
    boolean and broadcasts against (B, H, L, L)."""
    s = mm(q, jnp.swapaxes(k, -1, -2)) / math.sqrt(q.shape[-1])
    s = jnp.where(mask, s, -jnp.inf)
    return mm(jax.nn.softmax(s, axis=-1), v)


def split_heads(x, heads):
    b, l, d = x.shape
    return x.reshape(b, l, heads, d // heads).transpose(0, 2, 1, 3)


def merge_heads(x):
    b, h, l, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, l, h * d)


def ce_sum(logits, labels, ignore_index=None):
    """Sum of -log softmax(logits)[label] over the rows whose label is not
    ``ignore_index``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = jnp.ones(labels.shape, bool) if ignore_index is None \
        else labels != ignore_index
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0))


# ---- three steps of clip + AdamW -------------------------------------------
SAMPLE_PER_LEAF = 65536


def sample_index(specs):
    """{name: flat indices}: the entries of each leaf whose first gradient
    is compared entry by entry; every entry of a leaf that has at most
    SAMPLE_PER_LEAF, else that many drawn from the leaf's place in ``specs``
    (the same for every seed, so that every run compares the same entries)."""
    out = {}
    for i, (name, shape, _) in enumerate(specs):
        size = math.prod(shape)
        out[name] = np.arange(size) if size <= SAMPLE_PER_LEAF else \
            np.sort(np.random.default_rng(i).integers(0, size,
                                                      SAMPLE_PER_LEAF))
    return out


def train_steps(loss_part, denominators, params0, batches, recipe, index, *,
                micro=2, precision="float32", devices=None):
    """Follow the program's first ``len(batches)`` steps.

    ``loss_part(params, rows, denoms, mm)`` is the part of the step's loss
    that the rows of one micro-batch contribute, the batch-wide
    ``denominators(batch)`` held fixed; the step's loss and gradient are the
    sums over the micro-batches. Where the cell has several ``devices`` each
    takes one micro-batch at a time (the same function, mapped over a leading
    axis that is spread over them), so that following a four-chip cell's
    global batch takes no longer than following one chip's; rows that do not
    fill such a group follow in micro-batches of at most ``micro`` rows that
    every device computes alike. Returns the loss of each step; of the first
    gradient as AdamW receives it (after the global-norm clip) the norm per
    leaf and the entries that ``index`` (``sample_index``) names; and the norm
    per leaf of the parameters' change over all steps.

    Where the bytes live, so that a chip's share of a large model can be
    followed beside nothing but one micro-batch's activations: a device holds
    12 bytes a parameter (the running float32 parameters, the gradient summed
    so far and one micro-batch's gradient), the host 12 more as numpy (the
    first parameters and AdamW's two moments). Clip and AdamW run leaf by
    leaf: a leaf's moments go up, the new ones come down, and the first
    step's readings are taken in that same pass. ``params0`` is emptied: each
    seeded leaf leaves it as its float32 copy is made.
    """
    mm = matmul_of(precision)
    b1, b2 = recipe["beta1"], recipe["beta2"]
    lr, eps, wd = recipe["learning_rate"], recipe["epsilon"], \
        recipe["weight_decay"]
    clip = recipe["clip_global_norm"]
    devices = list(devices or jax.devices()[:1])
    mesh = jax.sharding.Mesh(np.array(devices), ("micro",))
    everywhere = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    spread = jax.sharding.NamedSharding(mesh,
                                        jax.sharding.PartitionSpec("micro"))
    group = micro * len(devices)

    @jax.jit
    def part(params, rows, denoms):
        loss, grads = jax.vmap(lambda r: jax.value_and_grad(loss_part)(
            params, r, denoms, mm))(rows)
        return jnp.sum(loss, 0), jax.tree_util.tree_map(
            lambda g: jnp.sum(g, 0), grads)

    @functools.partial(jax.jit, donate_argnums=0)
    def add(acc, new):
        return jax.tree_util.tree_map(jnp.add, acc, new)

    @jax.jit
    def clip_scale(grads):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
        return clip / jnp.maximum(norm, clip)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update_leaf(p, m, v, g, scale, t, entries):
        g = g * scale
        new_m = b1 * m + (1 - b1) * g
        new_v = b2 * v + (1 - b2) * g * g
        mhat = new_m / (1 - b1 ** t)
        vhat = new_v / (1 - b2 ** t)
        new_p = p - lr * mhat / (jnp.sqrt(vhat) + eps) - lr * wd * p
        return new_p, new_m, new_v, jnp.sqrt(jnp.sum(jnp.square(g))), \
            g.reshape(-1)[entries]

    @jax.jit
    def change_norm(p, start):
        return jnp.sqrt(jnp.sum(jnp.square(p - start)))

    def micro_batches(batch):
        """(rows on the devices, their sharding): whole groups spread one
        micro-batch a device, then what is left ``micro`` rows at a time."""
        n, lo = len(batch[0]), 0
        while lo < n:
            whole = n - lo >= group
            take = group if whole else min(micro, n - lo)
            shape = (len(devices), micro) if whole else (1, take)
            yield jax.device_put(tuple(
                np.asarray(a[lo:lo + take]).reshape(shape + a.shape[1:])
                for a in batch), spread if whole else everywhere)
            lo += take

    with jax.default_matmul_precision("highest"):
        params, first, m, v = {}, {}, {}, {}
        for k in list(params0):
            params[k] = jax.device_put(
                params0.pop(k).astype(jnp.float32), everywhere)
            first[k] = np.asarray(params[k])
            m[k], v[k] = np.zeros_like(first[k]), np.zeros_like(first[k])
        losses, grad_norms, grad_sample = [], {}, {}
        for t, batch in enumerate(batches, start=1):
            denoms = denominators(batch)
            loss = grads = None
            for rows in micro_batches(batch):
                l, g = part(params, rows, denoms)
                loss = l if loss is None else loss + l
                grads = g if grads is None else add(grads, g)
                del g
                # a dispatch allocates its outputs: without this wait the
                # host runs ahead and a gradient a micro-batch piles up
                jax.block_until_ready(grads)
            losses.append(float(loss))
            scale = clip_scale(grads)
            for k in list(grads):
                # zeros too go up from the host: made on the device they
                # cost a compile a shape, 1-2 s of a 20 s reference (PR 26)
                up = jax.device_put((m[k], v[k]), everywhere)
                params[k], *down = update_leaf(
                    params[k], *up, grads.pop(k), scale, jnp.float32(t),
                    index[k])
                del up
                m[k], v[k], norm, sample = jax.device_get(down)
                if t == 1:
                    grad_norms[k], grad_sample[k] = float(norm), sample
        delta = {k: float(change_norm(p, jax.device_put(first[k], everywhere)))
                 for k, p in params.items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sample": grad_sample, "delta_norms": delta}
