"""Mixture-of-Experts with expert parallelism.

TPU-native analog of the reference's incubate MoE (expert-parallel FFN with
all-to-all dispatch): GShard-style top-k gating with capacity, dispatch /
combine einsums, and an all_to_all over the 'expert' mesh axis so each
device runs only its local experts. Everything is dense einsums + one
collective — exactly the layout the MXU and ICI want.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from ..nn.layer import Layer
from ..nn import functional as F
from .env import get_mesh

__all__ = ["top2_gating", "moe_dispatch_combine", "MoEMLP", "DroplessMoE",
           "sigmoid_route", "plan_slots"]


def top2_gating(logits, capacity):
    """GShard top-2 gating. logits: (N, E). Returns combine (N, E, C) and
    dispatch mask (N, E, C) plus aux load-balancing loss."""
    N, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    g1_idx = jnp.argmax(probs, axis=-1)
    g1 = jnp.take_along_axis(probs, g1_idx[:, None], axis=-1)[:, 0]
    probs_wo1 = probs * (1.0 - jax.nn.one_hot(g1_idx, E))
    g2_idx = jnp.argmax(probs_wo1, axis=-1)
    g2 = jnp.take_along_axis(probs_wo1, g2_idx[:, None], axis=-1)[:, 0]

    # aux loss: mean prob per expert * fraction dispatched per expert
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(g1_idx, E), axis=0)
    aux = jnp.sum(me * ce) * E

    def positions(idx):
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based position
        return onehot, pos

    oh1, pos1 = positions(g1_idx)
    # second choice queues behind all first choices
    count1 = jnp.sum(oh1, axis=0, keepdims=True)
    oh2, pos2 = positions(g2_idx)
    pos2 = pos2 + count1 * oh2

    keep1 = (pos1 > 0) & (pos1 <= capacity)
    keep2 = (pos2 > 0) & (pos2 <= capacity)

    denom = g1 + g2 + 1e-9
    w1 = jnp.where(jnp.any(keep1, -1), g1 / denom, 0.0)
    w2 = jnp.where(jnp.any(keep2, -1), g2 / denom, 0.0)

    def scatter(onehot, pos, keep, w):
        slot = jax.nn.one_hot(pos - 1, capacity, dtype=jnp.float32)  # (N,E,C)
        return w[:, None, None] * onehot[..., None] * slot * keep[..., None]

    combine = scatter(oh1, pos1, keep1, w1) + scatter(oh2, pos2, keep2, w2)
    dispatch = (combine > 0).astype(logits.dtype)
    return combine.astype(logits.dtype), dispatch, aux


def moe_dispatch_combine(x, gate_logits, expert_fn, capacity_factor=2.0,
                         axis_name=None):
    """Dense dispatch→experts→combine. x: (N, D); gate_logits: (N, E).
    ``expert_fn(expert_inputs)`` maps (E, C, D) -> (E, C, D_out); when
    axis_name is set it runs under expert-parallel all_to_all."""
    N, E = gate_logits.shape
    capacity = max(1, int(capacity_factor * N / E))
    combine, dispatch, aux = top2_gating(gate_logits, capacity)
    expert_in = jnp.einsum("nd,nec->ecd", x, dispatch)  # (E, C, D)
    expert_out = expert_fn(expert_in)
    out = jnp.einsum("ecd,nec->nd", expert_out, combine.astype(expert_out.dtype))
    return out, aux


def _moe_mlp_kernel(xa, gw, w1, b1, w2, b2, *, use_ep, axis, activation,
                    capacity_factor):
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
           "silu": jax.nn.silu}[activation]
    xt = xa.reshape(-1, xa.shape[-1])
    logits = xt @ gw

    def dense_expert(ein):  # (E, C, D)
        h = act(jnp.einsum("ecd,edh->ech", ein, w1) + b1)
        return jnp.einsum("ech,ehd->ecd", h, w2) + b2

    if not use_ep:
        out, aux = moe_dispatch_combine(xt, logits, dense_expert,
                                        capacity_factor)
        return out.reshape(xa.shape[:-1] + (out.shape[-1],)), aux

    m = get_mesh()

    def shard_fn(xt_l, logits_l, w1_l, b1_l, w2_l, b2_l):
        # xt_l: this shard's tokens; w*_l: this shard's local experts
        def ep_expert(ein):  # (E, C, D): local tokens grouped by expert
            ein = jax.lax.all_to_all(ein, axis, split_axis=0,
                                     concat_axis=1, tiled=True)
            # now (E_local, C*n, D): every shard holds ALL tokens for its
            # local experts
            h = act(jnp.einsum("ecd,edh->ech", ein, w1_l) + b1_l)
            out = jnp.einsum("ech,ehd->ecd", h, w2_l) + b2_l
            return jax.lax.all_to_all(out, axis, split_axis=1,
                                      concat_axis=0, tiled=True)

        out, aux = moe_dispatch_combine(xt_l, logits_l, ep_expert,
                                        capacity_factor)
        return out, jax.lax.pmean(aux, axis)

    tok_spec = P(axis, None)
    exp_spec = P(axis, None, None)
    out, aux = jax.shard_map(
        shard_fn, mesh=m,
        in_specs=(tok_spec, tok_spec, exp_spec, exp_spec, exp_spec, exp_spec),
        out_specs=(tok_spec, P()))(xt, logits, w1, b1, w2, b2)
    return out.reshape(xa.shape[:-1] + (out.shape[-1],)), aux


from ..ops._base import register as _register  # noqa: E402

_register("moe_mlp")(_moe_mlp_kernel)


class MoEMLP(Layer):
    """Expert-parallel FFN block (ref: incubate MoE layer).

    Experts stacked on the leading axis of the weights and sharded over the
    'expert' mesh axis; dispatch runs through all_to_all inside shard_map.
    Falls back to dense (single-shard) execution without a mesh.
    """

    def __init__(self, d_model, d_hidden, num_experts, capacity_factor=2.0,
                 ep_axis="expert", activation="gelu", name=None):
        super().__init__()
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.ep_axis = ep_axis
        self.activation = activation
        self.gate = self.create_parameter((d_model, num_experts))
        self.w1 = self.create_parameter((num_experts, d_model, d_hidden))
        self.b1 = self.create_parameter((num_experts, 1, d_hidden), is_bias=True)
        self.w2 = self.create_parameter((num_experts, d_hidden, d_model))
        self.b2 = self.create_parameter((num_experts, 1, d_model), is_bias=True)
        for p, spec in ((self.w1, P(ep_axis, None, None)),
                        (self.b1, P(ep_axis, None, None)),
                        (self.w2, P(ep_axis, None, None)),
                        (self.b2, P(ep_axis, None, None))):
            p.sharding_spec = spec
        self.aux_loss = None

    def forward(self, x):
        from ..ops._base import apply

        mesh = get_mesh()
        ep = self.ep_axis
        use_ep = mesh is not None and ep in getattr(mesh, "shape", {}) and \
            mesh.shape[ep] > 1
        out, aux = apply("moe_mlp", x, self.gate, self.w1, self.b1, self.w2,
                         self.b2, use_ep=use_ep, axis=ep,
                         activation=self.activation,
                         capacity_factor=self.capacity_factor)
        self.aux_loss = aux
        return out


# ---------------------------------------------------------------------------
# Dropless top-k experts, for the share of the experts one chip holds
# ---------------------------------------------------------------------------
# A token's k choices are k *slots*. The layer routes every token over ALL
# experts, sorts the T*k slots by expert, computes the slots that land on the
# experts held here with grouped matrix products whose work follows the
# number of such slots (no capacity, nothing dropped, nothing padded to one),
# and adds each result into its token with the gate's weight. What the experts
# held elsewhere would add is left out: under expert parallelism the chips'
# parts add up to the whole layer (tests/test_dropless_moe.py).
#
# Five registered ops, so that the compiled step names each stage:
# moe_route (scores), moe_plan (top-k and the sort: integers only),
# moe_dispatch (the gather into expert order), moe_experts (the grouped
# products), moe_combine (gate weights, the way back, the weighted sum).

def _keep(x):
    """Mark ``x`` as kept by a recomputed region (framework.recompute). The
    scores and the plan made from them are kept TOGETHER with the products
    over the sorted slots: scores made again need not come out to the bit,
    a near-tie would then sort differently, and kept products would face
    the wrong rows (on the chip that read as 7% of the experts' gradient)."""
    from jax.ad_checkpoint import checkpoint_name

    from ..framework.recompute import RECOMPUTE_KEEP

    return checkpoint_name(x, RECOMPUTE_KEEP)


@_register("moe_route")
def sigmoid_route(h, w_gate):
    """``sigmoid(h W_g)`` in float32: (T, E) scores, one independent gate an
    expert (DeepSeek-V3's ``scoring_func: sigmoid``)."""
    return _keep(jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), w_gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)))


@_register("moe_plan")
def plan_slots(scores, bias, *, k):
    """Who goes where. ``choice`` (T, k): the top-k experts of ``scores +
    bias`` (the bias steers the choice only, ``noaux_tc``); ``order``
    (T*k,): the slots (token-major) sorted by expert; ``inv``: its inverse
    permutation; ``sizes`` (E,): slots an expert. All int32."""
    _, choice = jax.lax.top_k(scores + bias.astype(scores.dtype), k)
    flat = choice.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    # a second sort and a compare-and-sum, not scatters: both run in
    # parallel on the chip, where a scatter of T*k indices is serial
    inv = jnp.argsort(order).astype(jnp.int32)
    experts = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    sizes = jnp.sum((flat[:, None] == experts[None, :]).astype(jnp.int32),
                    axis=0)
    return tuple(_keep(a) for a in (choice.astype(jnp.int32), order, inv,
                                    sizes))


@jax.custom_vjp
def _permute_rows(x, index, inverse):
    """``x[index]`` for a permutation ``index``: its transpose is the gather
    by ``inverse``, where jax's own would be a scatter-add."""
    return x[index]


def _permute_fwd(x, index, inverse):
    return x[index], inverse


def _permute_bwd(inverse, g):
    return g[inverse], None, None


_permute_rows.defvjp(_permute_fwd, _permute_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_slots(h, order, inv, k):
    return h[order // k]


def _gather_fwd(h, order, inv, k):
    return h[order // k], inv


def _gather_bwd(k, inv, g):
    # back in token-major slot order, a token's k slots add up
    slots = g[inv].astype(jnp.float32).reshape(-1, k, g.shape[-1])
    return jnp.sum(slots, axis=1).astype(g.dtype), None, None


_gather_slots.defvjp(_gather_fwd, _gather_bwd)


def _gmm_tiling(m, k, n):
    """(tm, tk, tn) of the grouped product's tiles. A row tile is visited
    once for every group that has rows in it, streams that expert's whole
    (k, n) matrix each time and costs a whole tile's product, so the row tile
    sets what one more slot costs: at 512 rows a visit is bound by the MXU
    and not by the weights' traffic (at 256 the two are level), and the
    fuller experts, which hold most of the slots, run near the array's rate;
    the other two are as wide as VMEM lets them be."""
    def fit(size, want):
        # the widest tile of whole 128-lane columns that divides the size;
        # the size itself where there is none (the tests' small shapes)
        for t in range(min(want, size) // 128 * 128, 0, -128):
            if size % t == 0:
                return t
        return size

    return fit(m, 512), fit(k, 1024), fit(n, 1024)


def _grouped_swiglu(xs, sizes, w_gate, w_up, w_down, first, interpret):
    """The experts ``first .. first + held - 1`` over their rows of ``xs``
    (sorted by expert; ``sizes`` counts the rows of every expert) through
    megablox's grouped matrix products: the grid's length follows the rows
    held here, and rows of other experts come back as zeros."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    offset = jnp.asarray(first, jnp.int32)
    m, c = xs.shape
    i = w_gate.shape[-1]

    def product(lhs, rhs):
        return gmm(lhs, rhs, sizes, lhs.dtype,
                   _gmm_tiling(m, rhs.shape[1], rhs.shape[2]), offset,
                   None, False, interpret)

    # the two products into the experts' width are kept by a recomputed
    # region: 2 x T k x width, small beside what making them again costs;
    # the SwiGLU between is made again
    gate, up = _keep(product(xs, w_gate)), _keep(product(xs, w_up))
    return product(jax.nn.silu(gate) * up, w_down)


def _dense_swiglu(xs, sizes, w_gate, w_up, w_down, first):
    """The same by a loop over the experts held, every row through every
    one of them and masked: the path of backends without the kernel."""
    starts = jnp.cumsum(sizes) - sizes
    row = jnp.arange(xs.shape[0])
    out = jnp.zeros(xs.shape[:1] + w_down.shape[-1:], jnp.float32)
    for j in range(w_gate.shape[0]):
        mine = (row >= starts[first + j]) & \
            (row < starts[first + j] + sizes[first + j])
        y = jnp.matmul(jax.nn.silu(jnp.matmul(xs, w_gate[j])) *
                       jnp.matmul(xs, w_up[j]), w_down[j])
        out = out + jnp.where(mine[:, None], y.astype(jnp.float32), 0.0)
    return out.astype(xs.dtype)


@_register("moe_dispatch")
def _moe_dispatch(h, order, inv, *, k):
    return _gather_slots(h, order, inv, k)


@_register("moe_experts")
def _moe_experts(xs, sizes, w_gate, w_up, w_down, *, first):
    from ..ops import pallas as pk

    if pk.enabled() and get_mesh() is None:
        return _grouped_swiglu(xs, sizes, w_gate, w_up, w_down, first,
                               pk.auto_interpret())
    return _dense_swiglu(xs, sizes, w_gate, w_up, w_down, first)


@_register("moe_combine")
def _moe_combine(out, scores, choice, order, inv, *, scale, normalize):
    k = choice.shape[-1]
    w = jnp.take_along_axis(scores, choice, axis=-1)        # (T, k) float32
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * scale
    back = _permute_rows(out, inv, order).astype(jnp.float32)
    back = back.reshape(-1, k, out.shape[-1])
    return jnp.sum(w[..., None] * back, axis=1).astype(out.dtype)


class DroplessMoE(Layer):
    """Top-k of ``num_experts`` sigmoid-routed SwiGLU experts, of which this
    layer holds ``held`` contiguous ones starting at ``first``.

    ``forward(x)`` returns ``(y, load)``: ``y`` is the part of the routed
    result that the experts held here give (all of it when all are held; a
    shared expert is the caller's), and ``load`` the slots each of the
    ``num_experts`` experts was chosen for in this call, as float32 so that
    it can leave a recomputed region beside ``y``. ``e_score_correction_bias``
    steers the choice and not the weight; it is a buffer that no step
    updates.
    """

    def __init__(self, d_model, d_expert, num_experts, top_k, first=0,
                 held=None, routed_scale=1.0, normalize=True,
                 weight_attr=None, down_attr=None, name=None):
        super().__init__()
        held = num_experts if held is None else held
        if not 0 <= first <= first + held <= num_experts:
            raise ValueError(f"experts {first}..{first + held - 1} of "
                             f"{num_experts}")
        self.num_experts, self.top_k = num_experts, top_k
        self.first, self.held = first, held
        self.routed_scale, self.normalize = routed_scale, normalize
        self.router = self.create_parameter((d_model, num_experts),
                                            attr=weight_attr)
        self.register_buffer(
            "e_score_correction_bias",
            Tensor(jnp.zeros((num_experts,), jnp.float32), _internal=True),
            persistable=False)
        self.experts_gate = self.create_parameter(
            (held, d_model, d_expert), attr=weight_attr)
        self.experts_up = self.create_parameter(
            (held, d_model, d_expert), attr=weight_attr)
        self.experts_down = self.create_parameter(
            (held, d_expert, d_model), attr=down_attr or weight_attr)

    def forward(self, x):
        from ..ops._base import apply

        lead, c = tuple(x.shape[:-1]), x.shape[-1]
        h = x.reshape([-1, c])
        scores = apply("moe_route", h, self.router)
        choice, order, inv, sizes = apply(
            "moe_plan", scores, self.e_score_correction_bias, k=self.top_k)
        xs = apply("moe_dispatch", h, order, inv, k=self.top_k)
        out = apply("moe_experts", xs, sizes, self.experts_gate,
                    self.experts_up, self.experts_down, first=self.first)
        y = apply("moe_combine", out, scores, choice, order, inv,
                  scale=float(self.routed_scale), normalize=self.normalize)
        return y.reshape(list(lead) + [c]), sizes.astype("float32")
