"""``DroplessMoE``: one chip's share of a dropless top-k expert layer
(DeepSeek-V3's routing). A token's k choices are k *slots*. The layer routes
every token over ALL experts, sorts the T*k slots by expert, computes the
slots that land on the experts held here with grouped matrix products whose
work follows the number of such slots (no capacity, nothing dropped, nothing
padded to one), and adds each result into its token with the gate's weight.
What the experts held elsewhere would add is left out: under expert
parallelism the chips' parts add up to the whole layer
(tests/test_dropless_moe.py).

Five registered ops, so that the compiled step names each stage:
moe_route (scores), moe_plan (top-k and the sort: integers only),
moe_dispatch (the gather into expert order), moe_experts (the grouped
products), moe_combine (gate weights, the way back, the weighted sum).

The windows. Sorted by expert, the slots of the experts held here are ONE
run of the order: ``count = sum(sizes[first:first + held])`` entries from
``start = sum(sizes[:first])`` on, about T k held / E of the T k. A layer
that holds a share of the experts therefore never touches all T k rows: the
three stages after the plan work on a window of ``window_rows`` rows, R,
known from the shapes, and the same body runs ``ceil(count / R)`` times on
the device (``moe_held``): pass p gathers the tokens of rows ``start + p R``
to ``start + (p + 1) R`` of the order, takes them through the grouped
products with the held experts' sizes clipped to the pass, weighs them and
adds them into a (T, C) float32 sum by token. No pass where no slot landed
here, one more pass where a routing puts more on the held experts than a
window holds: nothing is dropped, clipped or delayed, and there is no
second path. The backward is a loop of the same trip count that makes what
it needs again from the layer's operands and the plan. Every instruction of
a pass runs under the name of its stage; the loops themselves, and what
finds a pass's rows, under ``moe_plan``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn.layer import Layer
from ..ops._base import register as _register
from .env import get_mesh

__all__ = ["DroplessMoE", "route_scores", "sigmoid_route", "plan_slots",
           "window_rows"]

ROW_TILE = 512      # rows of a grouped product's tile (``_gmm_tiling``)


def _fit_tile(size, want):
    """The widest tile of whole 128-lane columns, ``want`` at most, that
    divides ``size``; the size itself where there is none (the tests' small
    shapes)."""
    for t in range(min(want, size) // 128 * 128, 0, -128):
        if size % t == 0:
            return t
    return size


def window_rows(tokens, k, held, experts):
    """R, the rows of one window over the held experts' slots: twice their
    even share of the ``tokens * k`` slots in whole row tiles of the grouped
    products, never more than all the slots. Seeded routers put 0.2-1.5
    times the even share on a chip's experts in every layer but a stack's
    first expert layer, which draws up to 2.7 times (PERF.md section 6): at
    twice the share nearly every call is one pass, a fuller one costs one
    more pass and not the whole layer, and a wider window would cost every
    call its gathers and its sum by token over rows that hold nothing.
    ``R == tokens * k`` (every expert held, half of them and more, one small
    tile) means no window and no loop."""
    rows = tokens * k
    tile = _fit_tile(rows, ROW_TILE)
    return min(rows, max(1, -(-2 * rows * held // (experts * tile))) * tile)


def _keep(x):
    """Mark ``x`` as kept by a recomputed region (framework.recompute). The
    scores and the plan made from them are kept TOGETHER with the products
    over the sorted slots: scores made again need not come out to the bit,
    a near-tie would then sort differently, and kept products would face
    the wrong rows (on the chip that read as 7% of the experts' gradient)."""
    from jax.ad_checkpoint import checkpoint_name

    from ..framework.recompute import RECOMPUTE_KEEP

    return checkpoint_name(x, RECOMPUTE_KEEP)


SCORES = {"sigmoid": jax.nn.sigmoid,
          "softmax": functools.partial(jax.nn.softmax, axis=-1)}


@_register("moe_route")
def route_scores(h, w_gate, *, score="sigmoid"):
    """(T, E) float32 scores of the logits ``h W_g``: ``sigmoid``, one
    independent gate an expert (DeepSeek-V3's ``scoring_func: sigmoid``), or
    ``softmax`` over all E experts (Qwen-MoE's and Mixtral's router)."""
    return _keep(SCORES[score](jnp.matmul(
        h.astype(jnp.float32), w_gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)))


def sigmoid_route(h, w_gate):
    """``route_scores`` with the sigmoid."""
    return route_scores(h, w_gate)


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


def expert_route():
    """Whether the experts' products go through megablox's kernels: where
    the Pallas kernels are on and the program is one chip's (no mesh); the
    dense forms otherwise."""
    from ..ops import pallas as pk

    return pk.enabled() and get_mesh() is None


def _gmm_tiling(m, k, n):
    """(tm, tk, tn) of the grouped product's tiles. A row tile is visited
    once for every group that has rows in it, streams that expert's whole
    (k, n) matrix each time and costs a whole tile's product, so the row tile
    sets what one more slot costs: at 512 rows a visit is bound by the MXU
    and not by the weights' traffic (at 256 the two are level), and the
    fuller experts, which hold most of the slots, run near the array's rate;
    the other two are as wide as VMEM lets them be."""
    return _fit_tile(m, ROW_TILE), _fit_tile(k, 1024), _fit_tile(n, 1024)


# entries of the transposed product's (k, n) tile that VMEM holds four times
# over (the result and the sum it adds to, both double-buffered, and the
# float32 accumulator) beside its operands' row tiles: 1024 x 896, the widest
# any configuration ran with before one asked for 1024 x 1024 and Mosaic
# refused it
TGMM_TILE = 1024 * 896


def _tgmm_tiling(m, k, n):
    """``_gmm_tiling`` for the transposed product, whose result tile is (tk,
    tn) and not (tm, tn): the tile of the longer axis narrows until the pair
    fits ``TGMM_TILE``."""
    tm, tk, tn = _gmm_tiling(m, k, n)
    while tk * tn > TGMM_TILE and max(tk, tn) > 128:
        if k > n:
            tk = _fit_tile(k, tk - 128)
        else:
            tn = _fit_tile(n, tn - 128)
    return tm, tk, tn


def _grouped_swiglu(xs, sizes, w_gate, w_up, w_down, first, interpret):
    """The experts ``first .. first + held - 1`` over their rows of ``xs``
    (sorted by expert; ``sizes`` counts the rows of every expert) through
    megablox's grouped matrix products: the grid's length follows the rows
    held here, and rows of other experts come back as zeros."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    offset = jnp.asarray(first, jnp.int32)
    m, c = xs.shape
    i = w_gate.shape[-1]

    def product(lhs, rhs):      # one tiling for it and, in its vjp, tgmm
        return gmm(lhs, rhs, sizes, lhs.dtype,
                   _tgmm_tiling(m, rhs.shape[1], rhs.shape[2]), offset,
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
    if expert_route():
        from ..ops import pallas as pk

        return _grouped_swiglu(xs, sizes, w_gate, w_up, w_down, first,
                               pk.auto_interpret())
    return _dense_swiglu(xs, sizes, w_gate, w_up, w_down, first)


def _gate_weights(scores, choice, scale, normalize):
    """(T, k) float32: what each of a token's slots weighs in its sum."""
    w = jnp.take_along_axis(scores, choice, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale


@_register("moe_combine")
def _moe_combine(out, scores, choice, order, inv, *, scale, normalize):
    k = choice.shape[-1]
    w = _gate_weights(scores, choice, scale, normalize)
    back = _permute_rows(out, inv, order).astype(jnp.float32)
    back = back.reshape(-1, k, out.shape[-1])
    return jnp.sum(w[..., None] * back, axis=1).astype(out.dtype)


# ---- the windows over the held experts' rows -----------------------------------
def _under(op):
    """The scope of a stage inside ``moe_held``: its registered name."""
    return jax.named_scope(op._op_name)


class _Pass(NamedTuple):
    """One pass's R rows, from ``moe_plan``'s integers and the layer's
    operands. Row r holds a slot of token ``token[r]`` if ``live[r]`` and
    nothing otherwise (the last pass's tail)."""
    token: jax.Array      # (R,)
    live: jax.Array       # (R, 1) bool
    sizes: jax.Array      # (held,): the rows of each held expert, in order
    first_row: jax.Array  # (): the first row's place in the order
    weight: jax.Array     # (R, 1) float32: the slots' gate weights
    xs: jax.Array         # (R, C): the tokens' rows of the hidden state
    gate: jax.Array       # (R, width): the two products into the experts'
    up: jax.Array         # width, rows that hold nothing zeroed


def _grouped_products(rows, dtype):
    """(product, transposed product) over the rows of one pass, by megablox
    or, on backends without the kernel, dense. ``product(lhs, rhs, sizes,
    transpose_rhs)``: (R, n), group j's rows of ``lhs`` (R, k) through
    ``rhs[j]`` ((k, n), or (n, k) transposed); rows past the last group are
    UNWRITTEN by the kernel (they can hold a NaN): the caller takes them out
    by a select, never by a product. ``product_t(lhs, rhs, sizes, into)``:
    ``into`` (held, k, n) plus, for group j, its rows of ``lhs`` (R, k)
    transposed times its rows of ``rhs`` (R, n), in place."""
    if expert_route():
        # the module ``gmm``: the package gives its name to a function
        from jax.experimental.pallas.ops.tpu.megablox.ops import \
            backend as kernels

        from ..ops import pallas as pk

        interpret = pk.auto_interpret()

        def product(lhs, rhs, sizes, transpose_rhs=False):
            n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
            return kernels.gmm(
                lhs, rhs, sizes, dtype, _gmm_tiling(rows, lhs.shape[1], n),
                transpose_rhs=transpose_rhs, interpret=interpret)

        def product_t(lhs, rhs, sizes, into):
            return kernels.tgmm(
                lhs.swapaxes(0, 1), rhs, sizes, into.dtype,
                _tgmm_tiling(rows, lhs.shape[1], rhs.shape[1]),
                existing_out=into, interpret=interpret)

        return product, product_t

    def mine(sizes, j):
        row = jnp.arange(rows)
        end = jnp.cumsum(sizes)[j]
        return ((row >= end - sizes[j]) & (row < end))[:, None]

    def product(lhs, rhs, sizes, transpose_rhs=False):
        out = 0.0
        for j in range(rhs.shape[0]):
            y = jnp.matmul(lhs, rhs[j].T if transpose_rhs else rhs[j])
            out = out + jnp.where(mine(sizes, j), y.astype(jnp.float32), 0.0)
        return out.astype(dtype)

    def product_t(lhs, rhs, sizes, into):
        return into + jnp.stack([
            jnp.matmul(jnp.where(mine(sizes, j), lhs, 0).T, rhs)
            for j in range(into.shape[0])]).astype(into.dtype)

    return product, product_t


def _over_passes(operands, k, first, rows, body, sums):
    """``sums = body(pass, product, product_t, sums)`` for each of the
    ``ceil(count / R)`` passes over the held experts' run of the order, in
    one loop on the device; what the forward's and the backward's passes
    both begin with is made here."""
    h, w, order, _, sizes, w_gate, w_up, _ = operands
    product, product_t = _grouped_products(rows, h.dtype)
    with _under(plan_slots):
        start, mine = jnp.sum(sizes[:first]), \
            sizes[first:first + w_gate.shape[0]]
        count = jnp.sum(mine)
    with _under(_moe_combine):
        flat = w.reshape(-1)

    def one(p, sums):
        with _under(plan_slots):
            row = p * rows + jnp.arange(rows, dtype=jnp.int32)
            # a row past the end of the order lies past the run and is
            # clipped onto a row of the tail. Not a ``dynamic_slice``: that
            # would clamp its START, and the groups would begin off row 0,
            # where megablox's cannot follow
            slot = order[jnp.minimum(start + row, order.shape[0] - 1)]
            token, live = slot // k, (row < count)[:, None]
            ends = jnp.cumsum(mine) - p * rows
            here = jnp.clip(ends, 0, rows) - jnp.clip(ends - mine, 0, rows)
        with _under(_moe_dispatch):
            xs = h[token]
        with _under(_moe_experts):
            gate = jnp.where(live, product(xs, w_gate, here), 0)
            up = jnp.where(live, product(xs, w_up, here), 0)
        with _under(_moe_combine):
            weight = flat[slot][:, None]
        return body(_Pass(token, live, here, start + p * rows, weight, xs,
                          gate, up), product, product_t, sums)

    with _under(plan_slots):
        return jax.lax.fori_loop(0, (count + rows - 1) // rows, one, sums)


def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


def _sum_of_windows(h, w, order, inv, sizes, w_gate, w_up, w_down, k, first,
                    rows):
    """(T, C): the held experts' part of every token's weighted sum, pass by
    pass; ``w`` (T, k) float32 weighs the slots."""
    def add(at, product, _, total):
        with _under(_moe_experts):
            out = product(_swiglu(at.gate, at.up), w_down, at.sizes)
        with _under(_moe_combine):
            weighed = at.weight * out.astype(jnp.float32)
            return total.at[at.token].add(jnp.where(at.live, weighed, 0.0))

    with _under(_moe_combine):
        total = jnp.zeros(h.shape, jnp.float32)
    total = _over_passes((h, w, order, inv, sizes, w_gate, w_up, w_down), k,
                         first, rows, add, total)
    with _under(_moe_combine):
        return total.astype(h.dtype)


_windows = jax.custom_vjp(_sum_of_windows, nondiff_argnums=(8, 9, 10))


def _windows_fwd(*args):
    y = _sum_of_windows(*args)
    # a recomputed region keeps ``y`` beside the scores and the plan, so its
    # second forward runs no pass; the backward's passes make the products
    # again from the operands, which are the region's own or kept
    with _under(_moe_combine):
        y = _keep(y)
    return y, args[:8]


def _windows_bwd(k, first, rows, operands, g):
    """A loop of the forward's trip count: each pass makes its rows and
    products again, and adds into the gradients of the hidden state (by
    token, float32), of the gate weights (in the order's own places: the way
    back to slots is one gather by ``inv`` at the end) and of the three
    expert weights (in place: the kernel's ``existing_out``)."""
    h, w, order, inv, _, w_gate, w_up, w_down = operands

    def add(at, product, product_t, sums):
        dh, dflat, dgate, dup, ddown = sums
        with _under(_moe_experts):
            act, act_vjp = jax.vjp(_swiglu, at.gate, at.up)
            out = product(act, w_down, at.sizes)
        with _under(_moe_combine):
            gy = g[at.token].astype(jnp.float32)
            dflat = jax.lax.dynamic_update_slice(dflat, jnp.where(
                at.live[:, 0], jnp.sum(gy * out.astype(jnp.float32), axis=-1),
                0.0), (at.first_row,))
            dout = jnp.where(at.live, at.weight * gy, 0.0).astype(h.dtype)
        with _under(_moe_experts):
            ddown = product_t(act, dout, at.sizes, ddown)
            dgate_rows, dup_rows = act_vjp(jnp.where(
                at.live, product(dout, w_down, at.sizes, True), 0))
            dgate = product_t(at.xs, dgate_rows, at.sizes, dgate)
            dup = product_t(at.xs, dup_rows, at.sizes, dup)
            dxs = product(dgate_rows, w_gate, at.sizes, True).astype(
                jnp.float32) + product(dup_rows, w_up, at.sizes, True).astype(
                    jnp.float32)
        with _under(_moe_dispatch):
            dh = dh.at[at.token].add(jnp.where(at.live, dxs, 0.0))
        return dh, dflat, dgate, dup, ddown

    with _under(_moe_dispatch):
        dh = jnp.zeros(h.shape, jnp.float32)
    with _under(_moe_combine):
        # a pass writes R places from its first row on: R more than the
        # order's, so that the last pass's tail has where to go
        dflat = jnp.zeros((order.shape[0] + rows,), jnp.float32)
    with _under(_moe_experts):
        sums = (dh, dflat) + tuple(jnp.zeros_like(x)
                                   for x in (w_gate, w_up, w_down))
    dh, dflat, dgate, dup, ddown = _over_passes(operands, k, first, rows,
                                                add, sums)
    with _under(_moe_dispatch):
        dh = dh.astype(h.dtype)
    with _under(_moe_combine):
        dw = dflat[inv].reshape(w.shape)
    return dh, dw, None, None, None, dgate, dup, ddown


_windows.defvjp(_windows_fwd, _windows_bwd)


@_register("moe_held")
def _moe_held(h, scores, choice, order, inv, sizes, w_gate, w_up, w_down, *,
              k, first, rows, scale, normalize):
    """Dispatch, experts and combine of a layer that holds a share of the
    experts, window by window. One op for the tape, because the passes are
    counted on the device; no work of its own: every instruction is under a
    stage's name."""
    with _under(_moe_combine):
        w = _gate_weights(scores, choice, scale, normalize)
    return _windows(h, w, order, inv, sizes, w_gate, w_up, w_down, k, first,
                    rows)


class DroplessMoE(Layer):
    """Top-k of ``num_experts`` routed SwiGLU experts, of which this layer
    holds ``held`` contiguous ones starting at ``first``. ``score`` is what
    the router makes of its logits before the top-k: ``"sigmoid"`` (one gate
    an expert) or ``"softmax"`` (over all ``num_experts``), in float32.

    ``forward(x)`` returns ``(y, load)``: ``y`` is the part of the routed
    result that the experts held here give (all of it when all are held; a
    shared expert is the caller's), and ``load`` the slots each of the
    ``num_experts`` experts was chosen for in this call, as float32 so that
    it can leave a recomputed region beside ``y``. ``e_score_correction_bias``
    steers the choice and not the weight; it is a buffer that no step
    updates.

    A layer that holds a share of the experts works on windows of
    ``window_rows(tokens)`` rows of the sorted slots, as many as the slots on
    its experts fill (``moe_held``); one that holds them all, or so many that
    a window would be all ``tokens * top_k`` rows, runs the three stages over
    all the rows with no loop. Dropless either way, and the same result.
    """

    def __init__(self, d_model, d_expert, num_experts, top_k, first=0,
                 held=None, routed_scale=1.0, normalize=True,
                 weight_attr=None, down_attr=None, name=None,
                 score="sigmoid"):
        super().__init__()
        if score not in SCORES:
            raise ValueError(f"score {score!r}: one of {sorted(SCORES)}")
        self.score = score
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

    def window_rows(self, tokens):
        """Rows of one window of a call over ``tokens`` tokens."""
        return window_rows(tokens, self.top_k, self.held, self.num_experts)

    def forward(self, x):
        from ..ops._base import apply

        lead, c = tuple(x.shape[:-1]), x.shape[-1]
        h = x.reshape([-1, c])
        # the sigmoid's call carries no attribute, as before there was one
        how = {} if self.score == "sigmoid" else {"score": self.score}
        scores = apply("moe_route", h, self.router, **how)
        choice, order, inv, sizes = apply(
            "moe_plan", scores, self.e_score_correction_bias, k=self.top_k)
        weights = (self.experts_gate, self.experts_up, self.experts_down)
        scale = float(self.routed_scale)
        rows = self.window_rows(h.shape[0])
        if rows < h.shape[0] * self.top_k:
            y = apply("moe_held", h, scores, choice, order, inv, sizes,
                      *weights, k=self.top_k, first=self.first, rows=rows,
                      scale=scale, normalize=self.normalize)
        else:
            xs = apply("moe_dispatch", h, order, inv, k=self.top_k)
            out = apply("moe_experts", xs, sizes, *weights, first=self.first)
            y = apply("moe_combine", out, scores, choice, order, inv,
                      scale=scale, normalize=self.normalize)
        return y.reshape(list(lead) + [c]), sizes.astype("float32")
