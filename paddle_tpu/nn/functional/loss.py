"""Loss functions.

Refs: python/paddle/fluid/layers/loss.py (cross_entropy,
softmax_with_cross_entropy, square_error_cost, warpctc, ...),
paddle/fluid/operators/{softmax_with_cross_entropy_op,bce_loss_op,
smooth_l1_loss_op,kldiv_loss_op,warpctc_op,...}.

All losses compute in float32 internally (bf16-safe on TPU) and support the
reference's reduction modes. CTC is a pure lax.scan alpha recursion — no
cuDNN/warpctc handoff; the whole loss fuses into the training step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops._base import register, apply, unwrap

__all__ = [
    "cross_entropy", "softmax_with_cross_entropy", "nll_loss", "kl_div",
    "binary_cross_entropy", "binary_cross_entropy_with_logits", "mse_loss",
    "l1_loss", "smooth_l1_loss", "margin_ranking_loss", "cosine_embedding_loss",
    "ctc_loss", "square_error_cost", "log_loss", "sigmoid_focal_loss",
    "hinge_embedding_loss", "triplet_margin_loss", "npair_loss",
]


def _reduce(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


# -- cross entropy ----------------------------------------------------------


@register("cross_entropy_hard")
def _ce_hard(logits, label, weight, *, axis, ignore_index, reduction,
             use_softmax, label_smoothing):
    from ...ops import pallas as pk

    specs = pk.softmax_ce_route(logits.shape, label.shape, weight is not None,
                                axis, use_softmax, label_smoothing)
    if specs is not None:
        lab = label if label.ndim == 1 else jnp.squeeze(label, axis=-1)
        loss = pk.run(pk.softmax_cross_entropy, specs, (logits, lab),
                      int(ignore_index))
        if reduction == "mean":
            valid = (lab != ignore_index).astype(jnp.float32)
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1e-12)
        return _reduce(loss, reduction)
    lf = logits.astype(jnp.float32)
    n_cls = lf.shape[axis]
    logp = jax.nn.log_softmax(lf, axis=axis) if use_softmax else jnp.log(
        jnp.maximum(lf, 1e-12))
    label = label.astype(jnp.int32)
    if label.ndim == logp.ndim:  # (..., 1) trailing dim, fluid-style
        label = jnp.squeeze(label, axis=axis)
    valid = label != ignore_index
    safe = jnp.where(valid, label, 0)
    picked = jnp.take_along_axis(
        jnp.moveaxis(logp, axis, -1), safe[..., None], axis=-1)[..., 0]
    if label_smoothing > 0.0:
        mean_logp = jnp.mean(jnp.moveaxis(logp, axis, -1), axis=-1)
        picked = (1.0 - label_smoothing) * picked + label_smoothing * mean_logp
    loss = -picked
    if weight is not None:
        w = jnp.take(weight.astype(jnp.float32), safe, axis=0)
    else:
        w = jnp.ones_like(loss)
    w = jnp.where(valid, w, 0.0)
    loss = loss * w
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(jnp.sum(w), 1e-12)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


@register("cross_entropy_soft")
def _ce_soft(logits, label, *, axis, reduction, use_softmax, label_smoothing):
    lf = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(lf, axis=axis) if use_softmax else jnp.log(
        jnp.maximum(lf, 1e-12))
    lab = label.astype(jnp.float32)
    if label_smoothing > 0.0:
        lab = lab * (1.0 - label_smoothing) + label_smoothing / lab.shape[axis]
    loss = -jnp.sum(lab * logp, axis=axis)
    return _reduce(loss, reduction)


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    if soft_label:
        return apply("cross_entropy_soft", input, label, axis=axis,
                     reduction=reduction, use_softmax=bool(use_softmax),
                     label_smoothing=float(label_smoothing))
    return apply("cross_entropy_hard", input, label, weight, axis=axis,
                 ignore_index=int(ignore_index), reduction=reduction,
                 use_softmax=bool(use_softmax),
                 label_smoothing=float(label_smoothing))


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    """Fluid-style: per-example loss with trailing singleton dim kept."""
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    from ...ops.manipulation import unsqueeze

    loss = unsqueeze(loss, axis if axis < 0 else axis)
    if return_softmax:
        from ...ops.activation import softmax

        return loss, softmax(logits, axis=axis)
    return loss


@register("nll_loss")
def _nll(logp, label, weight, *, ignore_index, reduction):
    label = label.astype(jnp.int32)
    valid = label != ignore_index
    safe = jnp.where(valid, label, 0)
    lp = jnp.moveaxis(logp.astype(jnp.float32), 1, -1) if logp.ndim > 2 else logp.astype(jnp.float32)
    picked = jnp.take_along_axis(lp, safe[..., None], axis=-1)[..., 0]
    w = jnp.take(weight.astype(jnp.float32), safe, axis=0) if weight is not None \
        else jnp.ones_like(picked)
    w = jnp.where(valid, w, 0.0)
    loss = -picked * w
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(jnp.sum(w), 1e-12)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    return apply("nll_loss", input, label, weight,
                 ignore_index=int(ignore_index), reduction=reduction)


@register("kl_div")
def _kl_div(logp, target, *, reduction):
    t = target.astype(jnp.float32)
    loss = t * (jnp.log(jnp.maximum(t, 1e-12)) - logp.astype(jnp.float32))
    if reduction == "batchmean":
        return jnp.sum(loss) / logp.shape[0]
    return _reduce(loss, reduction)


def kl_div(input, label, reduction="mean", name=None):
    return apply("kl_div", input, label, reduction=reduction)


# -- regression -------------------------------------------------------------


@register("mse_loss")
def _mse(x, y, *, reduction):
    return _reduce(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)), reduction)


def mse_loss(input, label, reduction="mean", name=None):
    return apply("mse_loss", input, label, reduction=reduction)


@register("l1_loss")
def _l1(x, y, *, reduction):
    return _reduce(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)), reduction)


def l1_loss(input, label, reduction="mean", name=None):
    return apply("l1_loss", input, label, reduction=reduction)


@register("smooth_l1_loss")
def _smooth_l1(x, y, *, reduction, delta):
    d = x.astype(jnp.float32) - y.astype(jnp.float32)
    ad = jnp.abs(d)
    loss = jnp.where(ad < delta, 0.5 * d * d / delta, ad - 0.5 * delta)
    return _reduce(loss, reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    return apply("smooth_l1_loss", input, label, reduction=reduction,
                 delta=float(delta))


@register("square_error_cost")
def _sec(x, y):
    return jnp.square(x - y)


def square_error_cost(input, label):
    return apply("square_error_cost", input, label)


@register("log_loss")
def _log_loss(x, y, *, epsilon):
    xf = x.astype(jnp.float32)
    return -y * jnp.log(xf + epsilon) - (1.0 - y) * jnp.log(1.0 - xf + epsilon)


def log_loss(input, label, epsilon=1e-4, name=None):
    return apply("log_loss", input, label, epsilon=float(epsilon))


# -- binary -----------------------------------------------------------------


@register("bce")
def _bce(x, y, w, *, reduction):
    xf = jnp.clip(x.astype(jnp.float32), 1e-12, 1.0 - 1e-7)
    loss = -(y * jnp.log(xf) + (1.0 - y) * jnp.log(1.0 - xf))
    if w is not None:
        loss = loss * w.astype(jnp.float32)
    return _reduce(loss, reduction)


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    return apply("bce", input, label, weight, reduction=reduction)


@register("bce_logits")
def _bce_logits(x, y, w, pos_w, *, reduction):
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    # stable: max(x,0) - x*y + log(1+exp(-|x|)); pos_weight scales the y term
    log_sig = jax.nn.log_sigmoid(xf)
    log_sig_neg = jax.nn.log_sigmoid(-xf)
    if pos_w is not None:
        loss = -(pos_w * yf * log_sig + (1.0 - yf) * log_sig_neg)
    else:
        loss = -(yf * log_sig + (1.0 - yf) * log_sig_neg)
    if w is not None:
        loss = loss * w.astype(jnp.float32)
    return _reduce(loss, reduction)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    return apply("bce_logits", logit, label, weight, pos_weight,
                 reduction=reduction)


@register("sigmoid_focal_loss")
def _focal(x, y, norm, *, alpha, gamma, reduction):
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    p = jax.nn.sigmoid(xf)
    ce = -(yf * jax.nn.log_sigmoid(xf) + (1.0 - yf) * jax.nn.log_sigmoid(-xf))
    p_t = p * yf + (1.0 - p) * (1.0 - yf)
    a_t = alpha * yf + (1.0 - alpha) * (1.0 - yf)
    loss = a_t * ((1.0 - p_t) ** gamma) * ce
    if norm is not None:
        loss = loss / norm
    return _reduce(loss, reduction)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    return apply("sigmoid_focal_loss", logit, label, normalizer,
                 alpha=float(alpha), gamma=float(gamma), reduction=reduction)


# -- ranking / margin -------------------------------------------------------


@register("margin_ranking_loss")
def _margin_rank(x, y, label, *, margin, reduction):
    loss = jnp.maximum(0.0, -label * (x - y) + margin)
    return _reduce(loss, reduction)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    return apply("margin_ranking_loss", input, other, label,
                 margin=float(margin), reduction=reduction)


@register("cosine_embedding_loss")
def _cos_embed(x1, x2, label, *, margin, reduction):
    dot = jnp.sum(x1 * x2, axis=-1)
    n1 = jnp.sqrt(jnp.maximum(jnp.sum(x1 * x1, axis=-1), 1e-12))
    n2 = jnp.sqrt(jnp.maximum(jnp.sum(x2 * x2, axis=-1), 1e-12))
    cos = dot / (n1 * n2)
    loss = jnp.where(label > 0, 1.0 - cos, jnp.maximum(0.0, cos - margin))
    return _reduce(loss, reduction)


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean",
                          name=None):
    return apply("cosine_embedding_loss", input1, input2, label,
                 margin=float(margin), reduction=reduction)


@register("hinge_embedding_loss")
def _hinge_embed(x, label, *, margin, reduction):
    loss = jnp.where(label > 0, x, jnp.maximum(0.0, margin - x))
    return _reduce(loss, reduction)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    return apply("hinge_embedding_loss", input, label, margin=float(margin),
                 reduction=reduction)


@register("triplet_margin_loss")
def _triplet(a, p, n, *, margin, p_norm, epsilon, swap, reduction):
    def dist(u, v):
        return jnp.sum(jnp.abs(u - v + epsilon) ** p_norm, axis=-1) ** (1.0 / p_norm)

    d_ap = dist(a, p)
    d_an = dist(a, n)
    if swap:
        d_an = jnp.minimum(d_an, dist(p, n))
    return _reduce(jnp.maximum(0.0, d_ap - d_an + margin), reduction)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    return apply("triplet_margin_loss", input, positive, negative,
                 margin=float(margin), p_norm=float(p), epsilon=float(epsilon),
                 swap=bool(swap), reduction=reduction)


@register("npair_loss")
def _npair(anchor, positive, labels, *, l2_reg):
    sim = jnp.matmul(anchor, positive.T)
    lab = labels.reshape(-1)
    target = (lab[:, None] == lab[None, :]).astype(jnp.float32)
    target = target / jnp.sum(target, axis=1, keepdims=True)
    logp = jax.nn.log_softmax(sim, axis=1)
    ce = -jnp.mean(jnp.sum(target * logp, axis=1))
    reg = l2_reg * (jnp.mean(jnp.sum(anchor * anchor, axis=1))
                    + jnp.mean(jnp.sum(positive * positive, axis=1))) * 0.25
    return ce + reg


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    return apply("npair_loss", anchor, positive, labels, l2_reg=float(l2_reg))


# -- CTC --------------------------------------------------------------------


@register("ctc_loss")
def _ctc(log_probs, labels, input_lengths, label_lengths, *, blank, reduction,
         norm_by_times):
    """CTC forward (alpha) recursion in log space, batched over B.

    log_probs: (T, B, C) log-softmax scores; labels: (B, S) int.
    The recursion runs as a lax.scan over T — static shapes, fully fused;
    this is the TPU-correct replacement for warpctc (ref: warpctc_op.cc).
    """
    T, B, C = log_probs.shape
    S = labels.shape[1]
    lp = log_probs.astype(jnp.float32)
    labels = labels.astype(jnp.int32)
    neg_inf = jnp.float32(-1e30)

    # extended label sequence with interleaved blanks: length 2S+1
    ext = jnp.full((B, 2 * S + 1), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(labels)
    ext_len = 2 * label_lengths.astype(jnp.int32) + 1

    # transition mask: alpha[s] may come from s, s-1, and s-2 when
    # ext[s] != blank and ext[s] != ext[s-2]
    same_as_prev2 = jnp.concatenate(
        [jnp.ones((B, 2), bool), ext[:, 2:] == ext[:, :-2]], axis=1)
    can_skip = (ext != blank) & (~same_as_prev2)

    def emit(t_lp, s_idx):
        # gather per-position emission scores: (B, 2S+1)
        return jnp.take_along_axis(t_lp, ext, axis=1)

    init = jnp.full((B, 2 * S + 1), neg_inf)
    init = init.at[:, 0].set(lp[0, jnp.arange(B), ext[:, 0]])
    init = init.at[:, 1].set(jnp.where(ext_len > 1,
                                       lp[0, jnp.arange(B), ext[:, 1]], neg_inf))

    def step(alpha, t_lp):
        shift1 = jnp.concatenate([jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
        shift2 = jnp.concatenate([jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
        shift2 = jnp.where(can_skip, shift2, neg_inf)
        merged = jnp.logaddexp(jnp.logaddexp(alpha, shift1), shift2)
        new_alpha = merged + emit(t_lp, None)
        return new_alpha, None

    # sequences shorter than T stop at their own input_length: keep per-step
    # alphas and select at t = input_length - 1
    def step_keep(alpha, t_lp):
        new_alpha, _ = step(alpha, t_lp)
        return new_alpha, new_alpha

    _, alphas = jax.lax.scan(step_keep, init, lp[1:])
    alphas = jnp.concatenate([init[None], alphas], axis=0)  # (T, B, 2S+1)
    t_idx = jnp.clip(input_lengths.astype(jnp.int32) - 1, 0, T - 1)
    final = alphas[t_idx, jnp.arange(B)]  # (B, 2S+1)
    last = jnp.take_along_axis(final, (ext_len - 1)[:, None], axis=1)[:, 0]
    last2 = jnp.take_along_axis(final, jnp.maximum(ext_len - 2, 0)[:, None],
                                axis=1)[:, 0]
    loss = -jnp.logaddexp(last, jnp.where(ext_len > 1, last2, neg_inf))
    if norm_by_times:
        loss = loss / jnp.maximum(input_lengths.astype(jnp.float32), 1.0)
    if reduction == "mean":
        return jnp.mean(loss / jnp.maximum(label_lengths.astype(jnp.float32), 1.0))
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    return apply("ctc_loss", log_probs, labels, input_lengths, label_lengths,
                 blank=int(blank), reduction=reduction,
                 norm_by_times=bool(norm_by_times))
