"""The comparison that decides ``correct`` for a training cell.

The program's first three steps, taken through the window's own call and
feed, against the plain float32 reference following the same three batches
from the same seeded weights. Six numbers, each with a limit of its own:

``loss_gap_1``      |loss - reference| / reference before any update: forward
                    parity. At seeded weights the loss is ln(vocabulary) in
                    any precision, so its limit guards against rows left out
                    of the batch, not against precision.
``loss_gap_2/3``    the same after one and two updates: gradient and
                    optimizer parity.
``grad_norm_gap``   worst leaf of | ||g|| - ||g_ref|| | / max(||g_ref||,
                    median leaf's ||g_ref||), g the first gradient as AdamW
                    receives it (after the clip), read from the program's
                    first moment after one step: m1 = (1 - beta1) g.
``grad_rel_err``    ||g - g_ref|| / ||g_ref|| over a fixed sample of the first
                    gradient's entries (at most 65,536 a leaf, all leaves
                    together). The norm of a leaf barely feels rounding noise
                    (it adds in quadrature), so this is the number a lower
                    precision moves: the one the fp8 control must fail.
``delta_norm_gap``  the same form for the norm of each leaf's change over the
                    three steps (from the float32 master weights). AdamW's
                    first steps move every weight by about the learning rate
                    whatever the gradient's size, so this guards against a
                    step that returns its state unchanged. Leaves whose
                    gradient is null by construction (a key bias: softmax
                    ignores a shift of its scores) are left out, because
                    AdamW scales their rounding noise up to full-sized steps
                    of no defined direction: those whose reference gradient
                    norm is under NULL_GRADIENT of the median leaf's.

Each cell's file under ``workloads/`` holds its ``limits``, set from chip
readings by the rule of the benchmark's contract; PERF.md section 2 gives the
readings for each.
"""
import math
import statistics

import numpy as np


NULL_GRADIENT = 1e-4


def worst_leaf_gap(got, want, leaves=None):
    """max over ``leaves`` (all) of |got - want| / max(want, median want)."""
    floor = statistics.median(want.values())
    worst, where = 0.0, None
    for name, w in want.items():
        if leaves is not None and name not in leaves:
            continue
        gap = abs(got[name] - w) / max(w, floor, 1e-30)
        if not gap <= worst:  # a NaN gap takes the maximum
            worst, where = gap, name
    return worst, where


def compare(got, want):
    """``got`` and ``want`` as ``reference._common.train_steps`` returns them
    (losses, grad_norms, grad_sample, delta_norms). Returns {number: (value, worst leaf)}."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), start=1):
        out[f"loss_gap_{i}"] = (abs(a - b) / abs(b), None)
    out["grad_norm_gap"] = worst_leaf_gap(got["grad_norms"],
                                          want["grad_norms"])
    diff = sum(float(np.sum(np.square(np.float64(got["grad_sample"][k]) - w)))
               for k, w in want["grad_sample"].items())
    size = sum(float(np.sum(np.square(np.float64(w))))
               for w in want["grad_sample"].values())
    out["grad_rel_err"] = (math.sqrt(diff / size), None)
    floor = NULL_GRADIENT * statistics.median(want["grad_norms"].values())
    out["delta_norm_gap"] = worst_leaf_gap(
        got["delta_norms"], want["delta_norms"],
        {k for k, g in want["grad_norms"].items() if g > floor})
    return out


def judge(numbers, limits, say=print):
    """Print every number beside its limit; True when all are inside."""
    ok = True
    for name, (value, where) in numbers.items():
        limit = limits[name]
        inside = math.isfinite(value) and value <= limit
        ok = ok and inside
        say(f"check {name} value={value:.6g} limit={limit:.6g} "
            f"{'ok' if inside else 'OUTSIDE'}"
            + (f" worst_leaf={where}" if where else ""))
    return ok
