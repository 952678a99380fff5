"""What the routed experts' grouped matrix products must do, beside
``kernel_costs.py`` (which only a ``benchmark`` PR may change): operations of
the algorithm for the slots that landed on the experts held, not of an
implementation.

A slot is one token at one of its chosen experts. A SwiGLU expert of hidden
size ``C`` and width ``I`` takes a slot through three products (gate and up
``C x I``, down ``I x C``): ``2 * 3 * C * I`` FLOPs forward, twice that
backward (each product's two gradients). A recomputed forward is not
counted. The slots are the program's own count
(``LatentMoE.expert_load_counts``, through the family's ``expert_load``), the
mean over the traced steps, so the share is of work that was really asked
for: slots of experts held elsewhere cost nothing here.
"""


def has_routed_experts(cell):
    """The rule of every reader of the routed experts' metrics."""
    return bool(cell["config"].get("n_routed_experts"))


def slot_flops(hidden, width):
    """FLOPs of one slot through one SwiGLU expert, forward and backward."""
    return 3.0 * (2.0 * 3.0 * hidden * width)


def held_slots(window):
    """(steps, expert layers, experts held) slot counts of the traced steps,
    or None where the family has no such counter or the model is gone."""
    ask = getattr(window.family, "expert_load", None)
    return ask(window.trace.steps) if ask and window.trace else None


def load_max_over_mean(counts):
    """Fullest held expert over the mean held expert, a layer and a step,
    averaged: 1.0 is perfect balance over the experts held."""
    mean = counts.mean(axis=-1)
    return float((counts.max(axis=-1) / mean.clip(min=1e-9)).mean())


def roofline_pct(counts, hidden, width, peak_flops_per_s, device_ms):
    """100 x (the least seconds the chip could take for the mean step's
    held slots) / (the device seconds ``moe_experts`` took a step)."""
    if not device_ms:
        return None
    slots = float(counts.sum()) / counts.shape[0]
    return 100.0 * slots * slot_flops(hidden, width) / peak_flops_per_s / \
        (device_ms / 1e3)
