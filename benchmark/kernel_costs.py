"""What a named kernel call must do, from the shapes on its own HLO line: the
operations and bytes of the algorithm, not of this implementation, so that a
kernel's share of its roofline is the least time the chip could take over the
time the kernel took.

The compiled text prints a Mosaic call as

    %flash_fwd_causal.12 = (bf16[192,1024,64]{..}, f32[192,1024,1]{..})
        custom-call(..), custom_call_target="tpu_custom_call",
        operand_layout_constraints={bf16[192,1024,64]{2,1,0}, ..}, ..

so the result type and the operands' types are on the line, and the cell's
configuration is not asked. Kernel names are the program's ``pallas_call``
``name=`` (``ops/pallas/``, PR 24).

- **flash attention** (FlashAttention-2's convention), with heads of ``Dqk``
  for queries and keys and ``Dv`` for values (latent attention has 192
  against 128; where they are equal this is the familiar ``4 * BH * Lq * Lk *
  D``): the forward is two matmuls over the scores, QK^T over ``Dqk`` and PV
  over ``Dv``, ``2 * BH * Lq * Lk * (Dqk + Dv)`` FLOPs; the backward five (the
  scores again, dQ and dK over ``Dqk``; dP and dV over ``Dv``), however the
  kernels split or repeat them. This program splits it in two calls that each
  compute the scores and dP again: ``flash_bwd_dq`` is given dQ and half of
  the two shared matmuls, ``Dqk + (Dqk + Dv) / 2`` in the bracket (1.0 of a
  forward at equal widths), ``flash_bwd_dkv`` dK, dV and the other half,
  ``Dqk + Dv + (Dqk + Dv) / 2`` (1.5). A ``_causal`` name needs only the
  ``Lk * (Lk + 1) / 2`` scores on and under the diagonal: times
  ``(Lk + 1) / (2 * Lk)``.
- **softmax cross-entropy** is bound by bytes: every operand and every result
  crosses HBM once (forward: the logits, labels, loss and ``lse``; backward:
  the logits and their gradient, labels, ``lse`` and the loss's gradient).
"""
import re

from benchmark import hlo_count

CAUSAL = "_causal"


def _flash_widths(dqk, dv):
    """Per score, the widths each call's matmuls contract or produce."""
    shared = (dqk + dv) / 2.0   # half of the scores (Dqk) and dP (Dv) again
    return {"flash_fwd": dqk + dv, "flash_bwd_dq": dqk + shared,
            "flash_bwd_dkv": dqk + dv + shared}


def _braced(text, start):
    """The text between the brace at ``start`` and its partner."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "{") - (text[i] == "}")
        if depth == 0:
            return text[start + 1:i]
    raise ValueError("unbalanced braces")


def call_types(line):
    """(operands' types, result type) of a custom call's HLO line, as text."""
    key = "operand_layout_constraints="
    at = line.find(key)
    if at < 0:
        raise ValueError("no operand_layout_constraints on the line")
    return (_braced(line, at + len(key)),
            line.partition(" = ")[2].partition(" custom-call(")[0])


def flash_flops(kernel, line):
    """FLOPs one call of a ``flash_*`` kernel must do. Operands are q, k, v
    (then dO, lse, delta in the backward): ``[BH, Lq, Dqk]``, ``[BH, Lk,
    Dqk]``, ``[BH, Lk, Dv]``; the value head's width is the ``v`` operand's."""
    causal = kernel.endswith(CAUSAL)
    q, k, v = [[int(d) for d in dims.split(",")] for dims in
               re.findall(r"\[([\d,]+)\]", call_types(line)[0])[:3]]
    (bh, lq, dqk), lk, dv = q, k[1], v[2]
    flops = 2.0 * bh * lq * lk * _flash_widths(dqk, dv)[
        kernel[:-len(CAUSAL)] if causal else kernel]
    return flops * (lk + 1) / (2.0 * lk) if causal else flops


def hbm_bytes(kernel, line):
    """Bytes of every operand and result of a call, each counted once."""
    return sum(sum(hlo_count.array_bytes(t)) for t in call_types(line))


def roofline_pct(table, lines, prefix, cost, peak_per_s):
    """100 x (the least seconds the chip could take for the calls of the
    kernels named ``prefix*``, at ``peak_per_s`` of what ``cost(kernel, line)``
    counts) / (the device seconds they took); None where the step holds no
    such kernel. ``table`` is ``scope_reduce.rows``', ``lines`` its
    instruction lines."""
    need = took = 0.0
    for row in table:
        if row.kernel and row.kernel.startswith(prefix):
            need += row.calls * cost(row.kernel, lines[row.instruction])
            took += row.ms / 1e3
    return 100.0 * need / peak_per_s / took if took else None


def window_roofline_pct(window, prefix, cost, peak):
    """``roofline_pct`` of a traced window's first device against the
    ``peak`` (a key of ``benchmark/peaks.json``) of the device it ran on."""
    import jax

    from benchmark import harness, scope_reduce

    table, lines = scope_reduce.of_window(window)
    if table is None:
        return None
    return roofline_pct(table, lines, prefix, cost,
                        harness.peaks(jax.devices()[0].device_kind)[peak])
