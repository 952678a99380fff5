"""What a windowed attention call must do, beside ``kernel_costs.py`` (which
only a ``benchmark`` PR may change): operations of the algorithm, from the
call's own HLO line and name, not of an implementation.

The program names a sliding-window kernel ``swa_<call>_w<window>``
(``ops/pallas/flash_attention.py``: ``swa_fwd_w512``, ``swa_bwd_dq_w512``,
``swa_bwd_dkv_w512``), with the operands q, k, v ``[BH, L, D]``, ``[BH, L,
D]``, ``[BH, L, Dv]`` first on its line as a ``flash_*`` call has them. Query
i sees the keys ``0 <= i - j < w``: the first ``w`` queries see ``1 .. w``
keys, the others ``w``, so a head has ``w L - w (w - 1) / 2`` scores (all ``L
(L + 1) / 2`` of a causal call where ``w >= L``). A score costs what
``kernel_costs`` says a flash call's costs (FlashAttention-2's convention:
``2 (Dqk + Dv)`` forward; the backward's five products split over the two
calls that each compute the scores and dP again), so a windowed call is that
call's widths over the band's scores and not over half of L^2.
"""
import re

from benchmark import kernel_costs

_NAME = re.compile(r"^swa_(\w+)_w(\d+)$")


def band_scores(length, window):
    """Scores a head of a row of ``length`` has under a window of ``window``."""
    w = min(window, length)
    return w * length - w * (w - 1) / 2.0


def swa_flops(kernel, line):
    """FLOPs one call of a ``swa_*`` kernel must do."""
    call, window = _NAME.match(kernel).groups()
    q, _, v = [[int(d) for d in dims.split(",")] for dims in
               re.findall(r"\[([\d,]+)\]", kernel_costs.call_types(line)[0])[:3]]
    (bh, length, dqk), dv = q, v[2]
    return 2.0 * bh * band_scores(length, int(window)) * \
        kernel_costs._flash_widths(dqk, dv)["flash_" + call]
