"""The sliding-window kernels' share of their roofline: the FLOPs the
``swa_*`` calls of a step must do (``benchmark/window_costs.py``: the band's
scores, ``w L - w (w - 1) / 2`` a head, at ``kernel_costs``'s widths a call)
over the bf16 peak, over the device time they took. Compute-bound, so the
peak is FLOP/s. It reads 0 where the compiled step holds no such call, which
is every cell whose configuration has no windowed layer: so it has no
``reports`` rule and no ``workloads`` list, as ``mtp_ms``."""
LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
KERNELS = "swa_"


def read(window):
    from benchmark import kernel_costs, window_costs

    share = kernel_costs.window_roofline_pct(
        window, KERNELS, window_costs.swa_flops, "bf16_flops_per_s")
    return 0.0 if share is None else share
