"""The flash kernels' share of their roofline: the FLOPs the ``flash_*``
calls of a step must do (``benchmark/kernel_costs.py``, FlashAttention-2's
convention, half for a ``_causal`` name) over the bf16 peak, over the device
time they took. Compute-bound, so the peak is FLOP/s."""
LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def reports(cell):
    return bool(cell.get("min_pallas_calls"))


def read(window):
    from benchmark import kernel_costs

    return kernel_costs.window_roofline_pct(
        window, "flash_", kernel_costs.flash_flops, "bf16_flops_per_s")
