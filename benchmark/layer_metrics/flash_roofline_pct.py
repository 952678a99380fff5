"""The flash kernels' share of their roofline: the FLOPs the ``flash_*``
calls of a step must do (``benchmark/kernel_costs.py``, FlashAttention-2's
convention, half for a ``_causal`` name) over the bf16 peak, over the device
time they took. Compute-bound, so the peak is FLOP/s."""
LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
KERNELS = "flash_"


def reports(cell):
    """Where the cell file lists these kernels among those its compiled step
    must hold (``kernels``): there the calls are there to be read."""
    return KERNELS in cell.get("kernels", ())


def read(window):
    from benchmark import kernel_costs

    return kernel_costs.window_roofline_pct(
        window, KERNELS, kernel_costs.flash_flops, "bf16_flops_per_s")
