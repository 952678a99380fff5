"""The fused softmax-CE kernels' share of their roofline: the bytes the
``softmax_ce_*`` calls of a step must move across HBM once
(``benchmark/kernel_costs.py``) over the HBM peak, over the device time they
took. Memory-bound, so the peak is bytes/s."""
LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
KERNELS = "softmax_ce_"


def reports(cell):
    """Where the cell file lists these kernels among those its compiled step
    must hold (``kernels``): there the calls are there to be read."""
    return KERNELS in cell.get("kernels", ())


def read(window):
    from benchmark import kernel_costs

    return kernel_costs.window_roofline_pct(
        window, KERNELS, kernel_costs.hbm_bytes, "hbm_bytes_per_s")
