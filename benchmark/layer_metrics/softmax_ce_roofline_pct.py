"""The fused softmax-CE kernels' share of their roofline: the bytes the
``softmax_ce_*`` calls of a step must move across HBM once
(``benchmark/kernel_costs.py``) over the HBM peak, over the device time they
took. Memory-bound, so the peak is bytes/s."""
LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def reports(cell):
    return bool(cell.get("min_pallas_calls"))


def read(window):
    from benchmark import kernel_costs

    return kernel_costs.window_roofline_pct(
        window, "softmax_ce_", kernel_costs.hbm_bytes, "hbm_bytes_per_s")
