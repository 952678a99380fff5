"""The gated short convolution's share of its roofline: the least time the
chip could take for what a step's convolutions must move
(``benchmark/conv_costs.py``: the three streams, the result and their
gradients once each, from the configuration's keys and the mix's rows, over
the HBM rate; the bytes bind) over the device time of the program op
``gated_short_conv``, forward and backward. Read where the configuration
states a short convolution (``conv_L_cache``); nothing where the compiled
step has no such op."""
from benchmark import conv_costs

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"

reports = conv_costs.has_short_conv


def read(window):
    import jax

    from benchmark import harness, scope_reduce

    took_ms = scope_reduce.program_op_ms(window, "gated_short_conv")
    if not took_ms:
        return None
    need_s = conv_costs.gated_conv_roofline_s(
        window.cell["config"], window.cell["traffic"],
        harness.peaks(jax.devices()[0].device_kind))
    return 100.0 * need_s / (took_ms / 1e3)
