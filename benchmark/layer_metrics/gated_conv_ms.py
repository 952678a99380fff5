"""Device time a step of the program op ``gated_short_conv`` alone
(``nn.functional.gated_short_conv``: the two gates and the causal depthwise
convolution between them, without the projections round it), forward and
backward; first device: what a kernel for it would have to beat. Read where
the configuration states a short convolution (``conv_L_cache``); nothing
where the compiled step has no such op."""
from benchmark import conv_costs

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"

reports = conv_costs.has_short_conv


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.program_op_ms(window, "gated_short_conv") or None
