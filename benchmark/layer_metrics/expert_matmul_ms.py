"""Device time a step of the program op ``moe_experts``: the grouped matrix
products of the routed experts held here (gate, up, down and the SwiGLU
between), forward and backward (and the forward made again under recompute,
which the backward phase holds); first device."""
from benchmark import expert_costs

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
reports = expert_costs.has_routed_experts


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.program_op_ms(window, "moe_experts") or None
