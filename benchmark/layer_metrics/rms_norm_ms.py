"""Device time a step of the program op ``rms_norm``, forward and backward:
where the time goes that ``layer_norm_ms`` counts in a model with layer
norms (it reads 0 here: this family has none); first device."""
from benchmark import expert_costs

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
reports = expert_costs.has_routed_experts


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.program_op_ms(window, "rms_norm") or None
