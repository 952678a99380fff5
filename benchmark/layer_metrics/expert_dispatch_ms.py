"""Device time a step of everything round the routed experts' products:
``moe_route`` (sigmoid scores), ``moe_plan`` (top-k, the sort by expert, the
counts), ``moe_dispatch`` (the gather into expert order) and ``moe_combine``
(gate weights, the way back, the weighted sum); forward and backward; first
device."""
from benchmark import expert_costs

LAYER = "expert layer"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
reports = expert_costs.has_routed_experts


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.program_op_ms(
        window, "moe_route", "moe_plan", "moe_dispatch", "moe_combine") or None
