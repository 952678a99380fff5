"""The routed experts' grouped products' share of their roofline: the FLOPs
the slots that landed on the experts held here need
(``benchmark/expert_costs.py``: 2 x 3 x C x I a slot forward, twice that
backward, the recomputed forward not counted; slots from the program's
counter, mean of the traced steps) over the bf16 peak, over the device time of
the op ``moe_experts``. Compute-bound by count; at a few hundred slots an
expert the weights' traffic is what binds."""
from benchmark import expert_costs

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
reports = expert_costs.has_routed_experts


def read(window):
    import jax

    from benchmark import harness, scope_reduce

    counts = expert_costs.held_slots(window)
    if counts is None:
        return None
    cfg = window.cell["config"]
    return expert_costs.roofline_pct(
        counts, cfg["hidden_size"], cfg["moe_intermediate_size"],
        harness.peaks(jax.devices()[0].device_kind)["bf16_flops_per_s"],
        scope_reduce.program_op_ms(window, "moe_experts"))
