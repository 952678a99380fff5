"""The fullest routed expert held here over the mean one, by the program's
own counter of token-slots an expert (``LatentMoE.expert_load_counts``), a
layer and a step, averaged over the traced steps: 1.0 is perfect balance;
the grouped products' row tiles and the slowest chip of an expert-parallel
group follow the fullest."""
from benchmark import expert_costs

LAYER = "expert layer"
UNIT = "ratio"
MOVES = "tokens_per_s_per_chip"
reports = expert_costs.has_routed_experts


def read(window):
    counts = expert_costs.held_slots(window)
    return None if counts is None else expert_costs.load_max_over_mean(counts)
