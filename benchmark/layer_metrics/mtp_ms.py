"""Device time a step of the multi-token-prediction module: every op whose
path holds the program scope ``mtp`` (``LatentMoE.forward_mtp`` and
``latent_moe_loss`` open it round the module's two norms, its projection,
its expert block, its final norm, head and cross-entropy), forward and
backward; first device. It reads 0 where the compiled step has no such op,
which is every cell whose configuration has no ``num_nextn_predict_layers``:
so it has no ``reports`` rule and no ``workloads`` list (PERF.md section 7
says which file a rule would need edited)."""
LAYER = "multi-token prediction"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_paths

    return scope_paths.scope_ms(window, "mtp")
