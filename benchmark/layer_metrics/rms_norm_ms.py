"""Device time a step of the program op ``rms_norm``, forward and backward:
where the time goes that ``layer_norm_ms`` counts in a model with layer
norms; first device. Read where the configuration states an RMS norm
(``rms_norm_eps``), whatever else it has."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def reports(cell):
    return "rms_norm_eps" in cell["config"]


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.program_op_ms(window, "rms_norm") or None
