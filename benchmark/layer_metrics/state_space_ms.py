"""Device time a step of the state-space sublayers whole: every op whose path
holds the program scope ``state_space`` (``SSMHybridBlock`` opens it round a
Mamba-2 sublayer: its projections, the convolution, the step size, the
chunked scan, the gated norm), forward and backward; first device. It reads 0
where the compiled step has no such op, which is every cell whose
configuration has no state-space layer: so it has no ``reports`` rule and no
``workloads`` list, as ``mtp_ms``."""
LAYER = "state-space layer"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_paths

    return scope_paths.scope_ms(window, "state_space")
