"""Device time a step of the linear-attention sublayers whole: every op whose
path holds the program scope ``linear_attn`` (``HybridMoEBlock`` opens it
round a gated-delta-rule sublayer: its projections, short convolutions,
gates, the chunked rule, the gated norm and the output projection), forward
and backward; first device. It reads 0 where the compiled step has no such
op, which is every cell whose configuration has no linear-attention layer:
so it has no ``reports`` rule and no ``workloads`` list, as ``mtp_ms``."""
LAYER = "linear attention"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_paths

    return scope_paths.scope_ms(window, "linear_attn")
