"""Device time a step of the sliding-window attention sublayers whole: every
op whose path holds the program scope ``window_attn`` (``LagunaMoEBlock``
opens it round a windowed sublayer: its projections, rotary, the windowed
``sdpa``, the head gate and the output projection), forward and backward;
first device. It reads 0 where the compiled step has no such op, which is
every cell whose configuration has no windowed layer: so it has no
``reports`` rule and no ``workloads`` list, as ``mtp_ms``."""
LAYER = "window attention"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_paths

    return scope_paths.scope_ms(window, "window_attn")
