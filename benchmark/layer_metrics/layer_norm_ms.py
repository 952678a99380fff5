"""Device time a step of the program ops ``layer_norm`` and
``layer_norm_noaffine``, forward and backward; first device."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.program_op_ms(window, "layer_norm",
                                      "layer_norm_noaffine")
