"""Device time a step of the program ops ``cross_entropy_hard`` and
``cross_entropy_soft``, forward and backward, the fused kernel or the dense
path alike (the head's matmul is a ``linear`` or ``matmul`` and not in it);
first device."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.program_op_ms(window, "cross_entropy_hard",
                                      "cross_entropy_soft")
