"""Device time a step of the program op ``sdpa``
(``nn.functional.scaled_dot_product_attention``), forward and backward, flash
kernels or the dense path alike: the op's name is on both
(``core.dispatch.apply``'s scope); first device."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.program_op_ms(window, "sdpa")
