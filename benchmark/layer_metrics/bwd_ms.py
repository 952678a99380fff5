"""Device time a step in the backward pass, first device: ops under
``TrainStep``'s ``backward`` scope or traced under a ``transpose(`` (a
``custom_vjp``'s backward kernels among them); ``benchmark/scope_reduce.py``."""
LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.phase_ms(window, "backward")
