"""Device time a step under ``TrainStep``'s ``forward`` scope (the loss
function's ops), first device: ``benchmark/scope_reduce.py``'s phase rule over
the compiled step's ``op_name``s and the trace's ops."""
LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.phase_ms(window, "forward")
