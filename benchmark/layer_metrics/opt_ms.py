"""Device time a step under ``TrainStep``'s ``optimizer`` scope (unscale,
finite check, clip, update) and, where ``dist.gradcomm`` runs one, its
``grad_exchange`` scope; first device; ``benchmark/scope_reduce.py``."""
LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.phase_ms(window, "optimizer", "grad_exchange")
