"""Mean time a step waited for its batch: the benchmark's span round
``next(loader)``. (A consumer-wait counter inside DataLoader is the tracing
issue's.)"""
LAYER = "host data path"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    return window.mean_span_ms("data_wait")
