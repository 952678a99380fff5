"""Mean time ``step(*batch)`` took to return: the enqueue, not the step."""
LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    return window.mean_span_ms("dispatch")
