"""Mosaic custom calls in the compiled step's HLO."""
LAYER = "kernels"
UNIT = "count"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import harness

    return harness.mosaic_calls(window.compiled_text)
