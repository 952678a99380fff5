"""Bytes one device needs to run the compiled step (arguments +
temporaries, ``memory_analysis()`` of the executable that ran): the bytes a
step needs bound the batch a chip holds."""
LAYER = "train step"
UNIT = "GB"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import harness

    return harness.step_bytes(window.compiled) / 1e9
