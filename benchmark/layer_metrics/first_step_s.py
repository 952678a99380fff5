"""Host clock round the first ``step(*batch)`` to its loss: the compile, or
the cache's answer."""
LAYER = "compile cache"
UNIT = "s"
MOVES = "setup_s"


def read(window):
    return window.first_step_s
