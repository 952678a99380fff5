"""Backend compiles the process asked for between the window's start and
its end; must read 0."""
LAYER = "compile cache"
UNIT = "count"
MOVES = "step_ms_p90"


def read(window):
    return window.compiles_in_window
