"""Tracing and lowering the train step and hashing its module, for every
signature compiled before the window: the program's records ``aot.lower`` +
``aot.key`` with ``site=trainstep`` (``runtime/aot.py:load_or_compile``)."""
from benchmark import startup_records

LAYER = "compile cache"
UNIT = "s"
MOVES = "setup_s"


def read(window):
    return startup_records.read(window, "step_lower_s")
