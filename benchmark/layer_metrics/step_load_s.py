"""The compile cache's answer for the train step: the program's record
``aot.load`` (a hit: read and deserialize), or ``aot.compile`` + ``aot.store``
(a miss), ``site=trainstep``."""
from benchmark import startup_records

LAYER = "compile cache"
UNIT = "s"
MOVES = "setup_s"


def read(window):
    return startup_records.read(window, "step_load_s")
