"""The first call of the compiled step: the executable's load onto the chip
and the first enqueue, the program's record ``trainstep.first_execute``
(with no cache active it would hold the lazy jit's whole compile)."""
from benchmark import startup_records

LAYER = "compile cache"
UNIT = "s"
MOVES = "setup_s"


def read(window):
    return startup_records.read(window, "step_first_execute_s")
