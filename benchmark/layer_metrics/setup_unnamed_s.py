"""From ``startup.import``'s start to the window's start, less the six named
durations (which are disjoint): what the program's timeline does not name:
the benchmark's rows and weights, ``TrainStep.__init__``, the checked steps'
own execution and reads, the warm-up."""
from benchmark import startup_records

LAYER = "start-up"
UNIT = "s"
MOVES = "setup_s"


def read(window):
    return startup_records.read(window, "setup_unnamed_s")
