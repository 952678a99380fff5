"""The host's time in parameter initializers while the model is built, the
programs they compile included: the sum of the program's records
``startup.param_init`` (one a parameter, ``nn/layer.py:create_parameter``)
that lie outside ``startup.import``."""
from benchmark import startup_records

LAYER = "start-up"
UNIT = "s"
MOVES = "setup_s"


def read(window):
    return startup_records.read(window, "param_init_s")
