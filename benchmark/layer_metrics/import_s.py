"""``import paddle_tpu``, first line to last: the program's record
``startup.import`` (jax itself and the device are imported and found before
it, by the benchmark)."""
from benchmark import startup_records

LAYER = "start-up"
UNIT = "s"
MOVES = "setup_s"


def read(window):
    return startup_records.read(window, "import_s")
