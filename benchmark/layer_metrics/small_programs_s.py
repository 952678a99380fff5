"""Every program the process traced, lowered, compiled or loaded beside its
step before the window: the program's ``jax.*`` records (jax.monitoring's
duration events) under none of the records the five readers above read:
optimizer slots, lr and key programs, the benchmark's own weights and
norms."""
from benchmark import startup_records

LAYER = "compile cache"
UNIT = "s"
MOVES = "setup_s"


def read(window):
    return startup_records.read(window, "small_programs_s")
