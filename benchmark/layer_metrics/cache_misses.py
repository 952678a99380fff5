"""Compiles the caches could not answer: the program's counters
``jax.cache.misses`` (jax's persistent cache, counted where jax writes an
entry, so only for a compile over its minimum compile time) +
``aot.cache.misses`` (the executable cache), as they stand when the reader
runs: the reference compiles later. 0 on a warm run once every program
whose compile takes about jax's minimum has been written: one that takes
0.9 s in one run and 1.1 s in the next is written, and counted, in the
second (cells 5-7 read 2-4 on their second run of a directory)."""
LAYER = "compile cache"
UNIT = "count"
MOVES = "setup_s"
COUNTERS = ("jax.cache.misses", "aot.cache.misses")


def read(window):
    from paddle_tpu.obs import metrics

    held = (metrics.REGISTRY.get(name) for name in COUNTERS)
    return float(sum(c.value for c in held if c is not None))
