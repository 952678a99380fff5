"""Share of the first device's busy time in ops that the join from trace to
program scope could not name: the instruction is not in the compiled text, or
its ``op_name`` names no phase."""
LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_reduce

    table, _ = scope_reduce.of_window(window)
    if table is None:
        return None
    return 100.0 * sum(r.ms for r in table if not r.phase) / \
        sum(r.ms for r in table)
