"""1 - union of device-op intervals / traced window; worst device."""
LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import trace_reduce

    return trace_reduce.busy_and_idle(window.trace.ops)[2]
