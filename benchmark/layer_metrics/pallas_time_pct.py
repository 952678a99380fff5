"""Share of device-busy time inside Mosaic custom calls, all kernels
together, first device. The kernels carry no names of their own yet (all
three training kernels call theirs ``_fwd_kernel``), so no kernel is told
from another: the trace marks which ops are Mosaic calls, and that is all."""
LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import trace_reduce

    events = next(iter(window.trace.ops.values()))
    return trace_reduce.share_of(events, trace_reduce.is_mosaic)
