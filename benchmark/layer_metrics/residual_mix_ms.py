"""Device time a step of the multi-stream residual: ``hc_maps`` (the norm
over the flattened streams, the maps' projection, sigmoids and Sinkhorn),
``hc_read`` (streams into a layer's input) and ``hc_mix`` (the streams mixed
and the layer's output written back); forward and backward; first device.
Read where the configuration has more than one residual stream (``hc_mult``):
a one-stream residual has no ``hc_*`` op, with routed experts or without."""
LAYER = "residual path"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def reports(cell):
    return cell["config"].get("hc_mult", 1) > 1


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.program_op_ms(
        window, "hc_maps", "hc_read", "hc_mix") or None
