"""The state-space scan's share of its roofline: the least time the chip
could take for what a step's recurrences must do (``benchmark/ssm_costs.py``:
FLOPs and bytes from the configuration's keys and the mix's rows, the larger
of FLOPs over the bf16 peak and bytes over the HBM rate; the bytes bind) over
the device time of the program op ``ssm_chunk``, forward and backward. It
reads 0 where the compiled step has no such op, which is every cell without
a state-space layer: so it has no ``reports`` rule and no ``workloads``
list, as ``mtp_ms``."""
LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(window):
    import jax

    from benchmark import harness, scope_reduce, ssm_costs

    took_ms = scope_reduce.program_op_ms(window, "ssm_chunk")
    if not took_ms:
        return took_ms      # None: nothing can be read; 0.0: no such op
    need_s = ssm_costs.scan_roofline_s(
        window.cell["config"], window.cell["traffic"],
        harness.peaks(jax.devices()[0].device_kind))
    return 100.0 * need_s / (took_ms / 1e3)
