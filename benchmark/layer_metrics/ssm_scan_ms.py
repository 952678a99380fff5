"""Device time a step of the program op ``ssm_chunk`` alone
(``nn.functional.ssm_chunk``: the chunked state-space recurrence without its
projections, convolution, step size and norm), forward and backward; first
device: what a kernel for the scan would have to beat. It reads 0 where the
compiled step has no such op (``scope_reduce.program_op_ms`` sums nothing),
which is every cell without a state-space layer: so it has no ``reports``
rule and no ``workloads`` list, as ``mtp_ms``."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(window):
    from benchmark import scope_reduce

    return scope_reduce.program_op_ms(window, "ssm_chunk")
