"""Model FLOP/s utilization: the window's steps times the family's count of
one step's FLOPs over the cell's traffic mix (``family.step_flops(config,
traffic)``; for rows of tokens batch x seq_len x (6N + 12 layers hidden L):
padding and full attention counted, recompute not), over the window's
seconds, over chips times the bf16 peak of benchmark/peaks.json."""
LAYER = "train step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(window):
    import jax

    from benchmark import harness

    cell = window.cell
    peak = harness.peaks(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    flops = window.family.step_flops(cell["config"], cell["traffic"])
    return 100.0 * window.steps * flops / window.seconds / \
        (cell["chips"] * peak)
