"""Model FLOP/s utilization: positions computed per second (padding
included: the device computes it) times the family's FLOPs per position
(6N + 12 layers hidden L, full attention counted, recompute not), over chips
times the bf16 peak of benchmark/peaks.json."""
LAYER = "train step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(window):
    import jax

    from benchmark import harness

    peak = harness.peaks(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    flops = window.family.flops_per_position(
        window.cell["config"], window.cell["traffic"]["seq_len"])
    return 100.0 * window.positions / window.seconds * flops / \
        (window.cell["chips"] * peak)
