"""What a state-space layer's recurrence must do, beside ``kernel_costs.py``
(which only a ``benchmark`` PR may change): operations and bytes of the
algorithm **from the configuration's keys and the mix's rows, not from an
implementation**, so that the scan's roofline reads the same work whatever
computes it: XLA ops today, a kernel later.

The recurrence (Mamba-2, arXiv:2405.21060; a head of P channels over a state
of N columns): ``S_t = a_t S_{t-1} + Delta_t x_t B_t^T``, ``y_t = S_t C_t``.

- **FLOPs**, as the token-by-token form has them: a token a head decays the
  state (P N), adds the outer product (2 P N) and reads it with C (2 P N): 5 P
  N, three times that with the backward pass.
- **Bytes**, each array once, at the parameter dtype: the forward reads x
  (H P a token), Delta (H), B and C (N each, one group) and writes y (H P);
  the backward reads those four and dy and writes dx, dDelta, dB and dC: 5 H P
  + 3 H + 6 N elements a token a layer. The state never leaves the chip's
  fast memory in this count, and nothing recomputed is counted, so the share
  can only understate what a run does.
"""


def mamba_layers(cfg):
    return sum(kind == "mamba" for kind in cfg.get("layer_types", ()))


def scan_flops_per_position(cfg):
    """Forward and backward, every state-space layer."""
    return 3.0 * 5.0 * cfg["mamba_d_head"] * cfg["mamba_d_state"] * \
        cfg["mamba_n_heads"] * mamba_layers(cfg)


def scan_bytes_per_position(cfg, itemsize):
    """Forward and backward, every state-space layer."""
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return float(itemsize) * (5 * h * p + 3 * h + 6 * n) * mamba_layers(cfg)


def scan_roofline_s(cfg, traffic, peaks):
    """The least seconds a step's recurrences could take on a device with
    ``peaks`` (an entry of ``peaks.json``): the larger of FLOPs over the bf16
    peak and bytes over the HBM rate; 0.0 for a configuration without a
    state-space layer."""
    if not mamba_layers(cfg):
        return 0.0
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["recipe"]["parameter_dtype"]]
    tokens = traffic["batch"] * traffic["seq_len"]
    return tokens * max(
        scan_flops_per_position(cfg) / peaks["bf16_flops_per_s"],
        scan_bytes_per_position(cfg, itemsize) / peaks["hbm_bytes_per_s"])
