"""What a gated short convolution must move, beside ``kernel_costs.py`` (which
only a ``benchmark`` PR may change): bytes of the algorithm **from the
configuration's keys and the mix's rows, not from an implementation**, so
that the op's roofline reads the same work whatever computes it: XLA ops
today, a kernel later.

The op (LFM2's token mixer between its two projections, C = ``hidden_size``
channels): ``y_t = C_t * sum_j w_j (B X)_{t - (K - 1) + j}``.

- **Bytes**, each array once, at the parameter dtype: the forward reads the
  streams ``B``, ``C``, ``X`` and writes ``y``, 4 C elements a token a layer;
  the backward reads those three and ``dy`` and writes ``dB``, ``dC``, ``dX``,
  7 C. The K x C taps and their gradient are a few thousandths of one token
  row's worth a step and are left out; nothing recomputed is counted, so the
  share can only understate what a run does.
- **FLOPs**: 2 K + 2 a channel a token forward, about three times that with
  the backward: at K = 3 two operations a byte, far under the chip's 240, so
  the bytes bind and the FLOPs are not reckoned.
"""


def has_short_conv(cell):
    """The rule of every reader of the convolution layer's metrics: the
    configuration states a short convolution as LFM2's does."""
    return "conv_L_cache" in cell["config"]


def conv_layers(cfg):
    return sum(kind == "conv" for kind in cfg.get("layer_types", ()))


def gated_conv_bytes_per_position(cfg, itemsize):
    """Forward and backward, every convolution layer."""
    return float(itemsize) * (4 + 7) * cfg["hidden_size"] * conv_layers(cfg)


def gated_conv_roofline_s(cfg, traffic, peaks):
    """The least seconds a step's gated convolutions could take on a device
    with ``peaks`` (an entry of ``peaks.json``): their bytes over the HBM
    rate; 0.0 for a configuration without a convolution layer."""
    if not conv_layers(cfg):
        return 0.0
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["recipe"]["parameter_dtype"]]
    tokens = traffic["batch"] * traffic["seq_len"]
    return tokens * gated_conv_bytes_per_position(cfg, itemsize) / \
        peaks["hbm_bytes_per_s"]
