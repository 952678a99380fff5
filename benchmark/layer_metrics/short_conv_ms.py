"""Device time a step of the short-convolution sublayers whole: every op whose
path holds the program scope ``short_conv`` (``LFM2MoEBlock`` opens it round
a convolution sublayer: ``W_in``, the gated convolution, ``W_out``), forward
and backward; first device. Read where the configuration states a short
convolution (``conv_L_cache``); nothing where the program opens no such scope
(any before PR 48)."""
from benchmark import conv_costs

LAYER = "convolution layer"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"

reports = conv_costs.has_short_conv


def read(window):
    from benchmark import scope_paths

    return scope_paths.scope_ms(window, "short_conv") or None
