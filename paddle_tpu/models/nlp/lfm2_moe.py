"""A decoder family whose token mixer is, in most layers, a short convolution
(LiquidAI's LFM2-MoE, Hugging Face's ``Lfm2Moe*``): a causal depthwise
convolution of three taps gated on both sides, every few layers a softmax
attention over grouped-query heads with an RMS norm on every head of q and k
and a rotary embedding; the first layers carry a wide dense SwiGLU, the others
sigmoid-routed experts without a shared one; the head is the embedding; on
``decoder_stack.ExpertStack`` with a plain pre-norm residual. The equations
(``u`` the normed block input, C = ``hidden``):

- **Block**: ``h = x + Op(RMS_w(x))``, ``x' = h + FFN(RMS_w(h))``.
- **``conv`` operator** (``ShortConvMixer``): ``[B, C, X] = split3(W_in u)``
  (C -> 3 C); ``z = B * X``; ``c_t = sum_j w[j] z_{t - (K - 1) + j}``, depthwise
  and causal over ``conv_size`` taps, no bias and **no activation**; ``Op =
  W_out (C * c)`` (``nn.functional.gated_short_conv`` between the two
  projections).
- **``full_attention`` operator**: ``q = W_q u`` in ``heads`` heads, ``k, v``
  in ``kv_heads`` of ``head_dim``; ``q <- RMS(q) g_q``, ``k <- RMS(k) g_k`` a
  head, one ``head_dim``-wide weight each shared by the heads; rotary over the
  whole head (``rope_theta``, rotate-half); causal softmax at ``head_dim **
  -0.5``; ``Op = W_o att``; no gate, no bias
  (``GatedGroupedAttention(gated=False, qk_norm=rms_eps)``).
- **FFN**: layers before ``dense_layers`` a SwiGLU of ``dense_width``; the
  others ``routed_scale * sum_{e in top-k} g_e SwiGLU_e(u)`` with ``s =
  sigmoid(W_r u)`` in float32, the choice by ``s + b`` (``b`` the expert bias,
  a buffer), ``g`` the chosen scores over their sum (``norm_topk``), of which
  this chip computes the experts ``first_expert .. + experts_held``
  (``ExpertMLP`` without a shared expert).
- **Head**: ``logits = RMS_w(x_L) E^T``, ``E`` the embedding (``tie_head``).

All of a convolution sublayer's device work (``W_in``, the op, ``W_out``),
forward and backward, lies under the program scope ``short_conv``, an
attention sublayer's under ``gqa_attn`` (``core.dispatch.program_scope``).
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core.dispatch import program_scope
from ...core.tensor import Tensor
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import Layer
from ...nn.layers.common import SwiGLU
from ...nn.layers.norm import RMSNorm
from .decoder_stack import ExpertMLP, ExpertStack, GatedGroupedAttention, \
    _linear, _out_std, _std

__all__ = ["LFM2MoEConfig", "LFM2MoE", "LFM2MoEBlock", "ShortConvMixer",
           "lfm2_moe_tiny"]

CONV, FULL = "conv", "full_attention"
# LFM2-8B-A1B's 24 layers: six attention layers among eighteen convolutions
LAYER_TYPES = tuple(FULL if i in (2, 6, 10, 14, 18, 21) else CONV
                    for i in range(24))


class LFM2MoEConfig:
    router_score = "sigmoid"    # what ``ExpertMLP`` asks: one answer each
    shared_experts = 0
    tie_head = True             # ``ExpertStack`` builds no ``head``

    def __init__(self, vocab_size=65536, hidden=2048, layers=24,
                 layer_types=None, heads=32, kv_heads=8, head_dim=64,
                 rope_theta=1000000.0, conv_size=3, dense_layers=2,
                 dense_width=7168, experts=32, expert_width=1792, top_k=4,
                 routed_scale=1.0, norm_topk=True, first_expert=0,
                 experts_held=None, rms_eps=1e-5, initializer_range=0.02,
                 conv_initializer_range=1 / 3, use_recompute=False):
        self.vocab_size, self.hidden, self.layers = vocab_size, hidden, layers
        # the published list's first entries where nothing else is said
        self.layer_types = tuple(LAYER_TYPES[:layers] if layer_types is None
                                 else layer_types)
        if len(self.layer_types) != layers or \
                set(self.layer_types) - {CONV, FULL} or heads % kv_heads:
            raise ValueError(
                f"{layers} layers want a kind ({CONV} / {FULL}) each and "
                f"{heads} heads whole groups over {kv_heads} key/value "
                f"heads: {self.layer_types}")
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.rope_theta, self.conv_size = rope_theta, conv_size
        self.dense_layers, self.dense_width = dense_layers, dense_width
        self.experts, self.expert_width, self.top_k = experts, expert_width, \
            top_k
        self.routed_scale, self.norm_topk = routed_scale, norm_topk
        self.first_expert = first_expert
        self.experts_held = experts if experts_held is None else experts_held
        self.rms_eps, self.initializer_range = rms_eps, initializer_range
        self.conv_initializer_range = conv_initializer_range
        self.use_recompute = use_recompute  # jax.checkpoint per block


def lfm2_moe_tiny(**kw):
    base = dict(vocab_size=256, hidden=64, layers=4,
                layer_types=(CONV, FULL, CONV, CONV), heads=4, kv_heads=2,
                head_dim=16, dense_layers=1, dense_width=96, experts=8,
                expert_width=32, top_k=2)
    base.update(kw)
    return LFM2MoEConfig(**base)


class ShortConvMixer(Layer):
    """``W_out gated_short_conv(W_in x, taps)`` over (B, L, C)."""

    def __init__(self, cfg):
        super().__init__()
        self.conv = self.create_parameter(
            (cfg.conv_size, cfg.hidden),
            attr=I.Normal(0.0, cfg.conv_initializer_range))
        self.in_proj = _linear(cfg, cfg.hidden, 3 * cfg.hidden)
        self.out_proj = _linear(cfg, cfg.hidden, cfg.hidden, _out_std(cfg))

    def forward(self, x):
        return self.out_proj(F.gated_short_conv(self.in_proj(x), self.conv))


class LFM2MoEBlock(Layer):
    """``forward(x) -> (x', load)`` over the state (B, L, C): ``load`` the
    routed experts' slot counts (zeros for a dense block)."""

    def __init__(self, cfg, i):
        super().__init__()
        self.cfg, self.kind = cfg, cfg.layer_types[i]
        self.dense = i < cfg.dense_layers
        self.op_norm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.op = ShortConvMixer(cfg) if self.kind == CONV else \
            GatedGroupedAttention(
                cfg, heads=cfg.heads, kv_heads=cfg.kv_heads, gated=False,
                rope=(cfg.head_dim, cfg.rope_theta), qk_norm=cfg.rms_eps)
        self.mlp_norm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.mlp = SwiGLU(cfg.hidden, cfg.dense_width, weight_attr=_std(cfg),
                          down_attr=_out_std(cfg)) if self.dense else \
            ExpertMLP(cfg)

    def forward(self, x):
        h = self.op_norm(x)
        with program_scope("short_conv" if self.kind == CONV else "gqa_attn"):
            y = self.op(h)
        x = x + y
        h = self.mlp_norm(x)
        if self.dense:
            y, load = self.mlp(h), Tensor(
                jnp.zeros((self.cfg.experts,), jnp.float32), _internal=True)
        else:
            y, load = self.mlp(h)
        return x + y, load


class LFM2MoE(ExpertStack):
    def _block(self, i):
        return LFM2MoEBlock(self.cfg, i)

    def hidden(self, ids):
        x = self.embed(ids)
        loads = []
        for block in self.blocks:
            x, load = self._run(block, x)
            if not block.dense:
                loads.append(load)
        return x, loads
