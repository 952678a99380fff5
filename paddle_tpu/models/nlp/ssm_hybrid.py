"""A hybrid state-space decoder (IBM's Granite 4.0-H, ``GraniteMoeHybrid``
without experts): most layers a Mamba-2 mixer (arXiv:2405.21060), every few
a softmax attention over grouped-query heads without positions, every layer
a dense SwiGLU; four scalar multipliers and a head tied to the embedding; on
``decoder_stack.DecoderStack``, the attention sublayer its
``GatedGroupedAttention`` without the gate. The equations (``x`` the residual
state, ``r = residual_multiplier``):

- **Model**: ``x_0 = embedding_multiplier E[ids]``; a block ``h = x + r
  Mixer(RMS_w(x))``, ``x' = h + r MLP(RMS_w(h))``; ``logits = RMS_w(x_L) E^T /
  logits_scaling`` with the one matrix ``E``.
- **Mamba-2 mixer** (H = ``ssm_heads`` of P = ``ssm_head_dim``, state N =
  ``ssm_state``, one group; ``d_inner = H P``): ``[z_t, u_t, d_t] = W_in
  x_t`` (``d_inner``, ``d_inner + 2 N``, H); ``[x'_t, B_t, C_t] =
  SiLU(conv(u)_t + b_conv)``, the convolution depthwise, causal,
  ``conv_size`` wide (``nn.functional.short_conv``); ``Delta_t = softplus(d_t
  + dt_bias)`` (``ssm_gate``); ``S_t = exp(-exp(A_log) Delta_t) S_{t-1} +
  Delta_t x'_t B_t^T``, ``y_t = S_t C_t + D x'_t`` a head, ``S = 0`` at the
  start of a row (``ssm_chunk``, chunks of ``chunk`` tokens); ``out_t = W_out
  RMS_w(y_t * SiLU(z_t))``, the norm over all ``d_inner`` channels
  (``gated_rms_norm(silu_first=True)``).
- **Attention**: ``q, k, v = W_q x, W_k x, W_v x`` in ``heads`` / ``kv_heads``
  heads of ``head_dim``, no position embedding, one causal ``sdpa`` at
  ``attention_multiplier``, ``W_o``; no bias, no gate.

All of a state-space sublayer's device work, forward and backward, lies under
the program scope ``state_space``, an attention sublayer's under ``gqa_attn``
(``core.dispatch.program_scope``). The last pass's most negative log-decay
over a chunk and its mean step size are kept in the buffer
``state_space_stats`` (``publish_gauges``: ``state_space.chunk_log_decay_min``,
``state_space.dt_mean``).
"""
from __future__ import annotations

import jax.numpy as jnp

from ... import ops
from ...core.dispatch import program_scope
from ...core.tensor import Tensor
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import Layer
from ...nn.layers.common import SwiGLU
from ...nn.layers.norm import RMSNorm
from .decoder_stack import DecoderStack, GatedGroupedAttention, _linear, \
    _out_std, _std

__all__ = ["SSMHybridConfig", "SSMHybrid", "SSMHybridBlock", "Mamba2Mixer",
           "ssm_hybrid_tiny"]

MAMBA, ATTENTION = "mamba", "attention"


class SSMHybridConfig:
    def __init__(self, vocab_size=100352, hidden=2048, layers=40,
                 layer_types=None, heads=32, kv_heads=8, head_dim=64,
                 mlp_width=8192, ssm_heads=64, ssm_head_dim=64, ssm_state=128,
                 conv_size=4, chunk=256, embedding_multiplier=12.0,
                 residual_multiplier=0.22, attention_multiplier=0.015625,
                 logits_scaling=8.0, rms_eps=1e-5, initializer_range=0.02,
                 conv_initializer_range=0.2887, use_recompute=False):
        self.vocab_size, self.hidden, self.layers = vocab_size, hidden, layers
        # nine state-space layers to one of attention, the sixth of every
        # ten, where nothing else is said
        self.layer_types = tuple(
            [ATTENTION if i % 10 == 5 else MAMBA for i in range(layers)]
            if layer_types is None else layer_types)
        if len(self.layer_types) != layers or \
                set(self.layer_types) - {MAMBA, ATTENTION} or heads % kv_heads:
            raise ValueError(
                f"{layers} layers want a kind ({MAMBA} / {ATTENTION}) each "
                f"and {heads} heads whole groups over {kv_heads} key/value "
                f"heads: {self.layer_types}")
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.mlp_width = mlp_width
        self.ssm_heads, self.ssm_head_dim = ssm_heads, ssm_head_dim
        self.ssm_state, self.conv_size, self.chunk = ssm_state, conv_size, \
            chunk
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.attention_multiplier = attention_multiplier
        self.logits_scaling = logits_scaling
        self.rms_eps, self.initializer_range = rms_eps, initializer_range
        self.conv_initializer_range = conv_initializer_range
        self.use_recompute = use_recompute  # jax.checkpoint per block


def ssm_hybrid_tiny(**kw):
    base = dict(vocab_size=256, hidden=64, layers=3,
                layer_types=(MAMBA, ATTENTION, MAMBA), heads=4, kv_heads=2,
                head_dim=16, mlp_width=96, ssm_heads=4, ssm_head_dim=16,
                ssm_state=8, chunk=8)
    base.update(kw)
    return SSMHybridConfig(**base)


class Mamba2Mixer(Layer):
    """``forward(x) -> (y, stats)``: a Mamba-2 sublayer, and float32 ``[the
    most negative log-decay a head ran up over a chunk, the mean step
    size]``."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        h, inner = cfg.ssm_heads, cfg.ssm_heads * cfg.ssm_head_dim
        conv = inner + 2 * cfg.ssm_state
        self.in_proj = _linear(cfg, cfg.hidden, inner + conv + h)
        self.conv = self.create_parameter(
            (cfg.conv_size, conv), attr=I.Normal(0.0,
                                                 cfg.conv_initializer_range))
        self.conv_bias = self.create_parameter((conv,), is_bias=True)
        self.dt_bias = self.create_parameter((h,), is_bias=True)
        self.A_log = self.create_parameter(
            (h,), default_initializer=I.Constant(0.0))
        self.D = self.create_parameter(
            (h,), default_initializer=I.Constant(1.0))
        self.norm = self.create_parameter(
            (inner,), default_initializer=I.Constant(1.0))
        self.out_proj = _linear(cfg, inner, cfg.hidden, _out_std(cfg))

    def forward(self, x):
        c = self.cfg
        B, L = x.shape[0], x.shape[1]
        h, p, n = c.ssm_heads, c.ssm_head_dim, c.ssm_state
        z, u, raw = ops.split(self.in_proj(x), [h * p, h * p + 2 * n, h],
                              axis=-1)
        xs, b, cc = ops.split(F.short_conv(u, self.conv, self.conv_bias),
                              [h * p, n, n], axis=-1)
        dt = F.ssm_gate(raw, self.dt_bias)
        y, decay_min = F.ssm_chunk(ops.reshape(xs, [B, L, h, p]), dt,
                                   self.A_log, b, cc, self.D, chunk=c.chunk)
        y = F.gated_rms_norm(ops.reshape(y, [B, L, h * p]), z, self.norm,
                             epsilon=c.rms_eps, silu_first=True)
        return self.out_proj(y), ops.stack([decay_min, ops.mean(dt)], axis=0)


class SSMHybridBlock(Layer):
    """``forward(x) -> (x', stats)`` over the state (B, L, C): ``stats`` the
    state-space sublayer's (zeros for an attention block)."""

    def __init__(self, cfg, i):
        super().__init__()
        self.cfg, self.mamba = cfg, cfg.layer_types[i] == MAMBA
        self.mixer_norm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.mixer = Mamba2Mixer(cfg) if self.mamba else \
            GatedGroupedAttention(cfg, heads=cfg.heads, kv_heads=cfg.kv_heads,
                                  gated=False, scale=cfg.attention_multiplier)
        self.mlp_norm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.mlp = SwiGLU(cfg.hidden, cfg.mlp_width, weight_attr=_std(cfg),
                          down_attr=_out_std(cfg))

    def forward(self, x):
        r = self.cfg.residual_multiplier
        h = self.mixer_norm(x)
        if self.mamba:
            with program_scope("state_space"):
                y, stats = self.mixer(h)
        else:
            with program_scope("gqa_attn"):
                y = self.mixer(h)
            stats = Tensor(jnp.zeros((2,), jnp.float32), _internal=True)
        x = x + y * r
        return x + self.mlp(self.mlp_norm(x)) * r, stats


class SSMHybrid(DecoderStack):
    def __init__(self, cfg):
        super().__init__(cfg)
        # [most negative log-decay of a chunk, mean step size] of the last
        # pass, over the state-space layers
        self.register_buffer(
            "state_space_stats", Tensor(jnp.zeros((2,), jnp.float32),
                                        _internal=True), persistable=False)

    def _block(self, i):
        return SSMHybridBlock(self.cfg, i)

    def hidden(self, ids):
        """The state after the last block."""
        x = self.embed(ids) * self.cfg.embedding_multiplier
        stats = []
        for block in self.blocks:
            x, stat = self._run(block, x)
            if block.mamba:
                stats.append(stat._data.astype(jnp.float32))
        self._keep_stats(self.state_space_stats, stats)
        return x

    def _logits(self, h):
        return self._tied_logits(h) / self.cfg.logits_scaling

    def forward(self, ids):
        return self._logits(self.hidden(ids))

    def publish_gauges(self):
        """How near float32's edge the state-space layers' decays ran over a
        chunk in the last step, and their mean step size."""
        from ...obs import metrics

        decay_min, dt_mean = (float(t) for t in self.state_space_stats._data)
        metrics.gauge("state_space.chunk_log_decay_min").set(decay_min)
        metrics.gauge("state_space.dt_mean").set(dt_mean)
