"""A hybrid decoder family of current open models: most layers gated-delta-
rule linear attention, every few a gated softmax attention without positions
over grouped-query heads, every layer sigmoid-routed experts beside a shared
one, on ``decoder_stack.ExpertStack`` with a plain pre-norm residual ``x = x +
F(RMS_w(x))`` and its ``ExpertMLP``. The equations of what is its own (``x_t``
the normed state, a head h of width d):

- **Linear attention** (Kimi Linear's KDA, arXiv:2510.26692): ``q', k', v' =
  SiLU(conv(W_q x)), SiLU(conv(W_k x)), SiLU(conv(W_v x))``, the convolution
  depthwise, causal, ``conv_size`` wide, no bias; ``q_t = unit(q'_t)
  d^-1/2``, ``k_t = unit(k'_t)``; the log-decay a channel ``g_t = -exp(A_log_h)
  softplus(W_f2 W_f1 x_t + dt_bias)`` in R^d, ``alpha_t = exp(g_t)``;
  ``beta_t = 2 sigmoid(w_b x_t)`` (``neg_eigval``; without it no 2); the
  state ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
  v_t^T`` in R^{d x d}, ``S_0 = 0`` at the start of a row; ``o_t = S_t^T
  q_t``; output ``W_o concat_h[RMS_w(o_t) * sigmoid(W_g2 W_g1 x_t + b_g)]``.
  ``W_f1`` and ``W_g1`` are hidden x ``gate_rank``. The rule runs chunked
  (``nn.functional.kda_chunk``, chunks of ``chunk`` tokens); the convolution,
  the gates and the gated norm are registered ops too (``short_conv``,
  ``kda_gate``, ``gated_rms_norm``).
- **Softmax attention**: ``q = W_q x`` in heads of ``head_dim``, ``k, v = W_k
  x, W_v x`` in ``kv_heads`` heads, each read by ``heads / kv_heads`` query
  heads, no position embedding, one causal ``sdpa`` at ``head_dim^-1/2``,
  output ``W_o (att * sigmoid(W_gate x))``
  (``decoder_stack.GatedGroupedAttention``).
- **A share of the heads.** As one chip of a tensor-parallel group the model
  holds ``heads_held`` of the ``heads`` query heads, from ``first_head``, the
  key/value heads those read, and the same share of the linear layers' heads:
  the columns of every projection with a head axis and the rows of ``W_o``;
  ``W_f1`` and ``W_g1`` whole. A sublayer then gives the held heads' part of
  its result; the shares' parts add up to the whole sublayer
  (``tests/test_hybrid_moe.py``), and on one chip that partial sum is what
  goes on, as ``experts_held`` / ``first_expert`` do for the experts.

All of a linear-attention sublayer's device work, forward and backward, lies
under the program scope ``linear_attn``, a softmax sublayer's under
``gqa_attn`` (``core.dispatch.program_scope``). The last pass's most negative
log-decay over a chunk and its mean ``beta`` are kept in the buffer
``linear_attn_stats`` (``publish_gauges``: ``linear_attn.
chunk_log_decay_min``, ``linear_attn.beta_mean``).
"""
from __future__ import annotations

import jax.numpy as jnp

from ... import ops
from ...core.dispatch import program_scope
from ...core.tensor import Tensor
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import Layer
from ...nn.layers.norm import RMSNorm
from .decoder_stack import ExpertMLP, ExpertStack, GatedGroupedAttention, \
    _linear, _out_std

__all__ = ["HybridMoEConfig", "HybridMoE", "HybridMoEBlock", "DeltaAttention",
           "hybrid_moe_tiny"]


class HybridMoEConfig:
    router_score = "sigmoid"    # ``ExpertMLP`` asks; one answer here

    def __init__(self, vocab_size=196608, hidden=4096, layers=48,
                 softmax_layers=None, heads=64, kv_heads=8, head_dim=128,
                 linear_heads=64, linear_head_dim=128,
                 conv_size=4, gate_rank=None, neg_eigval=True, chunk=64,
                 heads_held=None, first_head=0, experts=320,
                 expert_width=1280, shared_experts=1, top_k=8,
                 routed_scale=1.0, norm_topk=True, first_expert=0,
                 experts_held=None, rms_eps=1e-5, initializer_range=0.02,
                 conv_initializer_range=0.2887, use_recompute=False):
        self.vocab_size, self.hidden, self.layers = vocab_size, hidden, layers
        # one layer in four is softmax attention where nothing else is said
        self.softmax_layers = tuple(
            range(0, layers, 4) if softmax_layers is None else softmax_layers)
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.linear_heads, self.linear_head_dim = linear_heads, linear_head_dim
        self.conv_size, self.neg_eigval, self.chunk = conv_size, neg_eigval, \
            chunk
        self.gate_rank = linear_head_dim if gate_rank is None else gate_rank
        self.heads_held = heads if heads_held is None else heads_held
        self.first_head = first_head
        group = heads // kv_heads
        held = self.heads_held
        if heads % kv_heads or not 0 <= first_head <= first_head + held <= \
                heads or first_head % held or (held % group and group % held) \
                or linear_heads * held % heads:
            raise ValueError(
                f"heads {first_head}..{first_head + held - 1} of {heads} over "
                f"{kv_heads} key/value heads and {linear_heads} linear heads: "
                f"a share is whole key/value groups, or lies inside one")
        # the key/value heads the held query heads read, and the linear
        # layers' heads of the same share
        self.kv_heads_held = max(1, held // group)
        self.linear_heads_held = linear_heads * held // heads
        self.experts, self.expert_width = experts, expert_width
        self.shared_experts, self.top_k = shared_experts, top_k
        self.routed_scale, self.norm_topk = routed_scale, norm_topk
        self.first_expert = first_expert
        self.experts_held = experts if experts_held is None else experts_held
        self.rms_eps, self.initializer_range = rms_eps, initializer_range
        self.conv_initializer_range = conv_initializer_range
        self.use_recompute = use_recompute  # jax.checkpoint per block


def hybrid_moe_tiny(**kw):
    base = dict(vocab_size=256, hidden=64, layers=4, heads=4, kv_heads=2,
                head_dim=16, linear_heads=4, linear_head_dim=16, chunk=8,
                experts=8, expert_width=32, top_k=2)
    base.update(kw)
    return HybridMoEConfig(**base)


class DeltaAttention(Layer):
    """``forward(x) -> (y, stats)``: the held heads' part of a gated-delta-
    rule sublayer, and float32 ``[the most negative log-decay a channel ran
    up over a chunk, the mean beta]``."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        d, rank = cfg.hidden, cfg.gate_rank
        h, dh = cfg.linear_heads_held, cfg.linear_head_dim
        self.q, self.k, self.v = (_linear(cfg, d, h * dh) for _ in range(3))
        conv = I.Normal(0.0, cfg.conv_initializer_range)
        self.q_conv, self.k_conv, self.v_conv = (
            self.create_parameter((cfg.conv_size, h * dh), attr=conv)
            for _ in range(3))
        self.f_a, self.f_b = _linear(cfg, d, rank), _linear(cfg, rank, h * dh)
        self.A_log = self.create_parameter(
            (h,), default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter((h * dh,), is_bias=True)
        self.beta = _linear(cfg, d, h)
        self.g_a = _linear(cfg, d, rank)
        self.g_b = _linear(cfg, rank, h * dh, bias=True)
        self.o_norm = self.create_parameter(
            (dh,), default_initializer=I.Constant(1.0))
        self.o = _linear(cfg, h * dh, d, _out_std(cfg))

    def forward(self, x):
        c = self.cfg
        B, L = x.shape[0], x.shape[1]
        h, dh = c.linear_heads_held, c.linear_head_dim

        def heads(t):
            return ops.reshape(t, [B, L, h, dh])

        q = heads(F.short_conv(self.q(x), self.q_conv))
        k = heads(F.short_conv(self.k(x), self.k_conv))
        v = heads(F.short_conv(self.v(x), self.v_conv))
        g, beta = F.kda_gate(self.f_b(self.f_a(x)), self.A_log, self.dt_bias,
                             self.beta(x), head_dim=dh,
                             neg_eigval=c.neg_eigval)
        o, decay_min = F.kda_chunk(q, k, v, g, beta, chunk=c.chunk)
        o = F.gated_rms_norm(o, heads(self.g_b(self.g_a(x))), self.o_norm,
                             epsilon=c.rms_eps)
        stats = ops.stack([decay_min, ops.mean(beta)], axis=0)
        return self.o(ops.reshape(o, [B, L, h * dh])), stats


class HybridMoEBlock(Layer):
    """``forward(x) -> (x', load, stats)`` over the state (B, L, C): ``load``
    the routed experts' slot counts, ``stats`` the linear sublayer's (zeros
    for a softmax block)."""

    dense = False

    def __init__(self, cfg, softmax):
        super().__init__()
        self.cfg, self.softmax = cfg, softmax
        self.attn_norm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.attn = GatedGroupedAttention(cfg) if softmax else \
            DeltaAttention(cfg)
        self.mlp_norm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.mlp = ExpertMLP(cfg)

    def forward(self, x):
        h = self.attn_norm(x)
        if self.softmax:
            with program_scope("gqa_attn"):
                y = self.attn(h)
            stats = Tensor(jnp.zeros((2,), jnp.float32), _internal=True)
        else:
            with program_scope("linear_attn"):
                y, stats = self.attn(h)
        x = x + y
        y, load = self.mlp(self.mlp_norm(x))
        return x + y, load, stats


class HybridMoE(ExpertStack):
    def __init__(self, cfg):
        super().__init__(cfg)
        # [most negative log-decay of a chunk, mean beta] of the last pass,
        # over the linear-attention layers
        self.register_buffer(
            "linear_attn_stats", Tensor(jnp.zeros((2,), jnp.float32),
                                        _internal=True), persistable=False)

    def _block(self, i):
        return HybridMoEBlock(self.cfg, softmax=i in self.cfg.softmax_layers)

    def hidden(self, ids):
        x = self.embed(ids)
        loads, stats = [], []
        for block in self.blocks:
            x, load, stat = self._run(block, x)
            loads.append(load)
            if not block.softmax:
                stats.append(stat._data.astype(jnp.float32))
        self._keep_stats(self.linear_attn_stats, stats)
        return x, loads

    def publish_gauges(self):
        """Beside the routing gauges: how near float32's edge the linear
        layers' gates ran in the last step, and their mean step size."""
        from ...obs import metrics

        super().publish_gauges()
        decay_min, beta_mean = (float(t) for t in self.linear_attn_stats._data)
        metrics.gauge("linear_attn.chunk_log_decay_min").set(decay_min)
        metrics.gauge("linear_attn.beta_mean").set(beta_mean)
