"""A decoder family with two kinds of attention layer in one stack (poolside's
Laguna): most layers attend inside a sliding window over more query heads,
every few a layer attends to the whole row over fewer, both over the same few
key/value heads and under a sigmoid gate a head; the first layer's MLP is a
wide dense SwiGLU, the others softmax-routed experts beside a shared one; on
``decoder_stack.ExpertStack`` with a plain pre-norm residual ``x = x +
F(RMS_w(x))``, its ``ExpertMLP`` and its ``GatedGroupedAttention``. What a
layer ``l`` with ``H_l = heads_per_layer[l]`` heads computes (``x`` the normed
state, ``head_dim`` d):

- ``q = W_q x`` in ``H_l`` heads, ``k, v = W_k x, W_v x`` in ``kv_heads``,
  each read by ``H_l / kv_heads`` query heads; rotary on q and k; ``a =
  softmax(q k^T d^-1/2 + M_l) v``; ``g = sigmoid(W_g x)`` in R^{H_l}; output
  ``W_o concat_h(g_h a_h)``.
- ``M_l``: causal in a ``full_attention`` layer; in a ``sliding_attention``
  layer query i sees key j where ``0 <= i - j < window`` (one ``sdpa`` with
  ``window``: the ``swa_*`` kernels where their route takes the call).
- Rotary, a table a kind of layer (``rope``: for each kind ``theta``, the
  share of a head rotated ``partial``, an optional YaRN ``scaling`` and
  ``attention_factor``): the first ``partial * d`` dims of a head turn
  (rotate-half among themselves), the rest pass.
- Layers before ``first_dense`` have a SwiGLU of ``dense_width``; the others
  ``SwiGLU_shared(x) + routed_scale * sum_{e in top-k} w_e SwiGLU_e(x)`` with
  ``w`` the softmax scores of the chosen over their sum (``norm_topk``), of
  which this chip computes the experts ``first_expert .. + experts_held``.

All of a sliding sublayer's device work, forward and backward, lies under the
program scope ``window_attn``, a full sublayer's under ``gqa_attn``
(``core.dispatch.program_scope``). The mean head gate of the last pass is
kept in the buffer ``attn_stats`` beside the share of a causal layer's pairs
that a windowed layer kept at that pass's row length (``publish_gauges``:
``attn.head_gate_mean`` and ``attn.window_pair_share`` beside ``attn.window``,
``attn.window_layers`` and ``attn.full_layers``).
"""
from __future__ import annotations

import jax.numpy as jnp

from ... import ops
from ...core.dispatch import program_scope
from ...core.tensor import Tensor
from ...nn.layer import Layer
from ...nn.layers.common import SwiGLU
from ...nn.layers.norm import RMSNorm
from .decoder_stack import ExpertMLP, ExpertStack, GatedGroupedAttention, \
    _out_std, _std

__all__ = ["LagunaMoEConfig", "LagunaMoE", "LagunaMoEBlock",
           "laguna_moe_tiny", "window_pair_share"]

FULL, SLIDING = "full_attention", "sliding_attention"
# Laguna-S-2.1's two tables
ROPE = {
    FULL: dict(theta=500000.0, partial=0.5, attention_factor=1.4852030263919618,
               scaling={"factor": 128, "beta_fast": 32, "beta_slow": 1,
                        "original_max_position_embeddings": 8192}),
    SLIDING: dict(theta=10000.0, partial=1.0),
}


class LagunaMoEConfig:
    router_score = "softmax"    # ``ExpertMLP`` asks; one answer here

    def __init__(self, vocab_size=100352, hidden=3072, layers=48,
                 layer_types=None, heads_per_layer=None, heads=(48, 72),
                 kv_heads=8, head_dim=128, window=512, rope=None,
                 first_dense=1, dense_width=12288, experts=256,
                 expert_width=1024,
                 shared_experts=1, top_k=10, routed_scale=2.5, norm_topk=True,
                 first_expert=0, experts_held=None, rms_eps=1e-6,
                 initializer_range=0.02, use_recompute=False):
        self.vocab_size, self.hidden, self.layers = vocab_size, hidden, layers
        # three sliding layers after each full one, and ``heads`` (full,
        # sliding) of them by kind, where nothing else is said
        self.layer_types = tuple(
            [FULL if i % 4 == 0 else SLIDING for i in range(layers)]
            if layer_types is None else layer_types)
        self.heads_per_layer = tuple(
            [heads[t == SLIDING] for t in self.layer_types]
            if heads_per_layer is None else heads_per_layer)
        if len(self.layer_types) != layers or \
                len(self.heads_per_layer) != layers or \
                set(self.layer_types) - {FULL, SLIDING} or \
                any(h % kv_heads for h in self.heads_per_layer):
            raise ValueError(
                f"{layers} layers want a kind ({FULL} / {SLIDING}) and a "
                f"head count each, whole groups over {kv_heads} key/value "
                f"heads: {self.layer_types}, {self.heads_per_layer}")
        self.kv_heads, self.head_dim, self.window = kv_heads, head_dim, window
        self.rope = ROPE if rope is None else rope
        self.first_dense, self.dense_width = first_dense, dense_width
        self.experts, self.expert_width = experts, expert_width
        self.shared_experts, self.top_k = shared_experts, top_k
        self.routed_scale, self.norm_topk = routed_scale, norm_topk
        self.first_expert = first_expert
        self.experts_held = experts if experts_held is None else experts_held
        self.rms_eps, self.initializer_range = rms_eps, initializer_range
        self.use_recompute = use_recompute  # jax.checkpoint per block

    def rope_of(self, kind):
        """``F.rotary_cos_sin``'s arguments after the length."""
        r = self.rope[kind]
        return (int(self.head_dim * r["partial"]), r["theta"],
                r.get("scaling"), r.get("attention_factor"))


def laguna_moe_tiny(**kw):
    base = dict(vocab_size=256, hidden=64, layers=5, heads=(4, 6), kv_heads=2,
                head_dim=16, window=8, dense_width=96, experts=8,
                expert_width=32, top_k=2)
    base.update(kw)
    return LagunaMoEConfig(**base)


def window_pair_share(length, window):
    """Pairs (query, key) a windowed layer keeps over those a causal one has
    at this row length: 1 where the window reaches the whole row."""
    w = min(window, length)
    return (w * length - w * (w - 1) / 2) / (length * (length + 1) / 2)


class LagunaMoEBlock(Layer):
    """``forward(x) -> (x', load, gate)`` over the state (B, L, C): ``load``
    the routed experts' slot counts (zeros for a dense block), ``gate`` the
    float32 mean of the sublayer's head gates."""

    def __init__(self, cfg, i):
        super().__init__()
        self.cfg, self.kind = cfg, cfg.layer_types[i]
        self.dense = i < cfg.first_dense
        self.attn_norm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.attn = GatedGroupedAttention(
            cfg, heads=cfg.heads_per_layer[i], kv_heads=cfg.kv_heads,
            head_gate=True, rope=cfg.rope_of(self.kind),
            window=cfg.window if self.kind == SLIDING else None)
        self.mlp_norm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.mlp = SwiGLU(cfg.hidden, cfg.dense_width, weight_attr=_std(cfg),
                          down_attr=_out_std(cfg)) if self.dense else \
            ExpertMLP(cfg)

    def forward(self, x):
        scope = "window_attn" if self.kind == SLIDING else "gqa_attn"
        with program_scope(scope):
            y, gate = self.attn(self.attn_norm(x), with_gate=True)
        x = x + y
        h = self.mlp_norm(x)
        if self.dense:
            y, load = self.mlp(h), Tensor(
                jnp.zeros((self.cfg.experts,), jnp.float32), _internal=True)
        else:
            y, load = self.mlp(h)
        return x + y, load, ops.mean(gate.astype("float32"))


class LagunaMoE(ExpertStack):
    def __init__(self, cfg):
        super().__init__(cfg)
        # [mean head gate over the layers, the pairs a windowed layer kept
        # over a causal layer's at the row's length] of the last pass
        self.register_buffer(
            "attn_stats", Tensor(jnp.zeros((2,), jnp.float32),
                                 _internal=True), persistable=False)

    def _block(self, i):
        return LagunaMoEBlock(self.cfg, i)

    def hidden(self, ids):
        x = self.embed(ids)
        loads, gates = [], []
        for block in self.blocks:
            x, load, gate = self._run(block, x)
            gates.append(gate._data.astype(jnp.float32))
            if not block.dense:
                loads.append(load)
        # in the buffer's own type: a model cast to bfloat16 keeps its
        # buffers so
        self.attn_stats._replace(jnp.stack(
            [jnp.mean(jnp.stack(gates)), jnp.float32(window_pair_share(
                ids.shape[1], self.cfg.window))]).astype(
                self.attn_stats._data.dtype))
        return x, loads

    def publish_gauges(self):
        """Beside the routing gauges: the window, how many layers have one,
        what share of a causal layer's pairs such a layer kept at the last
        step's row length, and the mean head gate of that step."""
        from ...obs import metrics

        super().publish_gauges()
        c = self.cfg
        gate_mean, pair_share = (float(t) for t in self.attn_stats._data)
        sliding = sum(t == SLIDING for t in c.layer_types)
        metrics.gauge("attn.window").set(float(c.window))
        metrics.gauge("attn.window_layers").set(float(sliding))
        metrics.gauge("attn.full_layers").set(float(c.layers - sliding))
        metrics.gauge("attn.window_pair_share").set(pair_share)
        metrics.gauge("attn.head_gate_mean").set(gate_mean)
