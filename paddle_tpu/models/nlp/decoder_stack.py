"""What this package's pre-norm decoder families stand on, and nothing of what
a family is: ``DecoderStack``, ``ExpertStack`` (a stack with routed experts)
and the two sublayers more than one family builds, ``ExpertMLP`` and
``GatedGroupedAttention``. ``latent_moe``, ``hybrid_moe``, ``laguna_moe``,
``ssm_hybrid`` and ``lfm2_moe`` import from here and from no other family;
every stack trains under ``latent_moe.latent_moe_loss``.

A stack has one of three kinds of head: **untied over experts**
(``ExpertStack``'s ``head``, a leaf of its own: ``latent_moe``,
``hybrid_moe``, ``laguna_moe``), **tied without experts**
(``ssm_hybrid.SSMHybrid`` reads its logits off the embedding, under its own
scaling) and **tied over experts** (an ``ExpertStack`` whose configuration
says ``tie_head``: no ``head`` is built, ``lfm2_moe``). The tie is the
stack's, ``DecoderStack._tied_logits``, whichever family asks for it."""
from __future__ import annotations

import math

import jax.numpy as jnp

from ... import ops
from ...core.tensor import Tensor
from ...dist.moe import DroplessMoE, window_rows
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import Layer, LayerList
from ...nn.layers.common import Embedding, Linear, SwiGLU
from ...nn.layers.norm import RMSNorm

__all__ = ["DecoderStack", "ExpertStack", "ExpertMLP",
           "GatedGroupedAttention"]

LOAD_HISTORY = 8    # steps of expert load the model keeps


def _std(cfg):
    return I.Normal(0.0, cfg.initializer_range)


def _out_std(cfg):
    """Output projections into the residual, scaled as GPT-2's."""
    return I.Normal(0.0, cfg.initializer_range / math.sqrt(2 * cfg.layers))


def _linear(cfg, i, o, attr=None, bias=False):
    return Linear(i, o, weight_attr=attr or _std(cfg),
                  bias_attr=None if bias else False)


class ExpertMLP(Layer):
    """``dist.moe.DroplessMoE`` over the routed experts this chip holds,
    beside ``shared_experts`` shared SwiGLU experts that every chip
    computes."""

    def __init__(self, cfg):
        super().__init__()
        self.shared = SwiGLU(cfg.hidden,
                             cfg.shared_experts * cfg.expert_width,
                             weight_attr=_std(cfg), down_attr=_out_std(cfg)) \
            if cfg.shared_experts else None
        self.routed = DroplessMoE(
            cfg.hidden, cfg.expert_width, cfg.experts, cfg.top_k,
            first=cfg.first_expert, held=cfg.experts_held,
            routed_scale=cfg.routed_scale, normalize=cfg.norm_topk,
            weight_attr=_std(cfg), down_attr=_out_std(cfg),
            score=cfg.router_score)

    def forward(self, x):
        y, load = self.routed(x)
        if self.shared is not None:
            y = y + self.shared(x)
        return y, load


class GatedGroupedAttention(Layer):
    """The held heads' part of a causal softmax attention over grouped-query
    heads under a sigmoid gate on its output: ``W_o (att * sigmoid(W_gate
    x))``. ``models.nlp.hybrid_moe``'s: ``cfg.heads_held`` query heads over
    ``cfg.kv_heads_held``, one gate logit a channel of the attention output,
    no positions, every key under the diagonal. ``models.nlp.laguna_moe``
    gives it the rest: ``heads`` / ``kv_heads`` of a layer of its own,
    ``head_gate`` (one logit a head: ``W_gate`` is hidden x heads),
    ``rope`` (``F.rotary_cos_sin``'s arguments after the length: the width
    rotated, which may be part of a head, theta, a YaRN scaling, an attention
    factor) and ``window`` (a query sees its last ``window`` keys).
    ``forward(x, with_gate=True)`` returns the gate beside the result.
    ``models.nlp.ssm_hybrid`` takes the projections and the call alone:
    ``gated=False`` (no ``W_gate``: ``W_o att``) at a ``scale`` of its own.
    ``models.nlp.lfm2_moe`` adds ``qk_norm``, the epsilon of an RMS norm over
    each head of q and of k before the rotation, one ``head_dim``-wide weight
    for all query heads and one for all key heads; ``None``, no norm and no
    weight, is every other family's."""

    def __init__(self, cfg, heads=None, kv_heads=None, head_gate=False,
                 rope=None, window=None, gated=True, scale=None,
                 qk_norm=None):
        super().__init__()
        self.cfg = cfg
        d, dh = cfg.hidden, cfg.head_dim
        self.heads = hq = cfg.heads_held if heads is None else heads
        self.kv_heads = hkv = cfg.kv_heads_held if kv_heads is None else \
            kv_heads
        self.head_gate, self.rope, self.window = head_gate, rope, window
        self.scale = dh ** -0.5 if scale is None else scale
        self.q = _linear(cfg, d, hq * dh)
        self.k, self.v = _linear(cfg, d, hkv * dh), _linear(cfg, d, hkv * dh)
        self.gate = _linear(cfg, d, hq if head_gate else hq * dh) if gated \
            else None
        self.o = _linear(cfg, hq * dh, d, _out_std(cfg))
        self.q_norm = RMSNorm(dh, qk_norm) if qk_norm is not None else None
        self.k_norm = RMSNorm(dh, qk_norm) if qk_norm is not None else None

    def forward(self, x, with_gate=False):
        B, L, dh = x.shape[0], x.shape[1], self.cfg.head_dim

        def heads(t, n):
            return ops.transpose(ops.reshape(t, [B, L, n, dh]), [0, 2, 1, 3])

        q, k = heads(self.q(x), self.heads), heads(self.k(x), self.kv_heads)
        if self.q_norm is not None:
            q, k = self.q_norm(q), self.k_norm(k)
        if self.rope is not None:
            cos, sin = F.rotary_cos_sin(L, *self.rope)
            q, k = F.rotary(q, cos, sin), F.rotary(k, cos, sin)
        att = F.sdpa_bhld(q, k, heads(self.v(x), self.kv_heads),
                          is_causal=True, scale=self.scale,
                          window=self.window)
        att = ops.transpose(att, [0, 2, 1, 3])
        if self.gate is None:
            return self.o(ops.reshape(att, [B, L, self.heads * dh]))
        gate = F.sigmoid(self.gate(x))
        if self.head_gate:      # (B, L, H) over (B, L, H, d)
            att = att * ops.unsqueeze(gate, -1)
        att = ops.reshape(att, [B, L, self.heads * dh])
        if not self.head_gate:  # (B, L, H d) over the same
            att = att * gate
        y = self.o(att)
        return (y, gate) if with_gate else y


class DecoderStack(Layer):
    """The token embedding, ``cfg.layers`` blocks made by ``_block(i)`` and
    run one by one under ``cfg.use_recompute``, the final RMS norm. A family
    adds its logits: ``ExpertStack`` an untied head or, where its
    configuration ties it, ``_tied_logits``; ``ssm_hybrid.SSMHybrid`` reads
    them off the embedding too."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(cfg.vocab_size, cfg.hidden,
                               weight_attr=_std(cfg))
        self.blocks = LayerList([self._block(i) for i in range(cfg.layers)])
        self.final_norm = RMSNorm(cfg.hidden, cfg.rms_eps)

    def _block(self, i):
        """Layer ``i`` of the stack."""
        raise NotImplementedError

    def _tied_logits(self, h):
        """The head that is the embedding: one leaf, two gradients."""
        return ops.matmul(self.final_norm(h), self.embed.weight,
                          transpose_y=True)

    def _run(self, block, x):
        if self.cfg.use_recompute and self.training:
            from ...framework.recompute import recompute

            return recompute(block, x)
        return block(x)

    @staticmethod
    def _keep_stats(buffer, stats):
        """``buffer`` <- [the least first entry, the mean second entry] of
        the sublayers' float32 pairs ``stats`` (nothing where there is
        none), in the buffer's own type: a model cast to bfloat16 keeps its
        buffers so."""
        if stats:
            stats = jnp.stack(stats)
            buffer._replace(jnp.stack(
                [jnp.min(stats[:, 0]), jnp.mean(stats[:, 1])]).astype(
                    buffer._data.dtype))


class ExpertStack(DecoderStack):
    """A stack some of whose blocks hold routed experts, with an untied
    head unless the configuration says ``tie_head`` (then there is no leaf
    ``head`` and the logits are read off the embedding). A block has a
    ``dense`` (no routed experts) and returns, beside
    its state, the slots every routed expert was chosen for (float32, so
    that it can leave a recomputed block); the family's ``hidden(ids)``
    gives (the state after the last block, [load of each expert block]),
    and the model writes the loads to its ``expert_load`` buffer outside the
    recomputed region (a short history, newest last)."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.head = None if getattr(cfg, "tie_head", False) else Linear(
            cfg.hidden, cfg.vocab_size, weight_attr=_std(cfg),
            bias_attr=False)
        # the last calls' slots for every routed expert, newest last, one row
        # an expert layer; int32, so that no dtype cast touches it
        self.register_buffer(
            "expert_load",
            Tensor(jnp.zeros((LOAD_HISTORY, self._expert_layers(),
                              cfg.experts), jnp.int32), _internal=True),
            persistable=False)

    def _expert_layers(self):
        """Rows of a step's load: the layers with routed experts."""
        return sum(not block.dense for block in self.blocks)

    def _logits(self, h):
        if self.head is None:
            return self._tied_logits(h)
        return self.head(self.final_norm(h))

    def _record(self, loads):
        if loads:
            new = ops.stack(loads, axis=0).astype("int32")._data
            self.expert_load._replace(jnp.concatenate(
                [self.expert_load._data[1:], new[None]], axis=0))

    def forward(self, ids):
        h, loads = self.hidden(ids)
        self._record(loads)
        return self._logits(h)

    def expert_load_counts(self, steps=None):
        """numpy (expert layers, experts): the slots each routed expert (all
        of them, held here or not) was chosen for in the last forward pass;
        with ``steps`` (at most ``LOAD_HISTORY``), (steps, expert layers,
        experts) of the last ``steps`` passes, newest last."""
        import numpy as np

        history = np.asarray(self.expert_load._data)
        return history[-1] if steps is None else history[-int(steps):]

    def publish_gauges(self):
        """``obs`` gauges of the last step's routing: slots that landed on
        the experts held here, the fullest held expert over their mean, the
        most passes an expert layer ran over its windows
        (``moe.window_passes_max``) and the held slots over the rows the
        layers worked on (``moe.window_live_share``; a layer without a
        window works once on all its rows). Host arithmetic on the counts
        the step writes anyway. It waits for the step in flight, so no step
        calls it: ``TrainStep`` hands it to the registry as a collector,
        which runs it when the registry is read (``Registry.collect()``)."""
        from ...obs import metrics

        c = self.cfg
        counts = self.expert_load_counts()
        held = counts[:, c.first_expert:c.first_expert + c.experts_held]
        metrics.gauge("moe.slots_held").set(float(held.sum()))
        mean = held.mean(axis=1)
        metrics.gauge("moe.load_max_over_mean").set(
            float((held.max(axis=1) / mean.clip(min=1e-9)).mean()))
        # every slot is counted, so a layer's counts add up to tokens x k
        # (nothing before the first step)
        slots = int(counts.sum(axis=1).max(initial=0))
        rows = slots and window_rows(slots // c.top_k, c.top_k,
                                     c.experts_held, c.experts)
        passes = -(-held.sum(axis=1) // rows) if 0 < rows < slots else \
            (counts.sum(axis=1) > 0).astype(int)
        metrics.gauge("moe.window_passes_max").set(
            float(passes.max(initial=0)))
        metrics.gauge("moe.window_live_share").set(
            float(held.sum() / max(passes.sum() * rows, 1)))
