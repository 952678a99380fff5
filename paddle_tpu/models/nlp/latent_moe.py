"""A decoder family of current open models: latent attention (MLA), sigmoid-
routed fine-grained experts beside a shared one, a residual of ``n`` streams
mixed by per-token constrained maps, and an optional multi-token-prediction
head. The equations (T tokens, C hidden, n streams; ``RMS_w`` an RMS norm
with a learned weight):

- **Residual.** With one stream (``streams=1``) the plain pre-norm residual
  of DeepSeek-V3 and its kin: state ``x`` (B, L, C), ``x = x + F(RMS_w(x))``
  for attention and then the MLP, ``logits = RMS_w(x) W_head``; no maps, no
  ``HyperConnection`` leaves, no ``hc_*`` op. With ``n`` > 1 streams:
  state ``X`` (T, n, C) (held streams-first, (n, B, L, C):
  ``nn.functional.decoder``), ``X_0[:, j] = Emb(ids)`` for every j.
  For each sublayer F (attention, then MLP, each with maps of its own):
  ``[H_pre, H_post, H_res] = hc_maps(X)`` (``nn.functional.hc_maps``:
  sigmoid, 2 sigmoid and Sinkhorn-normalised maps of the RMS-normed,
  flattened state); ``h = sum_j H_pre[j] X[:, j]``; ``y = F(RMS_w(h))``;
  ``X'[:, i] = sum_j H_res[i, j] X[:, j] + H_post[i] y``. After the last
  layer ``logits = RMS_w(sum_j X[:, j]) W_head``.
- **Latent attention** (DeepSeek-V2, no absorption in training): ``c_q =
  RMS_w(h W_qa)``, ``q = c_q W_qb`` in heads of ``[q_nope, q_rope]``;
  ``[c_kv, k_rope] = h W_kva``; ``[k_nope, v] = RMS_w(c_kv) W_kvb`` a head;
  ``q = [q_nope, rope(q_rope)]``, ``k = [k_nope, rope(k_rope)]`` with
  ``k_rope`` shared by all heads; one causal ``sdpa`` with ``Dqk = nope +
  rope`` and ``Dv`` of its own; scale ``Dqk^-0.5 m^2`` with YaRN's ``m``.
- **Experts** (DeepSeek-V3): ``decoder_stack.ExpertMLP``; the first
  ``first_dense`` layers are a dense SwiGLU.
- **Multi-token prediction**, depth 1 (DeepSeek-V3 section 2.2): ``h' = W_eh
  [RMS_w(h_i); RMS_w(Emb(t_{i+1}))]``, one more expert block (``h'`` copied
  to the n streams and their sum read out), the shared final norm and head,
  cross-entropy against ``t_{i+2}``; loss = main + ``mtp_lambda`` x MTP.
  All of its device work, forward and backward, lies under the program
  scope ``mtp`` (``core.dispatch.program_scope``), and the two terms of the
  last step's loss are kept in the buffer ``loss_terms``.

The embedding, the blocks' run, the final norm, the head and the experts'
load are ``decoder_stack.ExpertStack``'s.
"""
from __future__ import annotations

import jax.numpy as jnp

from ... import ops
from ...core.dispatch import program_scope
from ...core.tensor import Tensor
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import Layer
from ...nn.layers.common import Linear, SwiGLU
from ...nn.layers.norm import RMSNorm
from .decoder_stack import ExpertMLP, ExpertStack, _out_std, _std

__all__ = ["LatentMoEConfig", "LatentMoE", "LatentMoEBlock",
           "LatentAttention", "HyperConnection", "latent_moe_loss",
           "latent_moe_tiny"]

IGNORE = -100


class LatentMoEConfig:
    def __init__(self, vocab_size=131072, hidden=3584, layers=40,
                 first_dense=2, dense_width=9216, heads=32, q_lora_rank=768,
                 kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                 v_head_dim=128, rope_theta=10000.0, rope_scaling=None,
                 experts=64, expert_width=1024, shared_experts=1, top_k=4,
                 routed_scale=2.0, norm_topk=True, first_expert=0,
                 experts_held=None, streams=4, sinkhorn_iters=20, hc_eps=1e-6,
                 hc_clamp=(-30.0, 30.0), hc_alpha_init=0.01,
                 hc_res_init=4.0, rms_eps=1e-6, mtp_layers=0, mtp_lambda=0.3,
                 initializer_range=0.02, use_recompute=False,
                 router_score="sigmoid"):
        self.vocab_size, self.hidden, self.layers = vocab_size, hidden, layers
        self.first_dense, self.dense_width = first_dense, dense_width
        self.heads = heads
        self.router_score = router_score    # DroplessMoE's ``score``
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.qk_nope_dim, self.qk_rope_dim = qk_nope_dim, qk_rope_dim
        self.v_head_dim = v_head_dim
        self.rope_theta, self.rope_scaling = rope_theta, rope_scaling
        self.experts, self.expert_width = experts, expert_width
        self.shared_experts, self.top_k = shared_experts, top_k
        self.routed_scale, self.norm_topk = routed_scale, norm_topk
        self.first_expert = first_expert
        self.experts_held = experts if experts_held is None else experts_held
        self.streams, self.sinkhorn_iters = streams, sinkhorn_iters
        self.hc_eps, self.hc_clamp = hc_eps, tuple(hc_clamp)
        # the gates of the maps' input-dependent part start at alpha_init and
        # H_res's logits at res_init * I (identity-dominant); they are stored
        # as a multiple of the first and an offset from the second
        self.hc_alpha_init, self.hc_res_init = hc_alpha_init, hc_res_init
        self.rms_eps = rms_eps
        self.mtp_layers, self.mtp_lambda = mtp_layers, mtp_lambda
        self.initializer_range = initializer_range
        self.use_recompute = use_recompute  # jax.checkpoint per block

    @property
    def softmax_scale(self):
        m = 1.0
        if self.rope_scaling:
            m = F.yarn_mscale(self.rope_scaling["factor"],
                              self.rope_scaling.get("mscale_all_dim", 0.0))
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5 * m * m


def latent_moe_tiny(**kw):
    base = dict(vocab_size=256, hidden=64, layers=3, first_dense=1,
                dense_width=96, heads=2, q_lora_rank=32, kv_lora_rank=16,
                qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                rope_scaling={"factor": 4, "beta_fast": 32, "beta_slow": 1,
                              "mscale": 1, "mscale_all_dim": 1,
                              "original_max_position_embeddings": 16},
                experts=8, expert_width=32, top_k=2, streams=4,
                sinkhorn_iters=20)
    base.update(kw)
    return LatentMoEConfig(**base)


class HyperConnection(Layer):
    """The maps of one sublayer's constrained multi-stream residual."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        n = cfg.streams
        self.phi = self.create_parameter((n * cfg.hidden, 2 * n + n * n),
                                         attr=_std(cfg))
        self.alpha = self.create_parameter(
            (3,), default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter((2 * n + n * n,), is_bias=True)

    def forward(self, x):
        c = self.cfg
        return F.hc_maps(x, self.phi, self.alpha, self.bias,
                         iters=c.sinkhorn_iters, eps=c.hc_eps,
                         clamp=c.hc_clamp, alpha_scale=c.hc_alpha_init,
                         res_offset=c.hc_res_init, norm_eps=c.rms_eps)


class LatentAttention(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        d, h = cfg.hidden, cfg.heads
        dqk = cfg.qk_nope_dim + cfg.qk_rope_dim

        def lin(i, o, attr=None):
            return Linear(i, o, weight_attr=attr or _std(cfg),
                          bias_attr=False)

        self.q_a = lin(d, cfg.q_lora_rank)
        self.q_norm = RMSNorm(cfg.q_lora_rank, cfg.rms_eps)
        self.q_b = lin(cfg.q_lora_rank, h * dqk)
        self.kv_a = lin(d, cfg.kv_lora_rank + cfg.qk_rope_dim)
        self.kv_norm = RMSNorm(cfg.kv_lora_rank, cfg.rms_eps)
        self.kv_b = lin(cfg.kv_lora_rank,
                        h * (cfg.qk_nope_dim + cfg.v_head_dim))
        self.o = lin(h * cfg.v_head_dim, d, _out_std(cfg))

    def forward(self, x):
        c = self.cfg
        B, L, H = x.shape[0], x.shape[1], c.heads
        nope, rope, dv = c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim
        cos, sin = F.rotary_cos_sin(L, rope, c.rope_theta, c.rope_scaling)

        def heads(t, width):
            return ops.transpose(ops.reshape(t, [B, L, H, width]),
                                 [0, 2, 1, 3])

        # a head's last ``rope`` columns turn; the rest pass
        q = F.rotary(heads(self.q_b(self.q_norm(self.q_a(x))), nope + rope),
                     cos, sin, offset=nope)
        c_kv, k_r = ops.split(self.kv_a(x), [c.kv_lora_rank, rope], axis=-1)
        kv = heads(self.kv_b(self.kv_norm(c_kv)), nope + dv)
        k_n, v = ops.split(kv, [nope, dv], axis=-1)
        k_r = F.rotary(ops.reshape(k_r, [B, 1, L, rope]), cos, sin)
        k = ops.concat([k_n, ops.expand(k_r, [B, H, L, rope])], axis=-1)
        att = F.sdpa_bhld(q, k, v, is_causal=True, scale=c.softmax_scale)
        att = ops.reshape(ops.transpose(att, [0, 2, 1, 3]), [B, L, H * dv])
        return self.o(att)


class LatentMoEBlock(Layer):
    """``forward(X) -> (X', load)`` over the streams ``X`` (n, B, L, C), or
    over the one state (B, L, C) of a plain residual (``streams=1``, which
    has no maps); ``load`` is the routed experts' slot counts (zeros for a
    dense block)."""

    def __init__(self, cfg, dense):
        super().__init__()
        self.cfg, self.dense = cfg, dense
        if cfg.streams > 1:
            self.attn_hc = HyperConnection(cfg)
        self.attn_norm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.attn = LatentAttention(cfg)
        if cfg.streams > 1:
            self.mlp_hc = HyperConnection(cfg)
        self.mlp_norm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.mlp = SwiGLU(cfg.hidden, cfg.dense_width, weight_attr=_std(cfg),
                          down_attr=_out_std(cfg)) if dense else ExpertMLP(cfg)

    def _sublayer(self, x, hc, norm, fn):
        pre, post, res = hc(x)
        y = fn(norm(F.hc_read(x, pre)))
        load = None
        if isinstance(y, tuple):
            y, load = y
        return F.hc_mix(x, y, post, res), load

    def forward(self, x):
        if self.cfg.streams > 1:
            x, _ = self._sublayer(x, self.attn_hc, self.attn_norm, self.attn)
            x, load = self._sublayer(x, self.mlp_hc, self.mlp_norm, self.mlp)
        else:
            x = x + self.attn(self.attn_norm(x))
            h = self.mlp_norm(x)
            y, load = (self.mlp(h), None) if self.dense else self.mlp(h)
            x = x + y
        if load is None:
            load = Tensor(jnp.zeros((self.cfg.experts,), jnp.float32),
                          _internal=True)
        return x, load


class MTPHead(Layer):
    """One multi-token-prediction module: the projection of [hidden; next
    token's embedding] and one more expert block; embedding, final norm and
    head are the main model's."""

    def __init__(self, cfg):
        super().__init__()
        self.hnorm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.enorm = RMSNorm(cfg.hidden, cfg.rms_eps)
        self.proj = Linear(2 * cfg.hidden, cfg.hidden, weight_attr=_std(cfg),
                           bias_attr=False)
        self.block = LatentMoEBlock(cfg, dense=False)


class LatentMoE(ExpertStack):
    def __init__(self, cfg):
        super().__init__(cfg)
        self.mtp = MTPHead(cfg) if cfg.mtp_layers else None
        if cfg.mtp_layers:
            # the last step's two cross-entropies (main, MTP), written by
            # ``latent_moe_loss``
            self.register_buffer(
                "loss_terms", Tensor(jnp.zeros((2,), jnp.float32),
                                     _internal=True), persistable=False)

    # -- pieces ---------------------------------------------------------------
    def _block(self, i):
        """Layer ``i`` of the stack: ``forward(x) -> (x', load)``, ``dense``
        where it has no routed experts."""
        return LatentMoEBlock(self.cfg, dense=i < self.cfg.first_dense)

    def _expert_layers(self):
        # the MTP block's row is the last
        return super()._expert_layers() + (1 if self.cfg.mtp_layers else 0)

    def _streams(self, h):
        """``h`` (B, L, C) copied to the n streams, which lead: (n, B, L, C)
        (``nn.functional.decoder``'s layout); a plain residual's state is
        ``h`` itself."""
        if self.cfg.streams == 1:
            return h
        B, L, C = h.shape
        return ops.expand(ops.unsqueeze(h, 0), [self.cfg.streams, B, L, C])

    def _readout(self, x):
        return x if self.cfg.streams == 1 else ops.sum(x, axis=0)

    def hidden(self, ids):
        """(the state after the last block, its streams summed, [load of
        each expert block])."""
        x = self._streams(self.embed(ids))
        loads = []
        for block in self.blocks:
            x, load = self._run(block, x)
            if not block.dense:
                loads.append(load)
        return self._readout(x), loads

    def forward_mtp(self, ids, next_ids):
        """(main logits, MTP logits): position i of the second predicts the
        token after ``next_ids[i]``."""
        h, loads = self.hidden(ids)
        m = self.mtp
        with program_scope("mtp"):
            joined = ops.concat([m.hnorm(h), m.enorm(self.embed(next_ids))],
                                axis=-1)
            x, load = self._run(m.block, self._streams(m.proj(joined)))
            extra = self._logits(self._readout(x))
        self._record(loads + [load])
        return self._logits(h), extra

    def publish_gauges(self):
        """Beside the routing gauges, with a multi-token-prediction module:
        the two terms of its loss (``loss.lm``, ``loss.mtp``)."""
        from ...obs import metrics

        super().publish_gauges()
        if self.mtp is not None:
            main, extra = (float(t) for t in self.loss_terms._data)
            metrics.gauge("loss.lm").set(main)
            metrics.gauge("loss.mtp").set(extra)


def latent_moe_loss(model, ids, labels):
    """Next-token cross-entropy (labels already shifted by one) of any
    ``DecoderStack``, plus ``mtp_lambda`` times the multi-token-prediction
    module's where the model has one: it sees ``labels`` as the next tokens
    and predicts the ones after them, so its last position has no target."""
    V = model.cfg.vocab_size

    def ce(logits, target):
        return F.cross_entropy(ops.reshape(logits, [-1, V]),
                               ops.reshape(target, [-1]),
                               ignore_index=IGNORE)

    if getattr(model, "mtp", None) is None:
        return ce(model(ids), labels)
    main, extra = model.forward_mtp(ids, labels)
    main = ce(main, labels)
    with program_scope("mtp"):
        after = ops.concat([labels[:, 1:],
                            ops.full_like(labels[:, :1], IGNORE)], axis=1)
        extra = ce(extra, after)
    # in the buffer's own type: a model cast to bfloat16 keeps its buffers so
    model.loss_terms._replace(jnp.stack(
        [main._data, extra._data]).astype(model.loss_terms._data.dtype))
    return main + model.cfg.mtp_lambda * extra
