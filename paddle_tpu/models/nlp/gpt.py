"""GPT: decoder-only LM — the 4D-parallel flagship.

Ref (capability target): the reference's ERNIE/GPT-era model-parallel LMs
built on c_allgather/c_reducescatter collective ops and Fleet hybrid
parallelism. TPU-native design:

- dp: batch sharded on the 'data' mesh axis (grad psum by GSPMD)
- tp: Column/RowParallel projections + VocabParallelEmbedding over 'model'
- sp: activations sharded along sequence on 'sp' between attention blocks
  (Megatron-SP style via sharding constraints); ring attention
  (dist/ring_attention.py) is the long-context attention path
- pp: GPTPipeline stacks per-layer params on a leading stage axis and runs
  the GPipe schedule over the 'pipe' axis
- everything compiles into ONE donated XLA executable via
  DistributedTrainStep; bf16 activations with f32 softmax/normalization.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ... import ops
from ...core.tensor import Tensor
from ...nn import functional as F
from ...nn.layer import Layer, LayerList
from ...nn import initializer as I
from ...nn.layers.common import Linear, Dropout, Embedding
from ...nn.layers.norm import LayerNorm
from ...dist.tp_layers import (ColumnParallelLinear, RowParallelLinear,
                               VocabParallelEmbedding, mark_sharding,
                               _constrain)
from ...dist.env import get_mesh
from ...nn.layers.transformer import MultiHeadAttention as _MHA

StaticKVCache = _MHA.StaticKVCache  # shared fixed-size KV-cache record

__all__ = ["GPTConfig", "GPT", "GPTBlock", "gpt_loss", "GPTPipeline",
           "gpt_tiny", "gpt_small"]


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden=768, layers=12, heads=12,
                 max_seq=1024, dropout=0.1, mp_axis="model", sp_axis="sp",
                 use_ring_attention=False, dtype="float32",
                 initializer_range=0.02, use_recompute=False):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.max_seq = max_seq
        self.dropout = dropout
        self.mp_axis = mp_axis
        self.sp_axis = sp_axis
        self.use_ring_attention = use_ring_attention
        self.dtype = dtype
        self.initializer_range = initializer_range
        self.use_recompute = use_recompute  # jax.checkpoint per block


def gpt_tiny(**kw):
    return GPTConfig(vocab_size=1024, hidden=128, layers=2, heads=4,
                     max_seq=128, **kw)


def gpt_small(**kw):
    return GPTConfig(vocab_size=50304, hidden=768, layers=12, heads=12, **kw)


def _sp_constrain(x, cfg):
    """Shard activations (B, L, D) along sequence on the sp axis."""
    mesh = get_mesh()
    if mesh is not None and cfg.sp_axis in mesh.shape and \
            mesh.shape[cfg.sp_axis] > 1:
        return _constrain(x, (cfg.sp_axis, None))
    return x


class GPTAttention(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.heads = cfg.heads
        self.head_dim = cfg.hidden // cfg.heads
        std = cfg.initializer_range
        self.qkv = ColumnParallelLinear(
            cfg.hidden, 3 * cfg.hidden, gather_output=False,
            weight_attr=I.Normal(0.0, std), mp_axis=cfg.mp_axis)
        self.proj = RowParallelLinear(
            cfg.hidden, cfg.hidden, input_is_parallel=True,
            weight_attr=I.Normal(0.0, std / math.sqrt(2 * cfg.layers)),
            mp_axis=cfg.mp_axis)
        self.drop = Dropout(cfg.dropout)

    def forward(self, x, cache=None):
        B, L = x.shape[0], x.shape[1]
        qkv = self.qkv(x)
        q, k, v = ops.split(qkv, 3, axis=-1)

        def heads_of(t, l):
            t = ops.reshape(t, [B, l, self.heads, self.head_dim])
            return ops.transpose(t, [0, 2, 1, 3])

        q, k, v = heads_of(q, L), heads_of(k, L), heads_of(v, L)
        if isinstance(cache, StaticKVCache):
            return self._forward_static_kv(q, k, v, cache, B, L)
        new_cache = None
        if cache is not None:
            pk, pv = cache
            k = ops.concat([pk, k], axis=2)
            v = ops.concat([pv, v], axis=2)
            new_cache = (k, v)
        mesh = get_mesh()
        if self.cfg.use_ring_attention and cache is None and \
                mesh is not None and self.cfg.sp_axis in mesh.shape and \
                mesh.shape[self.cfg.sp_axis] > 1:
            from ...dist.ring_attention import ring_attention

            att = ring_attention(q, k, v, axis_name=self.cfg.sp_axis,
                                 causal=True)
        else:
            att = F.sdpa_bhld(q, k, v, is_causal=cache is None,
                              dropout_p=self.cfg.dropout,
                              training=self.training)
        att = ops.reshape(ops.transpose(att, [0, 2, 1, 3]),
                          [B, L, self.cfg.hidden])
        out = self.drop(self.proj(att))
        return out if cache is None and new_cache is None else (out, new_cache)

    def _forward_static_kv(self, q, k_new, v_new, cache, B, L):
        """Incremental attention against fixed-size KV buffers — the
        shared jittable decode core (nn/layers/transformer.py
        static_kv_attention) plus this block's output projection."""
        from ...nn.layers.transformer import static_kv_attention

        att, new_cache = static_kv_attention(
            q, k_new, v_new, cache, dropout_p=self.cfg.dropout,
            training=self.training)
        att = ops.reshape(ops.transpose(att, [0, 2, 1, 3]),
                          [B, L, self.cfg.hidden])
        out = self.drop(self.proj(att))
        return out, new_cache


class GPTBlock(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.ln1 = LayerNorm(cfg.hidden)
        self.attn = GPTAttention(cfg)
        self.ln2 = LayerNorm(cfg.hidden)
        std = cfg.initializer_range
        self.fc1 = ColumnParallelLinear(cfg.hidden, 4 * cfg.hidden,
                                        gather_output=False,
                                        weight_attr=I.Normal(0.0, std),
                                        mp_axis=cfg.mp_axis)
        self.fc2 = RowParallelLinear(4 * cfg.hidden, cfg.hidden,
                                     input_is_parallel=True,
                                     weight_attr=I.Normal(
                                         0.0, std / math.sqrt(2 * cfg.layers)),
                                     mp_axis=cfg.mp_axis)
        self.drop = Dropout(cfg.dropout)

    def forward(self, x, cache=None):
        if cache is None:
            x = x + self.attn(self.ln1(x))
            x = _sp_constrain(x, self.cfg)
            x = x + self.drop(self.fc2(F.gelu(self.fc1(self.ln2(x)),
                                              approximate=True)))
            return _sp_constrain(x, self.cfg)
        att, new_cache = self.attn(self.ln1(x), cache=cache)
        x = x + att
        x = x + self.drop(self.fc2(F.gelu(self.fc1(self.ln2(x)),
                                          approximate=True)))
        return x, new_cache


class GPT(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        std = cfg.initializer_range
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden,
                                          weight_attr=I.Normal(0.0, std),
                                          mp_axis=cfg.mp_axis)
        self.wpe = Embedding(cfg.max_seq, cfg.hidden,
                             weight_attr=I.Normal(0.0, std))
        self.drop = Dropout(cfg.dropout)
        self.blocks = LayerList([GPTBlock(cfg) for _ in range(cfg.layers)])
        self.ln_f = LayerNorm(cfg.hidden)
        # LM head tied to wte (ref: weight sharing in GPT); logits computed
        # against the (vocab-sharded) embedding matrix
        if cfg.dtype != "float32":
            self.astype(cfg.dtype)

    def forward(self, ids, cache=None):
        B, L = ids.shape[0], ids.shape[1]
        if cache is None:
            pos = ops.arange(0, L, dtype="int64")
        elif isinstance(cache[0], StaticKVCache):
            # write index (possibly traced) is the global position;
            # int32 — positions fit trivially and x64 is never enabled
            idx = cache[0].idx
            idx = idx._data if isinstance(idx, Tensor) else idx
            pos = Tensor(jnp.arange(L, dtype=jnp.int32) +
                         jnp.asarray(idx, jnp.int32), _internal=True)
        else:
            pos = ops.arange(cache[0][0].shape[2],
                             cache[0][0].shape[2] + L, dtype="int64")
        x = self.wte(ids) + self.wpe(pos)
        x = self.drop(x)
        x = _sp_constrain(x, self.cfg)
        new_caches = [] if cache is not None else None
        for i, blk in enumerate(self.blocks):
            if cache is None:
                if self.cfg.use_recompute and self.training:
                    from ...framework.recompute import recompute

                    x = recompute(blk, x)
                else:
                    x = blk(x)
            else:
                x, c = blk(x, cache=cache[i])
                new_caches.append(c)
        x = self.ln_f(x)
        logits = ops.matmul(x, ops.transpose(self.wte.weight, [1, 0]))
        # the tied head inherits wte's vocab sharding: keep the logits
        # vocab-sharded over the model axis (never gathered, and the
        # batch never replicated) for the loss to consume
        logits = _constrain(logits, (self.cfg.mp_axis,))
        return logits if cache is None else (logits, new_caches)

    def set_recompute(self, value=True):
        """fleet protocol: DistributedStrategy.recompute toggles this."""
        self.cfg.use_recompute = bool(value)

    def init_cache(self, batch_size):
        import numpy as np

        shape = (batch_size, self.cfg.heads, 0, self.cfg.hidden // self.cfg.heads)
        z = Tensor(jnp.zeros(shape, self.wte.weight.dtype), _internal=True)
        return [(z, z) for _ in range(self.cfg.layers)]

    def init_static_cache(self, batch_size, max_length):
        """Fixed-size per-layer KV buffers for the jittable decode."""
        shape = (batch_size, self.cfg.heads, max_length,
                 self.cfg.hidden // self.cfg.heads)
        return [StaticKVCache(
            Tensor(jnp.zeros(shape, self.wte.weight.dtype), _internal=True),
            Tensor(jnp.zeros(shape, self.wte.weight.dtype), _internal=True),
            jnp.zeros((), jnp.int32)) for _ in range(self.cfg.layers)]

    def generate(self, ids, max_new_tokens=32, temperature=1.0, top_k=None):
        """Greedy/sampled decode with KV cache (eager path)."""
        import numpy as np

        cache = self.init_cache(ids.shape[0])
        out = ids
        cur = ids
        for _ in range(max_new_tokens):
            logits, cache = self.forward(cur, cache=cache)
            last = logits[:, -1]
            if temperature == 0.0:
                nxt = ops.argmax(last, axis=-1, keepdim=True)
            else:
                last = last / temperature
                if top_k is not None:
                    kth = ops.topk(last, top_k, axis=-1)[0][:, -1:]
                    last = ops.where(last < kth,
                                     ops.full_like(last, -1e30), last)
                probs = F.softmax(last, axis=-1)
                nxt = ops.multinomial(probs, 1)
            nxt = nxt.astype("int64")
            out = ops.concat([out, nxt], axis=1)
            cur = nxt
        return out

    # -- single-executable decode (static KV cache + lax.scan) -------------
    def _traced_generate(self, ids, key, *, max_new_tokens, temperature,
                         top_k):
        from ...inference.decoder import tree_unwrap, tree_wrap

        B, Lp = ids.shape
        max_len = Lp + max_new_tokens
        caches = self.init_static_cache(B, max_len)

        def pick(last, k):  # last: (B, V) raw array
            if temperature == 0.0:
                return jnp.argmax(last, axis=-1)
            logits = last.astype(jnp.float32) / temperature
            if top_k is not None:
                kth = jax.lax.top_k(logits, int(top_k))[0][:, -1:]
                logits = jnp.where(logits < kth, -1e30, logits)
            return jax.random.categorical(k, logits, axis=-1)

        keys = jax.random.split(key, max_new_tokens)
        logits, caches = self.forward(Tensor(ids, _internal=True),
                                      cache=caches)  # prefill
        nxt = pick(logits._data[:, -1], keys[0])

        def body(carry, k):
            cur, st = carry
            lg, st_t = self.forward(
                Tensor(cur[:, None], _internal=True), cache=tree_wrap(st))
            tok = pick(lg._data[:, -1], k)
            return (tok, tree_unwrap(st_t)), tok

        (_, _), toks = jax.lax.scan(body, (nxt, tree_unwrap(caches)),
                                    keys[1:])
        gen = jnp.concatenate([nxt[:, None],
                               jnp.transpose(toks, (1, 0))], axis=1) \
            if max_new_tokens > 1 else nxt[:, None]
        # int32 throughout (x64 is never enabled; values are token ids)
        return jnp.concatenate([ids.astype(jnp.int32),
                                gen.astype(jnp.int32)], axis=1)

    def generate_xla(self, ids, max_new_tokens=32, temperature=0.0,
                     top_k=None, seed=0):
        """Whole-decode jit: prefill + lax.scan token loop in ONE XLA
        executable over fixed-size KV buffers — no per-token dispatch or
        host sync (``generate`` above pays both every token). Greedy at
        temperature 0.0, else top-k/temperature sampling. One cached
        executable per (shape, knobs) signature; parameters are threaded
        as jit ARGUMENTS (not baked constants), so weight updates between
        calls are honored without retracing."""
        import functools

        from ...framework.jit import _rebind

        ids_arr = ids._data if isinstance(ids, Tensor) else jnp.asarray(ids)
        if max_new_tokens <= 0:  # degenerate case: eager returns prompt
            return Tensor(ids_arr.astype(jnp.int32), _internal=True)
        key = jax.random.PRNGKey(seed)
        # the active mesh shapes the traced sharding constraints, so it
        # is part of the executable's identity (tp-sharded serving)
        sig = (tuple(ids_arr.shape), int(max_new_tokens),
               float(temperature), top_k, self.training, get_mesh())
        cache = getattr(self, "_xla_gen_cache", None)
        if cache is None:
            cache = self._xla_gen_cache = {}
        if sig not in cache:
            params = list(self.parameters())
            traced = functools.partial(
                self._traced_generate, max_new_tokens=int(max_new_tokens),
                temperature=float(temperature), top_k=top_k)

            def with_params(param_arrs, ids_a, k, _traced=traced,
                            _params=params):
                with _rebind(_params, list(param_arrs)):
                    return _traced(ids_a, k)

            cache[sig] = (params, jax.jit(with_params))
        params, fn = cache[sig]
        return Tensor(fn([p._data for p in params], ids_arr, key),
                      _internal=True)


def gpt_loss(model, ids, labels):
    """Next-token CE (labels already shifted)."""
    logits = model(ids)
    V = logits.shape[-1]
    return F.cross_entropy(ops.reshape(logits, [-1, V]),
                           ops.reshape(labels, [-1]))


class GPTPipeline:
    """Pipeline-parallel GPT (SURVEY §2 #23): per-layer block params
    stacked on a leading stage axis sharded over 'pipe'; embeddings and
    the final LN/LM-head run replicated around the GPipe schedule.

    Built FROM a ``GPT`` model — the stacked arrays are snapshots of the
    model's block weights, so single-device parity is directly testable
    and the full forward (ids -> logits) matches ``GPT.forward``.
    Homogeneous blocks make the schedule a plain lax.scan; with a
    ``batch_axis`` the same shard_map runs dp x pp.
    """

    def __init__(self, model, num_microbatches=4, axis_name="pipe",
                 batch_axis=None):
        assert isinstance(model, GPT), "build GPTPipeline from a GPT model"
        # active dropout would draw its keys once at trace time and replay
        # the same masks every step (and break GPT.forward parity)
        assert not model.training or model.cfg.dropout == 0.0, \
            "GPTPipeline needs model.eval() or cfg.dropout == 0.0"
        self.model = model
        self.cfg = model.cfg
        self.num_microbatches = num_microbatches
        self.axis_name = axis_name
        self.batch_axis = batch_axis
        self.param_names = [n for n, _ in model.blocks[0].named_parameters()]
        self.stacked = self.snapshot_blocks()

    def snapshot_blocks(self):
        """Re-stack block weights from the model (call after updates)."""
        dicts = [dict(b.named_parameters()) for b in self.model.blocks]
        return {n: jnp.stack([d[n]._data for d in dicts])
                for n in self.param_names}

    def _block_apply(self, params, x):
        """One block applied with explicit param arrays (pure, traceable)."""
        blk = self.model.blocks[0]
        named = dict(blk.named_parameters())
        from ...framework.jit import _rebind

        tensors = [named[n] for n in self.param_names]
        arrays = [params[n] for n in self.param_names]
        with _rebind(tensors, arrays):
            out = blk(Tensor(x, _internal=True))
        return out._data

    def blocks_forward(self, x, stacked=None):
        """(B, L, D) activations through the pipelined block stack."""
        from ...dist.pipeline import pipeline_forward

        arr = x._data if isinstance(x, Tensor) else jnp.asarray(x)
        out = pipeline_forward(self._block_apply,
                               stacked if stacked is not None
                               else self.stacked, arr,
                               self.num_microbatches, self.axis_name,
                               batch_axis=self.batch_axis)
        return Tensor(out, _internal=True) if isinstance(x, Tensor) else out

    def forward(self, ids, stacked=None):
        """Full ids -> logits, matching GPT.forward with dropout off."""
        m = self.model
        L = ids.shape[1]
        pos = ops.arange(0, L, dtype="int64")
        x = m.wte(ids) + m.wpe(pos)
        x = self.blocks_forward(x, stacked=stacked)
        x = m.ln_f(x)
        return ops.matmul(x, ops.transpose(m.wte.weight, [1, 0]))

    __call__ = forward

    def loss(self, ids, labels, stacked=None):
        logits = self.forward(ids, stacked=stacked)
        V = logits.shape[-1]
        return F.cross_entropy(ops.reshape(logits, [-1, V]),
                               ops.reshape(labels, [-1]))

    def train_step_fn(self, lr=1e-3):
        """Pure jittable SGD step over the stacked block params: proves
        grads flow back through the ppermute ring (embeddings/head stay
        frozen constants here; DistributedTrainStep owns the full-model
        path)."""

        def step(stacked, ids, labels):
            def loss_of(st):
                l = self.loss(Tensor(ids, _internal=True),
                              Tensor(labels, _internal=True), stacked=st)
                return l._data

            loss, grads = jax.value_and_grad(loss_of)(stacked)
            new = {k: v - lr * grads[k] for k, v in stacked.items()}
            return loss, new

        return step
