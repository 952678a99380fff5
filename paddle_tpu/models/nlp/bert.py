"""BERT encoder with MLM + NSP pretraining heads.

Ref (capability target): the reference-era BERT-Base pretrain recipe named
in BASELINE.json ("BERT-Base pretrain (Fleet CollectiveOptimizer, fp16
AMP)"). TPU-native: the encoder is jnp matmul/attention graphs that fuse
into one XLA executable; recommended recipe is bf16 autocast (amp/) +
data-parallel mesh + the pallas flash-attention path for long sequences.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import ops
from ...core import dispatch
from ...framework.recompute import _region
from ...nn import Layer
from ...nn.layers.common import Linear, Embedding, Dropout
from ...nn.layers.norm import LayerNorm
from ...nn.layers.transformer import TransformerEncoder, TransformerEncoderLayer
from ...nn import functional as F
from ...nn import initializer as I

__all__ = ["BertConfig", "BertModel", "BertForPretraining", "bert_base",
           "bert_tiny", "bert_pretrain_loss", "compact_rows"]


class BertConfig:
    def __init__(self, vocab_size=30522, hidden=768, layers=12, heads=12,
                 intermediate=3072, max_position=512, type_vocab=2,
                 dropout=0.1, initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.intermediate = intermediate
        self.max_position = max_position
        self.type_vocab = type_vocab
        self.dropout = dropout
        self.initializer_range = initializer_range


def bert_base(**kw):
    return BertConfig(**kw)


def bert_tiny(**kw):
    kw.setdefault("vocab_size", 1024)
    kw.setdefault("hidden", 128)
    kw.setdefault("layers", 2)
    kw.setdefault("heads", 2)
    kw.setdefault("intermediate", 512)
    kw.setdefault("max_position", 128)
    return BertConfig(**kw)


class BertEmbeddings(Layer):
    def __init__(self, cfg):
        super().__init__()
        std = cfg.initializer_range
        self.word = Embedding(cfg.vocab_size, cfg.hidden,
                              weight_attr=I.Normal(0.0, std))
        self.position = Embedding(cfg.max_position, cfg.hidden,
                                  weight_attr=I.Normal(0.0, std))
        self.token_type = Embedding(cfg.type_vocab, cfg.hidden,
                                    weight_attr=I.Normal(0.0, std))
        self.norm = LayerNorm(cfg.hidden)
        self.drop = Dropout(cfg.dropout)

    def forward(self, ids, token_type_ids=None):
        L = ids.shape[1]
        pos = ops.arange(0, L, dtype="int64")
        x = self.word(ids) + self.position(pos)
        if token_type_ids is not None:
            x = x + self.token_type(token_type_ids)
        return self.drop(self.norm(x))


class BertModel(Layer):
    """Encoder trunk: embeddings -> N transformer layers -> pooled [CLS]."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)
        enc_layer = TransformerEncoderLayer(
            cfg.hidden, cfg.heads, cfg.intermediate, dropout=cfg.dropout,
            activation="gelu")
        self.encoder = TransformerEncoder(enc_layer, cfg.layers)
        self.pooler = Linear(cfg.hidden, cfg.hidden)

    def attn_mask(self, attention_mask):
        """(B, L) 1/0 -> additive (B, 1, 1, L) mask."""
        if attention_mask is None:
            return None
        m = (1.0 - attention_mask.astype("float32")) * -1e30
        return ops.unsqueeze(ops.unsqueeze(m, 1), 1)

    def forward(self, ids, token_type_ids=None, attention_mask=None):
        x = self.embeddings(ids, token_type_ids)
        x = self.encoder(x, src_mask=self.attn_mask(attention_mask))
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForPretraining(Layer):
    """MLM (tied decoder) + NSP heads over the trunk."""

    def __init__(self, cfg):
        super().__init__()
        self.bert = BertModel(cfg)
        self.transform = Linear(cfg.hidden, cfg.hidden)
        self.transform_norm = LayerNorm(cfg.hidden)
        self.mlm_bias = self.create_parameter((cfg.vocab_size,), is_bias=True)
        self.nsp = Linear(cfg.hidden, 2)

    def _mlm_head_parts(self):
        """All that ``mlm_head`` reads of the model, and the parameters a
        region round it has to be given (``_mlm_loss``)."""
        return (self.transform, self.transform_norm,
                self.bert.embeddings.word.weight, self.mlm_bias)

    def mlm_head(self, seq, masked_positions=None):
        """Vocabulary logits of ``seq`` ``(B, L, H)``: ``(B, L, V)``, or
        ``(K, V)`` for the rows of ``seq.reshape(-1, H)`` that
        ``masked_positions`` ``(K,)`` names."""
        transform, norm, word, bias = self._mlm_head_parts()
        if masked_positions is not None:
            seq = ops.gather(ops.reshape(seq, [-1, seq.shape[-1]]),
                             masked_positions)
        h = norm(F.gelu(transform(seq)))
        mlm_logits = ops.matmul(h, ops.transpose(word, [1, 0]))
        return mlm_logits + bias

    def forward(self, ids, token_type_ids=None, attention_mask=None,
                masked_positions=None):
        """``(mlm_logits, nsp_logits)``. ``masked_positions``: flat indices
        into the ``B x L`` positions, shape ``(K,)``; the MLM head then runs
        over those rows alone and ``mlm_logits`` is ``(K, V)`` (BERT's
        ``gather_indexes``). Without it, every position's: ``(B, L, V)``."""
        seq, pooled = self.bert(ids, token_type_ids, attention_mask)
        return self.mlm_head(seq, masked_positions), self.nsp(pooled)


# The share of a batch's positions one pass of the MLM head has room for.
# BERT's recipe labels 15% of a row (and at most ``max_predictions_per_seq``),
# and so does RoBERTa's Bernoulli 15%: at 12,288 positions a quarter is more
# than twenty standard deviations over that, so such a batch takes one pass,
# and the head's cost is a quarter of every position's.
COMPACT_SHARE = 4


def compact_rows(positions):
    """Rows one pass of the MLM head runs over: a quarter of the batch's
    positions, rounded up to whole sublanes of eight rows. A function of the
    row count alone, so one compiled step serves every batch of a shape."""
    return -(-positions // (8 * COMPACT_SHARE)) * 8


def _mlm_loss(model, seq, labels, ignore_index):
    """Mean masked-LM cross-entropy over the labelled positions of
    ``labels`` ``(B, L)``, the head run over those positions alone: they are
    gathered ``K = compact_rows(B x L)`` at a time, and a loop on the device
    makes ``ceil(count / K)`` passes (one for BERT's 15%, ``COMPACT_SHARE``
    when every position is labelled, none when none is), so every count of
    labels is exact, no host sync, one compile.

    One taped op over explicit ``(*params, seq, labels)``, a region as
    ``recompute``'s is: the ops inside run untaped and keep their names, a
    ``custom_vjp`` inside (the layer-norm kernel's) its rule. A loop of
    unknown length has no reverse pass, and one with logits-sized residuals
    is not wanted: each pass makes the gradients of its rows' loss *sum*
    beside the sum (``jax.value_and_grad``), the forward accumulates both,
    and the backward multiplies by cotangent / count. Nothing logits-sized
    outlives a pass. Float16 under a loss scale stays in range: the logits'
    gradient inside is ``softmax - onehot``, O(1) like a scaled one, and the
    scale and the 1 / count arrive together in float32. No second derivative.
    """
    params = [p for part in model._mlm_head_parts()
              for p in (part.parameters() if isinstance(part, Layer)
                        else [part])]
    positions = seq.shape[0] * seq.shape[1]
    rows = compact_rows(positions)
    passes = -(-positions // rows)

    def rows_loss(seq, at, kept):
        with dispatch.program_scope("mlm_compact"):
            return F.cross_entropy(model.mlm_head(seq, at), kept,
                                   ignore_index=ignore_index,
                                   reduction="sum")

    rows_loss = _region(rows_loss, params)

    def sums(arrays, with_grads):
        *head, labels = arrays
        flat = labels.reshape(-1)
        valid = flat != ignore_index
        count = jnp.sum(valid)
        # the labelled positions in order; a filler points at position 0 and
        # carries ``ignore_index``: nothing in the sum or in a gradient
        at, = jnp.nonzero(valid, size=passes * rows, fill_value=0)

        def one_pass(i, carry):
            picked = jax.lax.dynamic_slice(at, (i * rows,), (rows,))
            kept = jnp.where(i * rows + jnp.arange(rows) < count,
                             flat[picked], ignore_index)
            if not with_grads:
                return carry + rows_loss(*head, picked, kept)
            add = jax.value_and_grad(rows_loss, argnums=tuple(range(len(
                head))))(*head, picked, kept)
            return jax.tree.map(jnp.add, carry, add)

        zero = jnp.zeros((), jnp.float32)
        if with_grads:
            zero = (zero, tuple(jnp.zeros_like(a) for a in head))
        return jax.lax.fori_loop(0, (count + rows - 1) // rows, one_pass,
                                 zero), jnp.maximum(count, 1)

    @jax.custom_vjp
    def pure(*arrays):
        total, count = sums(arrays, with_grads=False)
        return total / count

    def forward(*arrays):
        (total, grads), count = sums(arrays, with_grads=True)
        return total / count, (grads, count)

    def backward(kept, g):
        grads, count = kept
        share = g.astype(jnp.float32) / count
        return (*((share * d.astype(jnp.float32)).astype(d.dtype)
                  for d in grads), None)

    pure.defvjp(forward, backward)
    return dispatch.apply("mlm_loss", pure, *params, seq, labels)


def bert_pretrain_loss(model, ids, token_type_ids, attention_mask,
                       mlm_labels, nsp_labels, ignore_index=-100):
    """Masked-LM CE (ignore_index for unmasked positions) + NSP CE.

    The MLM head (transform, layer norm, tied decoder, bias) and its softmax
    run over the labelled positions only, gathered ``K = compact_rows(B x
    L)`` rows at a time (a quarter of the positions): an unlabelled
    position's loss and gradient are exactly zero, so loss and gradients are
    those of the full-width head up to summation order. BERT's 15% fit one
    pass of ``K`` rows; a batch with more labels (every position labelled,
    as ELECTRA's) takes ``ceil(count / K)`` passes in a loop on the device,
    at most the full-width head's work (``_mlm_loss``). Eager calls run the
    same loop, with no host sync on the count, and compile it at each call;
    ``TrainStep`` compiles it once. The profile names the head
    ``mlm_compact``.
    """
    seq, pooled = model.bert(ids, token_type_ids, attention_mask)
    mlm = _mlm_loss(model, seq, mlm_labels, ignore_index)
    nsp = F.cross_entropy(model.nsp(pooled), nsp_labels)
    return mlm + nsp
