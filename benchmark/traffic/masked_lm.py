"""``masked_lm``: BERT's ``create_pretraining_data.py``. Target length
``seq_len``, with probability ``short_seq_prob`` a shorter one, uniform;
[CLS] A [SEP] B [SEP] with segment ids; of the tokens ``masked_lm_prob`` (at
most ``max_predictions_per_seq``) are predicted, 80% shown as [MASK], 10% as
a random token, 10% unchanged; the next-sentence label is 1 with probability
``random_next_prob``. Rows: (ids, token_type, attention_mask, mlm_labels,
nsp_label)."""
import numpy as np

from benchmark import generate

MIN_PAIR = 5  # [CLS] a [SEP] b [SEP]


def lengths_of(p, n):
    """The multiset of lengths every seed shares: the stated share of rows is
    short, at evenly spaced quantiles of uniform[2, seq_len] (raised to the
    five tokens a pair needs); the rest are full."""
    n_short = int(round(n * p["short_seq_prob"]))
    q = (np.arange(n_short) + 0.5) / max(n_short, 1)
    short = np.maximum(MIN_PAIR, np.floor(2 + q * (p["seq_len"] - 1)))
    return np.concatenate([short, np.full(n - n_short, p["seq_len"])]) \
        .astype(np.int64)


def rows(p, vocab_size, rng, n):
    length, sp = p["seq_len"], p["special"]
    lengths = rng.permutation(lengths_of(p, n))
    ids = np.full((n, length), sp["pad"], np.int32)
    token_type = np.zeros((n, length), np.int32)
    mask = np.zeros((n, length), np.int32)
    labels = np.full((n, length), generate.IGNORE, np.int32)
    tokens = generate.zipf_tokens(rng, int(lengths.sum()), p["first_token"],
                                  vocab_size, p["zipf_exponent"])
    at = 0
    for r, m in enumerate(lengths):
        row = tokens[at:at + m].copy()
        at += m
        first_sep = 1 + int(rng.integers(1, m - 3))  # A has 1..m-4 tokens
        row[0], row[first_sep], row[m - 1] = sp["cls"], sp["sep"], sp["sep"]
        ordinary = np.setdiff1d(np.arange(1, m - 1), [first_sep])
        k = min(p["max_predictions_per_seq"],
                max(1, int(round(m * p["masked_lm_prob"]))), len(ordinary))
        chosen = rng.choice(ordinary, k, replace=False)
        labels[r, chosen] = row[chosen]
        how = rng.random(k)
        shown = np.where(how < 0.8, sp["mask"], row[chosen])
        rand = generate.zipf_tokens(rng, k, p["first_token"], vocab_size,
                                    p["zipf_exponent"])
        row[chosen] = np.where((how >= 0.8) & (how < 0.9), rand, shown)
        ids[r, :m] = row
        token_type[r, first_sep + 1:m] = 1
        mask[r, :m] = 1
    nsp = (rng.random(n) < p["random_next_prob"]).astype(np.int32)
    return ids, token_type, mask, labels, nsp
