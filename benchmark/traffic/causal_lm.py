"""``causal_lm``: documents of log-normal length, each closed by
``eos_token``, packed end to end and cut into full rows; labels are the ids
shifted by one. Rows: (ids, labels)."""
import numpy as np

from benchmark import generate


def rows(p, vocab_size, rng, n):
    width = p["seq_len"] + 1
    need = n * width
    doc = p["documents"]
    lengths = []
    while sum(lengths) < need:
        lengths += list(np.maximum(2, rng.lognormal(
            np.log(doc["median_len"]), doc["sigma"], 256).astype(np.int64)))
    stream = generate.zipf_tokens(rng, int(sum(lengths)), p["first_token"],
                                  vocab_size, p["zipf_exponent"])
    stream[np.cumsum(lengths) - 1] = p["eos_token"]
    packed = stream[:need].reshape(n, width)
    return (np.ascontiguousarray(packed[:, :-1]),
            np.ascontiguousarray(packed[:, 1:]))
