"""The one traffic generator. A traffic mix is a file of parameters under
``traffic/`` (``<mix>.json``); this turns it and a seed into a pool of
training rows. The mix names its ``objective``, and what one row of that
objective looks like is ``traffic/<objective>.py``, found by that name:
``rows(params, vocab_size, rng, n)`` returns the n rows as a tuple of arrays.
A new mix of a known objective is a data file; a new objective is a new file.

Every seed gets the same sizes in another order: the same number of rows,
the same multiset of sequence lengths and so the same number of masked
positions, so that the seed changes the content of the work and never its
amount. Token ids follow a Zipf law over the vocabulary's ordinary ids, as
word frequencies do.
"""
import numpy as np

from benchmark import harness

IGNORE = -100


def zipf_tokens(rng, n, lo, hi, exponent):
    """n ids in [lo, hi), id lo + r with probability ~ 1 / (r + 1)**exponent."""
    cdf = np.cumsum(1.0 / np.arange(1, hi - lo + 1) ** exponent)
    draws = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
    return (lo + np.minimum(draws, hi - lo - 1)).astype(np.int32)


def pool(params, vocab_size, seed):
    """``pool_batches * batch`` rows as a tuple of arrays, from the seed."""
    n = params["pool_batches"] * params["batch"]
    rng = np.random.default_rng(int(seed))
    objective = harness.load_module("traffic", params["objective"])
    return objective.rows(params, vocab_size, rng, n)
