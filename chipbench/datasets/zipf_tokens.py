"""Dataset kind ``zipf_tokens``: ``size`` sequences of ``seq_len`` token ids
drawn independently from a Zipf distribution of exponent ``exponent`` over
``vocab_size`` ids (id ``r`` with probability proportional to
``(r + 1) ** -exponent``, so id 0 is the commonest), the rank-frequency law
of text. Every position is a real token: no padding, no packing, the loss
mask all True. Rows of this length never repeat.

The same seed gives the same arrays on every platform: drawn on the host with
numpy's PCG64, in bulk, by inverting the cumulative distribution.
"""

import numpy as np


def make(spec, seed):
    """(tokens int32 (size, seq_len), loss_mask bool (size, seq_len))."""
    size, seq_len = int(spec["size"]), int(spec["seq_len"])
    vocab = int(spec["vocab_size"])
    rng = np.random.default_rng([seed, 0x21BF])
    weights = np.arange(1, vocab + 1, dtype=np.float64) ** -float(
        spec.get("exponent", 1.0))
    cdf = np.cumsum(weights / weights.sum())
    tokens = np.searchsorted(cdf, rng.random((size, seq_len)), side="right")
    tokens = np.minimum(tokens, vocab - 1).astype(np.int32)
    return tokens, np.ones((size, seq_len), bool)
