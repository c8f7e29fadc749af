"""Synthetic token sequences: the training set of a decoder when there is no
corpus (``--synthetic-data`` with a token model), as ``synthetic_cifar10`` is
of a classifier. ``(tokens, loss_mask)``: ``tokens`` (size, SEQ_LEN) int32
drawn from a Zipf distribution over the vocabulary (id ``r`` with
probability proportional to ``1 / (r + 1)``, the rank-frequency law of
text), ``loss_mask`` of the same shape, True where a token is a real
target (everywhere: no padding, no packing). A corpus, or sequences of
another length, come as ``train_data``."""

from __future__ import annotations

import numpy as np


SEQ_LEN = 512  # tokens a synthetic sequence holds


def synthetic_tokens(size: int, vocab_size: int, seed: int = 0,
                     seq_len: int = SEQ_LEN):
    rng = np.random.default_rng([seed, 0x70CE])
    weights = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64)
    cdf = np.cumsum(weights / weights.sum())
    tokens = np.searchsorted(cdf, rng.random((size, seq_len)), side="right")
    tokens = np.minimum(tokens, vocab_size - 1).astype(np.int32)
    return tokens, np.ones((size, seq_len), bool)
