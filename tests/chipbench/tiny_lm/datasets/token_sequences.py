"""Token sequences from the seed: ``size`` rows of ``seq_len`` tokens drawn
uniformly from ``vocab_size``; each row is real up to a length drawn from
``[min_len, seq_len]`` and padding (token 0, mask False) after it."""

import numpy as np


def make(spec, seed):
    """(tokens int32 (size, seq_len), mask bool (size, seq_len))."""
    size, seq_len = int(spec["size"]), int(spec["seq_len"])
    rng = np.random.default_rng([seed, 0x70CE])
    tokens = rng.integers(0, int(spec["vocab_size"]), (size, seq_len),
                          dtype=np.int32)
    lengths = rng.integers(int(spec.get("min_len", seq_len)), seq_len + 1,
                           size)
    mask = np.arange(seq_len)[None, :] < lengths[:, None]
    return np.where(mask, tokens, 0).astype(np.int32), mask
