"""Token sequences for the training cells, made from ``--seed``.

The program's own ``data/datasets.py::make_synthetic_lm`` builds a
``V x V`` float64 transition table and its CDF, 20 GB each at a real
vocabulary, so the benchmark makes its tokens itself, in O(V) memory,
and hands them to ``Trainer(cfg, datasets=...)``.

The text is learnable in two ways, so that a falling loss is a check
that training works and not an accident: unigram frequencies follow
Zipf's law (exponent ``zipf_exponent``, as word frequencies do), and
with probability ``bigram_share`` a token is the fixed "favourite
successor" of the token before it (a seeded hash of that token pushed
through the same Zipf quantile function), which only attention to the
previous position can predict. Sequences are full length: no padding,
no packing boundaries."""

from __future__ import annotations

import numpy as np


def zipf_cdf(vocab_size: int, exponent: float) -> np.ndarray:
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    weights = ranks ** -exponent
    return np.cumsum(weights) / weights.sum()


def unigram_entropy(vocab_size: int, exponent: float) -> float:
    """Entropy in nats of the unigram distribution: where the loss of a
    model that has learnt the frequencies and nothing else settles."""
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = ranks ** -exponent
    p /= p.sum()
    return float(-(p * np.log(p)).sum())


def make_lm_tokens(seed: int, num_sequences: int, seq_len: int,
                   vocab_size: int, zipf_exponent: float = 1.1,
                   bigram_share: float = 0.5) -> np.ndarray:
    """``[num_sequences, seq_len]`` int32, a pure function of its
    arguments."""
    rng = np.random.default_rng([int(seed), 0x70C5])
    cdf = zipf_cdf(vocab_size, zipf_exponent)
    # frequency rank -> token id, so that frequent tokens are not the
    # low ids (a seeded permutation of the vocabulary)
    ids = rng.permutation(vocab_size).astype(np.int32)
    # each token's favourite successor, as a quantile of the same law
    successor_u = rng.random(vocab_size)
    successor = ids[np.minimum(np.searchsorted(cdf, successor_u),
                               vocab_size - 1)]

    def draw(n: int) -> np.ndarray:
        return ids[np.minimum(np.searchsorted(cdf, rng.random(n)),
                              vocab_size - 1)]

    out = np.empty((num_sequences, seq_len), np.int32)
    out[:, 0] = draw(num_sequences)
    follow = rng.random((num_sequences, seq_len)) < bigram_share
    fresh = draw(num_sequences * seq_len).reshape(num_sequences, seq_len)
    for t in range(1, seq_len):
        out[:, t] = np.where(follow[:, t], successor[out[:, t - 1]],
                             fresh[:, t])
    return out
