"""Seeded traffic from a mix's parameters.

Every seed gets the SAME set of sizes in another order: the lengths are
a stratified sample (the distribution's own quantiles, no draw), and the
seed only permutes them and draws the token ids.  So two seeds offer the
same work, and a difference between them is the system's.
"""
import math
from statistics import NormalDist

import numpy as np

SET_SIZE = 128         # length pairs in a mix's set
PAIRING_SEED = 0       # the fixed shuffle that pairs prompts with outputs


def lognormal_quantiles(median, sigma, lo, hi, n):
    """``n`` lengths at the mid-quantiles of a lognormal clipped to
    [lo, hi]."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def request_set(mix):
    """The mix's fixed set of (prompt_len, output_len) pairs: both
    marginals stratified, paired by a fixed shuffle."""
    n = SET_SIZE
    p = lognormal_quantiles(n=n, **mix["prompt_len"])
    o = lognormal_quantiles(n=n, **mix["output_len"])
    order = np.random.default_rng(PAIRING_SEED).permutation(n)
    return [(p[i], o[int(j)]) for i, j in enumerate(order)]


class RequestStream:
    """Request ``i`` of the run: the seed's permutation of the set,
    cycled (each cycle permuted anew), ids uniform over the vocabulary."""

    def __init__(self, mix, seed, vocab):
        self.pairs = request_set(mix)
        self.seed = int(seed)
        self.vocab = int(vocab)
        self._orders = {}

    def lengths(self, i):
        n = len(self.pairs)
        cycle, k = divmod(i, n)
        if cycle not in self._orders:
            self._orders[cycle] = np.random.default_rng(
                [self.seed, 1, cycle]).permutation(n)
        return self.pairs[int(self._orders[cycle][k])]

    def request(self, i):
        plen, olen = self.lengths(i)
        ids = np.random.default_rng([self.seed, 2, i]).integers(
            0, self.vocab, plen)
        return ids.astype(np.int32), olen


def lm_batch(seed, batch, seq, vocab):
    """A training batch: ids uniform, every row different; the label of
    a position is the next token (the last one is drawn)."""
    ids = np.random.default_rng([int(seed), 3]).integers(
        0, vocab, (batch, seq + 1))
    return ids[:, :-1].astype(np.int64), ids[:, 1:, None].astype(np.int64)
