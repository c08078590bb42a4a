"""The one traffic generator. A mix is a data file of parameters
(``chipbench/traffic/<mix>.json``); nothing here knows a mix by name.

The lengths do not depend on the seed. The k-th request sent, by any client,
takes the k-th prompt and output length of one fixed sequence: the
quantiles of the mix's clipped lognormals, visited in bit-reversed order so
that every stretch of the sequence spreads over the whole distribution. The
seed draws the token ids and which client starts with which in-flight
request. Clients are alike, so every seed asks for the same work.

Closed loop: a client sends its next request when its previous one has
finished. With ``staggered`` set, each client's first request stands for one
already in flight when the window opens: its output budget is a quantile of
the stationary residual of the output lengths, so completions do not come
in one wave.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

POOL = 512  # a power of two: the bit-reversed order visits every quantile


def pool(spec: dict, n: int = POOL) -> np.ndarray:
    """``n`` lengths: quantiles of a lognormal with the given median and
    sigma, clipped to [min, max], in ascending order."""
    nd = NormalDist()
    v = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def bit_reversed(n: int) -> np.ndarray:
    bits = n.bit_length() - 1
    return np.array([int(f"{i:0{bits}b}"[::-1], 2) for i in range(n)])


def residuals(outputs: np.ndarray, n: int) -> np.ndarray:
    """``n`` quantiles of the remaining output of a request met in flight:
    lengths weighted by their own size, remainder uniform over 1..L."""
    rem = np.sort(np.concatenate([np.arange(1, L + 1) for L in outputs]))
    return rem[((np.arange(n) + 0.5) / n * len(rem)).astype(np.int64)]


def _rng(seed: int, *more: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), *more])


class Stream:
    """The requests of a mix, in the order the clients send them."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        order = bit_reversed(POOL)
        self.prompts = pool(mix["prompt"])[order]
        # a second, shifted visit order, so prompt and output lengths of
        # one request are not tied to the same quantile
        self.outputs = pool(mix["output"])[order[(np.arange(POOL) + POOL // 3)
                                                 % POOL]]
        self.seed, self.vocab, self.n = seed, vocab, 0
        c = mix["clients"]
        self.firsts = None
        if mix.get("staggered"):
            res = residuals(pool(mix["output"]), c)
            self.firsts = list(res[_rng(seed, 3).permutation(c)])

    def first(self, client: int):
        """A client's first request: in flight when the window opens, where
        the mix is staggered."""
        toks, out = self.next()
        if self.firsts is not None:
            out = int(self.firsts[client])
        return toks, out

    def next(self):
        k = self.n
        self.n += 1
        plen = int(self.prompts[k % POOL])
        toks = _rng(self.seed, 2, k).integers(
            0, self.vocab, plen, dtype=np.int64).astype(np.int32)
        return toks, int(self.outputs[k % POOL])
