"""The one generator of requests and rows. It reads a mix's parameters
from ``traffic/<mix>.json`` and makes everything else from the seed.

Every seed gets the same set of prompt lengths (the quantiles of the mix's
distribution), in another order, so that two seeds differ in what is sent
when and not in how much work there is.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def prompt_lengths(spec: dict) -> list[int]:
    """The mix's fixed multiset of prompt lengths: ``distinct_lengths``
    evenly spaced quantiles of the distribution, clipped to [min, max]."""
    count = int(spec["distinct_lengths"])
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["distribution"] == "lognormal":
        mu, sigma = math.log(float(spec["median"])), float(spec["sigma"])
        unit = NormalDist()
        values = [math.exp(mu + sigma * unit.inv_cdf((i + 0.5) / count))
                  for i in range(count)]
    elif spec["distribution"] == "uniform":
        values = [lo + (hi - lo) * (i + 0.5) / count for i in range(count)]
    elif spec["distribution"] == "fixed":
        values = [float(spec["median"])] * count
    else:
        raise ValueError(f"unknown distribution {spec['distribution']!r}")
    return [min(hi, max(lo, int(round(v)))) for v in values]


class RequestStream:
    """Request ``i`` of a seed: its prompt's length is the ``i``-th of the
    seed's permutation of the fixed lengths (cycled), its ids come from
    (seed, i). Clients take the next index from :meth:`take`, so the order
    of requests does not depend on who answers first."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.shared = int(traffic.get("shared_prefix_tokens", 0))
        lengths = prompt_lengths(traffic["prompt_tokens"])
        order = np.random.default_rng([self.seed, 0]).permutation(
            len(lengths))
        self.lengths = [lengths[i] for i in order]
        self._prefix = np.random.default_rng([self.seed, 1]).integers(
            1, self.vocab_size, self.shared).tolist()

    def length(self, index: int) -> int:
        return self.lengths[index % len(self.lengths)]

    def prompt(self, index: int) -> list[int]:
        n = self.length(index)
        rng = np.random.default_rng([self.seed, 2, index])
        body = rng.integers(1, self.vocab_size, max(0, n - self.shared))
        return (self._prefix + body.tolist())[:n]


def train_batches(seed: int, steps: int, batch_size: int, seq_len: int,
                  vocab_size: int) -> list:
    """The first ``steps`` batches of the program's synthetic stream, made
    anew here: ``default_rng(seed)`` drawing one (batch, seq_len + 1) block
    of ids a step. A list of (tokens, targets)."""
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(steps):
        block = rng.integers(0, vocab_size, (batch_size, seq_len + 1),
                             dtype=np.int32)
        batches.append((block[:, :-1], block[:, 1:]))
    return batches
