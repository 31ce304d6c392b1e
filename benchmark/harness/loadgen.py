"""One general traffic generator, driven by the mix's data file.

Kinds:

- ``train``: ``rows`` x ``seq`` token ids a step, uniform over the
  vocabulary, a ring of ``ring`` distinct batches made ahead on the host.
- ``serve`` with ``"loop": "closed"``: ``clients`` callers, each sending
  its next request when the last one ended. The lengths are a deck of
  ``pool`` (prompt, output) pairs, the quantiles of the mix's two
  distributions, dealt deck after deck; ``--seed`` orders each deck and
  draws the token ids, so no seed changes the work.

A length distribution is ``{"dist": "lognormal", "median", "sigma",
"min", "max"}`` or ``{"dist": "uniform", "min", "max"}``.
"""

from __future__ import annotations

import math
import statistics
import threading
from typing import List, Tuple

import numpy as np


def length_quantiles(spec: dict, n: int) -> List[int]:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of the
    distribution, clipped: the whole distribution in ``n`` values."""
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "lognormal":
        z = statistics.NormalDist()
        xs = [math.exp(math.log(spec["median"])
                       + spec["sigma"] * z.inv_cdf(q)) for q in qs]
    elif spec["dist"] == "uniform":
        xs = [spec["min"] + q * (spec["max"] - spec["min"]) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(max(round(x), spec["min"]), spec["max"])) for x in xs]


def length_pool(traffic: dict) -> List[Tuple[int, int]]:
    """The mix's deck of ``pool`` (prompt tokens, output tokens) pairs:
    the quantiles of both distributions, paired by a permutation drawn
    once from the mix's own ``length_seed``."""
    n = int(traffic["pool"])
    pair = np.random.default_rng(int(traffic["length_seed"])).permutation(n)
    outs = length_quantiles(traffic["output_tokens"], n)
    return [(p, outs[j]) for p, j in
            zip(length_quantiles(traffic["prompt_tokens"], n), pair)]


def prompt_buckets(traffic: dict, bucket: int, capacity: int) -> List[int]:
    """Padded prompt lengths this mix can produce: what set-up warms."""
    lo, hi = traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]
    pad = lambda n: min(max(bucket, -(-n // bucket) * bucket), capacity)
    return sorted({pad(n) for n in range(lo, hi + 1)})


class ClosedLoopPlan:
    """The requests of one seed, dealt to whichever caller asks next:
    deck after deck of the mix's pool, each deck in an order of its own
    drawn from the seed. Any run therefore sends every length of the
    pool once before any twice, whatever its seed."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.vocab = int(vocab)
        self.clients = int(traffic["clients"])
        self.seed = int(seed)
        self.pool = length_pool(traffic)
        self._next = 0
        self._lock = threading.Lock()

    def lengths(self, j: int) -> Tuple[int, int]:
        deck, at = divmod(j, len(self.pool))
        order = np.random.default_rng([self.seed, 0, deck]).permutation(
            len(self.pool))
        return self.pool[order[at]]

    def next_request(self) -> Tuple[np.ndarray, int]:
        """(prompt ids, output tokens) of the next request of the run."""
        with self._lock:
            j = self._next
            self._next += 1
        plen, out = self.lengths(j)
        rng = np.random.default_rng([self.seed, 1, j])
        return rng.integers(0, self.vocab, plen).astype(np.int32), out


def train_ring(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """(ring, rows, seq) int32 token ids; every row differs."""
    rng = np.random.default_rng([int(seed), 2])
    return rng.integers(
        0, vocab, (int(traffic["ring"]), int(traffic["rows"]),
                   int(traffic["seq"]))).astype(np.int32)
