"""Closed loop of batches: the one traffic generator, read from a mix file.

A batch is one ``ServingEngine.generate`` call holding ``batch`` requests,
one per slot, all submitted together; batches follow each other back to
back.  Every request asks for ``new_tokens`` tokens.

Prompt lengths are stratified: batch ``i`` takes the ``batch`` quantiles
``(j + 0.5) / batch`` of the mix's length law, in an order drawn from the
seed.  Every seed therefore serves the same set of sizes, in another
order, and only the token ids (uniform over the vocabulary, id 0 left out)
differ.  With ``"order": "fixed"`` the order (and, with shared prefixes,
which system prompt each slot gets) is one drawn once for every seed:
where a mix's tails depend on which slot holds which length, as when
prefill chunks interleave with a single decode block, a seeded order
changes the work from seed to seed.

With ``shared_prefix``, each request is one of ``count`` system prompts of
``tokens`` tokens, chosen with Zipf(``zipf``) shares that are rounded to
whole requests per batch by largest remainder, followed by a unique
suffix whose length follows the law.

Mix file keys: ``slots``, ``max_len``, ``batch``, ``new_tokens``,
``prompt`` (``{"law": "lognormal", "median", "sigma", "min", "max"}`` or
``{"law": "uniform", "min", "max"}``), optional ``order`` and ``shared_prefix``
(``{"count", "tokens", "zipf"}``), ``check_requests`` (how many finished
requests the correctness check samples) and ``warmup_new_tokens``.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def stratified_lengths(law: Dict, n: int) -> List[int]:
    """The ``n`` mid-quantiles of the length law, ascending."""
    qs = [(j + 0.5) / n for j in range(n)]
    lo, hi = int(law["min"]), int(law["max"])
    if law["law"] == "lognormal":
        nd = NormalDist(math.log(law["median"]), law["sigma"])
        raw = [math.exp(nd.inv_cdf(q)) for q in qs]
    elif law["law"] == "uniform":
        raw = [lo + (hi - lo) * q for q in qs]
    else:
        raise ValueError(f"unknown length law {law['law']!r}")
    return [min(hi, max(lo, int(round(x)))) for x in raw]


def zipf_counts(count: int, exponent: float, n: int) -> List[int]:
    """Requests per system prompt in a batch of ``n``: Zipf shares rounded
    by largest remainder, so they sum to ``n``."""
    w = [1.0 / (k + 1) ** exponent for k in range(count)]
    share = [n * x / sum(w) for x in w]
    out = [int(s) for s in share]
    order = sorted(range(count), key=lambda k: out[k] - share[k])
    for k in order[:n - sum(out)]:
        out[k] += 1
    return out


class Batches:
    def __init__(self, mix: Dict, vocab_size: int, seed: int):
        self.mix = mix
        self.vocab = int(vocab_size)
        self.new_tokens = int(mix["new_tokens"])
        self.size = int(mix["batch"])
        self.rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
        # The slot order: from the seed, or one for every seed.
        self.order = (np.random.default_rng(0) if mix.get("order") == "fixed"
                      else self.rng)
        self.lengths = stratified_lengths(mix["prompt"], self.size)
        sp = mix.get("shared_prefix")
        self.prefixes = []
        if sp:
            self.prefixes = [self._ids(int(sp["tokens"]))
                             for _ in range(int(sp["count"]))]
            self.counts = zipf_counts(len(self.prefixes), float(sp["zipf"]),
                                      self.size)

    def _ids(self, n: int) -> np.ndarray:
        return self.rng.integers(1, self.vocab, n, dtype=np.int32)

    def warmup(self) -> List[np.ndarray]:
        """Prompts of the set-up batch: the system prompts themselves where
        the mix shares prefixes (so the prefix cache holds them), else one
        prompt of the longest length."""
        if self.prefixes:
            return list(self.prefixes)
        return [self._ids(max(self.lengths))]

    def batch(self) -> List[np.ndarray]:
        """The next batch's prompts."""
        lengths = self.order.permutation(self.lengths)
        if not self.prefixes:
            return [self._ids(int(n)) for n in lengths]
        which = self.order.permutation(
            np.repeat(np.arange(len(self.prefixes)), self.counts))
        return [np.concatenate([self.prefixes[k], self._ids(int(n))])
                for k, n in zip(which, lengths)]


def make(mix: Dict, vocab_size: int, seed: int) -> Batches:
    return Batches(mix, vocab_size, seed)
