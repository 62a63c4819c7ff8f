"""Closed-loop serving traffic: a fixed set of (prompt length, output
length, temperature) requests, the same for every seed, sent in an order
and with prompt tokens drawn from ``--seed``.

Lengths sit at the midpoint quantiles of a log-uniform law between the
traffic file's bounds, so each seed offers the same work in another
order; output lengths are paired with prompt lengths by a fixed
shuffle. Every ``greedy_every``-th request of the set is greedy (the
output check reads those), the rest sample at ``temperature``. The
clients are closed loops: each sends its next request when the last
one has finished.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import harness

RUNNER = "serve_cell"


@dataclasses.dataclass(frozen=True)
class Spec:
    prompt_len: int
    max_new: int
    temperature: float


def _quantile_lengths(lo: int, hi: int, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
                   ).astype(np.int64)


def request_set(traffic: dict) -> list[Spec]:
    """The seed-independent set of request sizes."""
    n = int(traffic["set_size"])
    p = _quantile_lengths(*traffic["prompt_len"], n)
    o = _quantile_lengths(*traffic["output_len"], n)
    o = o[np.random.default_rng(0).permutation(n)]
    every = int(traffic.get("greedy_every", 1))
    temp = float(traffic.get("temperature", 0.0))
    return [Spec(int(p[i]), int(o[i]),
                 0.0 if i % every == 0 else temp) for i in range(n)]


class Stream:
    """The request stream of one run: the set in a seeded order, again
    in a fresh order each time it is used up; prompt tokens uniform over
    ``[2, vocab)``."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.specs = request_set(traffic)
        self.vocab = vocab
        self.rng = harness.seed_rng(seed, 0x5E7E)
        self._order: list[int] = []
        self.sent = 0

    def next(self) -> tuple[np.ndarray, Spec]:
        if not self._order:
            self._order = list(self.rng.permutation(len(self.specs)))
        spec = self.specs[self._order.pop()]
        prompt = self.rng.integers(2, self.vocab, size=spec.prompt_len
                                   ).astype(np.int32)
        self.sent += 1
        return prompt, spec
