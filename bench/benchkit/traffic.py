"""The one traffic generator: a mix is a JSON file of parameters.

Keys of a mix file:

* ``clients``: closed-loop clients; each has one request outstanding.
* ``prompt_tokens``: ``{"fixed": n}`` or ``{"stratified": {"n": k,
  "clip": [lo, hi], "mixture": [[median, sigma], ...]}}`` -- the k
  lengths at the quantiles (j + 0.5) / k of an equal-weight mixture of
  lognormals, clipped.  The length SET is the same for every seed; the
  seed draws each client's order through it and every token id.
* ``max_new_tokens``: per request, a number or a length set in either
  of ``prompt_tokens``' forms; each client cycles through the set in its
  own seeded order, drawn apart from its prompts' order.
* ``next_on``: ``"first_token"`` or ``"finish"`` -- when a client sends
  its next request.
* ``setup``: what set-up runs before the window (see ``loop.Setup``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import numpy as np


def mixture_quantile(p: float, mixture: List[List[float]]) -> float:
    """Inverse CDF of an equal-weight mixture of lognormals
    ``[[median, sigma], ...]``, by bisection on the log scale."""
    def cdf(x: float) -> float:
        return sum(0.5 * (1.0 + math.erf((math.log(x) - math.log(m))
                                         / (s * math.sqrt(2.0))))
                   for m, s in mixture) / len(mixture)
    lo, hi = 1e-3, 1e9
    for _ in range(200):
        mid = math.sqrt(lo * hi)
        if cdf(mid) < p:
            lo = mid
        else:
            hi = mid
    return math.sqrt(lo * hi)


def prompt_lengths(spec: Any) -> List[int]:
    """The length set of a ``prompt_tokens`` or ``max_new_tokens`` entry."""
    if isinstance(spec, int):
        return [spec]
    if "fixed" in spec:
        return [int(spec["fixed"])]
    st = spec["stratified"]
    lo, hi = st["clip"]
    k = int(st["n"])
    return [int(round(min(max(mixture_quantile((j + 0.5) / k,
                                               st["mixture"]), lo), hi)))
            for j in range(k)]


class Traffic:
    """Seeded requests of one mix.  ``request(c, i)`` is the i-th request
    of client c: (token ids, max_new_tokens), a pure function of (seed,
    c, i), whatever order the loop asks in."""

    def __init__(self, mix: Dict[str, Any], vocab: int, seed: int):
        self.mix = mix
        self.vocab = int(vocab)
        self.seed = int(seed)
        self.clients = int(mix["clients"])
        self.lengths = prompt_lengths(mix["prompt_tokens"])
        self.new_tokens = prompt_lengths(mix["max_new_tokens"])
        # set-up requests take the largest, so planes reach their capacity
        self.max_new_tokens = max(self.new_tokens)
        self.next_on = mix["next_on"]
        if self.next_on not in ("first_token", "finish"):
            raise ValueError(f"next_on {self.next_on!r}")
        rng = np.random.default_rng([self.seed, 0])
        self.orders = [rng.permutation(len(self.lengths))
                       for _ in range(self.clients)]
        rng = np.random.default_rng([self.seed, 2])
        self.new_orders = [rng.permutation(len(self.new_tokens))
                           for _ in range(self.clients)]

    def length(self, c: int, i: int) -> int:
        order = self.orders[c]
        return self.lengths[int(order[i % len(order)])]

    def new_length(self, c: int, i: int) -> int:
        order = self.new_orders[c]
        return self.new_tokens[int(order[i % len(order)])]

    def tokens(self, n: int, *key: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 1, *key])
        return rng.integers(0, self.vocab, n, dtype=np.int32)

    def request(self, c: int, i: int) -> Tuple[np.ndarray, int]:
        return self.tokens(self.length(c, i), c, i), self.new_length(c, i)
