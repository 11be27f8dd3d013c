"""The one traffic generator: requests and arrivals from a mix's parameters.

A mix file (`bench/traffic/<name>.json`) gives length distributions, a
prompt pool and an arrival process.  Every seed gets the same *sequence*
of (arrival gap, prompt length, answer length): each quantity is drawn as
the stratified quantiles ``(i + 0.5) / n`` of its distribution and put in
an order fixed by the mix alone.  The seed picks only the token contents.
Runs with different seeds then do the same work at the same times, so
seed-to-seed spread is not mistaken for noise.

Arrivals:

* ``"open"``: Poisson arrivals at ``rate_rps``.  The gaps of each block of
  ``round(rate * seconds)`` requests are the stratified quantiles of the
  exponential distribution, in the fixed order; blocks repeat past the
  window (each block in another fixed order) so
  load continues while late first tokens are awaited.
* ``"backlog"``: a closed backlog; every request is due at once and the
  driver keeps the queue longer than the window can drain.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass
class Request:
    """One generated request; the driver fills in what the run observes."""
    index: int
    due: float                    # seconds after the window opens
    prompt: np.ndarray            # int32 token ids
    max_new_tokens: int
    submitted: float | None = None          # host clock, absolute
    token_times: list = dataclasses.field(default_factory=list)
    handle: object = None                   # the engine's request object


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


#: The seed of every order: the same for every run of a mix.
ORDER_SEED = 0


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, as ints.

    ``lognormal``: ``median * exp(sigma * z)``; ``loguniform``: uniform in
    log between ``min`` and ``max``.  Both are clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        x = np.exp(lo + u * (hi - lo))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def prompt_pool(mix: dict, vocab: int, seed: int) -> list[np.ndarray]:
    """The prompts: lengths in the mix's fixed order, tokens from the seed."""
    spec = mix["prompt"]
    lens = quantiles(spec, spec["pool"])
    _rng(ORDER_SEED, 1).shuffle(lens)
    rng = _rng(seed, 1)
    return [rng.integers(0, vocab, size=int(n), dtype=np.int32)
            for n in lens]


class Traffic:
    """An endless, seeded stream of requests for one run of one mix."""

    def __init__(self, mix: dict, vocab: int, seed: int, seconds: float):
        self.mix = mix
        self.pool = prompt_pool(mix, vocab, seed)
        self._rng = _rng(ORDER_SEED, 2)
        self.open_loop = mix["arrivals"] == "open"
        if self.open_loop:
            self.rate = float(mix["rate_rps"])
            self.block = max(1, round(self.rate * seconds))
        elif mix["arrivals"] == "backlog":
            self.rate = None
            self.block = len(self.pool)
        else:
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        self._next = 0
        self._clock = 0.0
        self._pending: list[Request] = []

    def _refill(self) -> None:
        rng = self._rng
        n = self.block
        outs = quantiles(self.mix["output"], n)
        rng.shuffle(outs)
        order = np.concatenate([rng.permutation(len(self.pool))
                                for _ in range(-(-n // len(self.pool)))])
        if self.open_loop:
            u = (np.arange(n) + 0.5) / n
            gaps = -np.log1p(-u) / self.rate
            rng.shuffle(gaps)
        else:
            gaps = np.zeros(n)
        for i in range(n):
            self._clock += float(gaps[i])
            self._pending.append(Request(
                index=self._next, due=self._clock,
                prompt=self.pool[int(order[i])],
                max_new_tokens=int(outs[i])))
            self._next += 1

    def peek(self) -> Request:
        if not self._pending:
            self._refill()
        return self._pending[0]

    def pop(self) -> Request:
        r = self.peek()
        self._pending.pop(0)
        return r
