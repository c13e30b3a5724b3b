"""The one generator of serving traffic: requests and arrival times from
a traffic file's parameters and ``--seed``.

Every seed gets the same multiset of lengths and of gaps between
arrivals, the quantiles of the stated distributions, in an order drawn
from the seed: the seed changes which request meets which, never how
much work a run holds.  Lengths and gaps come in rounds of ``ROUND``
draws, each round a fresh permutation of the same quantiles.

Distributions (``{"dist": ...}``):
  lognormal  median, sigma, min, max   (clipped, rounded to whole tokens)
Arrivals (``{"process": ...}``):
  exponential_stratified  rate_per_s

``exponential_stratified`` is not a Poisson process: the gaps of a round
are the ``ROUND`` quantiles of the exponential distribution, shuffled, so
every ``ROUND`` arrivals take exactly ``ROUND / rate_per_s`` seconds and
the count in a long window does not vary from seed to seed as a Poisson
count would (at 80/s over 20 s: 1,600 +- 40).  Inside a round the gaps
bunch as exponential gaps do.  A tail read under it is that of the stated
rate held steady, and understates the tail under true Poisson load.
"""
import math
from statistics import NormalDist

import numpy as np

ROUND = 512


def _quantile_points(n):
    return (np.arange(n) + 0.5) / n


def length_quantiles(spec, n=ROUND):
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(q)) for q in _quantile_points(n)])
        v = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError("unknown length distribution %r" % (spec["dist"],))


def gap_quantiles(spec, n=ROUND):
    """Gaps between arrivals in seconds, mean 1 / rate_per_s."""
    if spec["process"] != "exponential_stratified":
        raise ValueError("unknown arrival process %r" % (spec["process"],))
    g = -np.log1p(-_quantile_points(n))
    return g / g.mean() / float(spec["rate_per_s"])


class Requests(object):
    """An endless seeded stream of (prompt ids, max new tokens)."""

    def __init__(self, traffic, vocab, seed):
        self._rng = np.random.default_rng([seed, 1])
        self._vocab = vocab
        self._prompt_q = length_quantiles(traffic["prompt_len"])
        self._new_q = length_quantiles(traffic["new_tokens"])
        self._round = iter(())

    def __iter__(self):
        return self

    def __next__(self):
        try:
            plen, new = next(self._round)
        except StopIteration:
            p = self._rng.permutation(self._prompt_q)
            n = self._rng.permutation(self._new_q)
            self._round = iter(zip(p.tolist(), n.tolist()))
            plen, new = next(self._round)
        prompt = self._rng.integers(1, self._vocab, plen).tolist()
        return prompt, int(new)


def arrival_times(traffic, seed, horizon_s):
    """Due times in [0, horizon_s) of an open loop."""
    rng = np.random.default_rng([seed, 2])
    base = gap_quantiles(traffic["arrivals"])
    need = int(math.ceil(horizon_s * traffic["arrivals"]["rate_per_s"]
                         / ROUND)) + 1
    gaps = np.concatenate([rng.permutation(base) for _ in range(need)])
    due = np.cumsum(gaps)
    return due[due < horizon_s].tolist()
