"""The generator under ``--seed``: the same seed gives the same
requests and arrivals; another seed gives the same multiset in another
order; the program sees only what was generated."""
import json
import os

import numpy as np

from benchmark import traffic_gen as g

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _traffic(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _take(traffic, seed, n):
    src = g.Requests(traffic, 793471, seed)
    return [next(src) for _ in range(n)]


def test_same_seed_same_requests_and_arrivals():
    tr = _traffic("decode-steady")
    assert _take(tr, 3000000019, 700) == _take(tr, 3000000019, 700)
    assert g.arrival_times(tr, 7, 20.0) == g.arrival_times(tr, 7, 20.0)


def test_other_seed_same_work_in_another_order():
    tr = _traffic("decode-saturated")
    a, b = _take(tr, 1, g.ROUND), _take(tr, 2, g.ROUND)
    assert a != b
    for part in (lambda r: len(r[0]), lambda r: r[1]):
        assert sorted(map(part, a)) == sorted(map(part, b))
    ta, tb = (np.diff([0.0] + g.arrival_times(_traffic("decode-steady"), s,
                                              60.0)) for s in (1, 2))
    n = (min(len(ta), len(tb)) // g.ROUND) * g.ROUND
    assert n >= g.ROUND
    assert np.allclose(np.sort(ta[:n]), np.sort(tb[:n]))


def test_lengths_follow_the_file():
    tr = _traffic("decode-saturated")
    reqs = _take(tr, 5, 2 * g.ROUND)
    plen = np.array([len(p) for p, _n in reqs])
    new = np.array([n for _p, n in reqs])
    assert plen.min() >= 4 and plen.max() <= 128
    assert new.min() >= 16 and new.max() <= 384
    assert abs(np.median(plen) - 24) <= 1 and abs(np.median(new) - 96) <= 1
    assert (plen + new).max() <= tr["engine"]["max_len"]
    assert all(1 <= t < 793471 for p, _n in reqs[:50] for t in p)


def test_arrival_rate_and_spread_of_gaps():
    tr = _traffic("decode-steady")
    rate = tr["arrivals"]["rate_per_s"]
    due = g.arrival_times(tr, 11, 20.0)
    assert abs(len(due) / 20.0 - rate) / rate < 0.05
    gaps = np.diff(due)
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1      # exponential gaps
    # stratified: a round of arrivals takes the same time under every seed
    for seed in (11, 12):
        due = g.arrival_times(tr, seed, 2.5 * g.ROUND / rate)
        assert due[g.ROUND - 1] == np.float64(g.ROUND / rate).item() or \
            abs(due[g.ROUND - 1] - g.ROUND / rate) < 1e-9
