"""The traffic generator: seeded, the same work for every seed, and the
length mix it states."""
import json
import math
import os
from collections import Counter
from statistics import NormalDist

import numpy as np
import pytest

from chipbench.traffic import gen

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["serve-migrate", "serve-steady"])
def test_same_seed_same_requests(name):
    a = gen.requests(mix(name), 2**40 + 3, 51.0, 100352)
    b = gen.requests(mix(name), 2**40 + 3, 51.0, 100352)
    assert [(r.due, r.max_new) for r in a] == [(r.due, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = gen.requests(mix(name), 2**40 + 4, 51.0, 100352)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["serve-migrate", "serve-steady"])
def test_every_seed_gets_the_same_work(name):
    runs = [gen.requests(mix(name), s, 51.0, 1000) for s in (1, 2**33 + 5)]
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert Counter(map(key, runs[0])) == Counter(map(key, runs[1]))
    gaps = [np.diff([0.0] + [r.due for r in rs]) for rs in runs]
    assert sorted(gaps[0]) == pytest.approx(sorted(gaps[1]))
    assert [len(r.prompt) for r in runs[0]] != [len(r.prompt) for r in runs[1]]


def test_length_mix_is_as_stated():
    m = mix("serve-migrate")
    reqs = gen.requests(m, 7, 51.0, 1000)
    assert len(reqs) == round(m["rate_per_s"] * 51.0)
    p, n = m["prompt"], len(reqs)
    nd = NormalDist()
    cdf = lambda x: nd.cdf(math.log(x / p["median"]) / p["sigma"])  # noqa
    counts = Counter(len(r.prompt) for r in reqs)
    edges = [0] + sorted(p["buckets"])
    for lo, hi in zip(edges, edges[1:]):
        want = (1.0 if hi == max(p["buckets"]) else cdf(hi)) - \
            (cdf(lo) if lo else 0.0)
        assert abs(counts[hi] / n - want) <= 1.5 / n
    o = m["output"]
    outs = [r.max_new for r in reqs]
    assert o["min"] <= min(outs) and max(outs) <= o["max"]
    assert np.median(outs) == pytest.approx(o["median"], abs=1)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in reqs)


def test_arrivals_are_poisson_at_the_rate():
    m = mix("serve-migrate")
    reqs = gen.requests(m, 11, 51.0, 1000)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and dues[0] > 0.0
    gaps = np.diff([0.0] + dues)
    assert np.mean(gaps) == pytest.approx(1 / m["rate_per_s"], rel=0.05)
    # exponential: the coefficient of variation is near 1
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.2)
    assert dues[-1] < 51.0


def test_offline_prefixes_do_the_same_work():
    """The window serves a prefix of the offline batch: every whole block of
    it takes one length from each stratum, so prefixes of whole blocks carry
    about the same work for every seed, in another order."""
    m = mix("serve-steady")
    k = m["block"]
    runs = [gen.requests(m, s, 51.0, 1000) for s in (3, 2**40 + 9)]
    for blocks in (4, 8, 12):
        for key in (lambda r: len(r.prompt), lambda r: r.max_new):
            a, b = (sum(map(key, rs[:blocks * k])) for rs in runs)
            assert a == pytest.approx(b, rel=0.03)
    assert [len(r.prompt) for r in runs[0][:k]] != \
        [len(r.prompt) for r in runs[1][:k]]


@pytest.mark.parametrize("n,block", [(1200, 40), (103, 20), (103, None)])
def test_order_is_a_stratified_permutation(n, block):
    got = gen.order(np.random.default_rng(1), n, block)
    assert sorted(got) == list(range(n))
    if block and n % block == 0:
        nb = n // block
        for j in range(0, n - n % block, block):
            assert {i // nb for i in got[j:j + block]} == set(range(block))


def test_offline_is_all_due_at_once():
    m = mix("serve-steady")
    reqs = gen.requests(m, 5, 51.0, 1000)
    assert len(reqs) == m["requests"]
    assert {r.due for r in reqs} == {0.0}


def test_buckets():
    assert gen.to_bucket(1, [128, 256]) == 128
    assert gen.to_bucket(129, [128, 256]) == 256
    assert gen.to_bucket(999, [128, 256]) == 256
