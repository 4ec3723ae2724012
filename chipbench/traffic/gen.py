"""The one generator of traffic: reads a mix's parameters (a JSON file
beside this one) and makes the requests, or the training batches, of a run
from its seed.

Every seed gets the same multiset of prompt lengths, output lengths and
arrival gaps, taken at the quantiles of the stated distributions; the seed
only orders them and draws the prompt tokens. So runs with different seeds
do the same work in another order. A mix that states ``block`` (an offline
batch, of which a window serves only a prefix) is ordered in blocks of that
many requests, each holding one length of every stratum, so that every
prefix of whole blocks holds the same lengths for every seed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Due:
    """One request of the mix: when it is due (seconds after the window
    opens), its prompt and how many tokens it asks for."""
    index: int
    due: float
    prompt: np.ndarray
    max_new: int


def _quantiles(n):
    return [(i + 0.5) / n for i in range(n)]


def lognormal(n, median, sigma, lo, hi):
    """n lengths at the quantiles of a lognormal, clipped to [lo, hi]."""
    nd = NormalDist()
    return [min(max(int(round(median * math.exp(sigma * nd.inv_cdf(u)))),
                    lo), hi) for u in _quantiles(n)]


def to_bucket(n, buckets):
    """The smallest bucket that holds n, or the largest."""
    return next((b for b in sorted(buckets) if n <= b), max(buckets))


def order(rng, n, block=None):
    """A seeded permutation of range(n), the indices of lengths in rising
    order. With ``block``, each run of ``block`` consecutive places takes one
    index from each of ``block`` strata of rising length, in the seed's order
    (where ``block`` does not divide n, the runs are as even as they can be)."""
    if not block:
        return list(rng.permutation(n))
    nb = -(-n // block)
    blocks = [list(range(b, n, nb)) for b in range(nb)]
    return [blocks[b][i] for b in rng.permutation(nb)
            for i in rng.permutation(len(blocks[b]))]


def count(mix: dict, seconds: float) -> int:
    if mix["arrivals"] == "poisson":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    return int(mix["requests"])


def requests(mix: dict, seed: int, seconds: float, vocab: int):
    """The requests of one run, in the order they are due."""
    n = count(mix, seconds)
    p, o = mix["prompt"], mix["output"]
    prompts = [to_bucket(x, p["buckets"]) for x in lognormal(
        n, p["median"], p["sigma"], min(p["buckets"]), max(p["buckets"]))]
    outs = lognormal(n, o["median"], o["sigma"], o["min"], o["max"])
    rng = np.random.Generator(np.random.PCG64(int(seed) % 2 ** 128))
    block = mix.get("block")
    prompts = [prompts[i] for i in order(rng, n, block)]
    outs = [outs[i] for i in order(rng, n, block)]
    if mix["arrivals"] == "poisson":
        mean = 1.0 / mix["rate_per_s"]
        gaps = [-mean * math.log(1.0 - u) for u in _quantiles(n)]
        gaps = [gaps[i] for i in rng.permutation(n)]
        dues = np.cumsum(gaps)
    else:                                   # offline: all due at once
        dues = np.zeros(n)
    return [Due(i, float(dues[i]),
                rng.integers(0, vocab, prompts[i], dtype=np.int32), outs[i])
            for i in range(n)]


def token_batches(mix: dict, seed: int, vocab: int):
    """The training batches of one run, ``batch`` rows of ``seq_len`` token
    ids each, drawn uniformly from the vocabulary: rows cut from one stream
    of text, as packed pre-training data reaches a step that masks no
    document boundary. Every call with the same seed yields the same
    batches in the same order."""
    rng = np.random.Generator(np.random.PCG64(int(seed) % 2 ** 128))
    while True:
        yield rng.integers(0, vocab, (mix["batch"], mix["seq_len"]),
                           dtype=np.int32)
