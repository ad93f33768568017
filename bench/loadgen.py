"""The one traffic generator: a traffic file's parameters -> a schedule.

A traffic file (``bench/traffic/<name>.json``) holds only numbers:

    {"arrivals":   {"kind": "poisson", "rate_per_s": 4.0},
     "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                    "min": 16, "max": 512},
     "output_len": {"dist": "uniform", "min": 32, "max": 256}, ...}

Prompt lengths, output lengths and inter-arrival gaps are drawn at the
evenly spaced quantiles ``(i + 0.5) / n`` of their distributions, so
every seed offers the same set of sizes and gaps.  Their order is a base
order fixed by the traffic file, in which the seed swaps neighbours: it
shuffles each run of ``SWAP_BLOCK`` consecutive prompt lengths, output
lengths and gaps, each list on its own.  The seed also draws the prompt
tokens (and, elsewhere, the weights).  So a seed changes which requests
meet in a tick and the tokens they carry, while the load over any few
seconds stays the same.  A window holds only some tens of requests: with
the whole order drawn from the seed, the order alone moved a cell's
output tokens/s by 13-16% (inter-quartile, over six seeds) where two
runs of one seed agreed within 1.5% (PERF.md).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

DISTS = ("lognormal", "uniform")
ORDER_SEED = 20250317           # the base order, the same for all seeds
SWAP_BLOCK = 2                  # the seed shuffles runs of this many


@dataclasses.dataclass(frozen=True)
class Item:
    """One request of the schedule."""
    index: int
    due_s: float            # send time, seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new: int


def quantiles(spec: dict, n: int) -> List[int]:
    """``n`` lengths at the evenly spaced quantiles of ``spec``, clipped
    to ``[min, max]``, ascending."""
    dist = spec["dist"]
    lo, hi = int(spec["min"]), int(spec["max"])
    us = [(i + 0.5) / n for i in range(n)]
    if dist == "lognormal":
        nd = NormalDist()
        vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(u))
                for u in us]
    elif dist == "uniform":
        vals = [lo + u * (hi - lo) for u in us]
    else:
        raise ValueError(f"unknown length distribution {dist!r}; "
                         f"known: {DISTS}")
    return [min(max(int(round(v)), lo), hi) for v in vals]


def gaps(arrivals: dict, n: int, seconds: float) -> np.ndarray:
    """``n`` inter-arrival gaps whose sum is exactly ``seconds``."""
    if arrivals["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arrivals['kind']!r}")
    g = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (seconds / g.sum())


def n_requests(traffic: dict, seconds: float) -> int:
    return max(1, int(traffic["arrivals"]["rate_per_s"] * seconds))


def shuffle_blocks(values, rng) -> np.ndarray:
    """``values`` with each run of ``SWAP_BLOCK`` consecutive entries
    shuffled by ``rng``: the same entries, each within its run."""
    out = np.array(values)
    for s in range(0, len(out), SWAP_BLOCK):
        out[s:s + SWAP_BLOCK] = rng.permutation(out[s:s + SWAP_BLOCK])
    return out


def schedule(traffic: dict, seed: int, seconds: float, vocab: int,
             rate: float = 0.0, part: int = 0) -> List[Item]:
    """Requests due in ``[0, seconds)``, their tokens from ``seed``: the
    measured window (``part`` 0) or the pre-roll before it (``part`` 1),
    each its own stream.

    ``rate`` overrides the file's rate (the knee sweep); the benchmark's
    own runs always take the file's."""
    if rate:
        traffic = {**traffic, "arrivals": {**traffic["arrivals"],
                                           "rate_per_s": rate}}
    n = n_requests(traffic, seconds)
    order = np.random.default_rng([ORDER_SEED, part])
    plens = order.permutation(quantiles(traffic["prompt_len"], n))
    olens = order.permutation(quantiles(traffic["output_len"], n))
    g = order.permutation(gaps(traffic["arrivals"], n, seconds))
    swap = np.random.default_rng([seed, part, 1])
    plens, olens, g = (shuffle_blocks(a, swap) for a in (plens, olens, g))
    rng = np.random.default_rng([seed, part])
    # the first request opens the window; the last gap is the idle tail
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    return [Item(index=i, due_s=float(due[i]),
                 prompt=rng.integers(0, vocab, int(plens[i]), dtype=np.int32),
                 max_new=int(olens[i]))
            for i in range(n)]


def warmup_prompts(buckets, vocab: int) -> List[tuple]:
    """``(prompt, max_new)`` pairs that run every prefill bucket once and
    the decode step: one request per bucket, two tokens each.  The same
    for every seed."""
    rng = np.random.default_rng(0)
    return [(rng.integers(0, vocab, b, dtype=np.int32), 2) for b in buckets]
