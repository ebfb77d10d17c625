"""Serving traffic: arrivals and request lengths from a traffic file.

A traffic file reads, for example::

    {"generator": "requests",
     "arrivals": {"kind": "poisson", "rate": 1.6},
     "prompt": {"dist": "lognormal", "mean": 161.31, "sigma": 0.8,
                "min": 4, "max": 512},
     "output": {"dist": "lognormal", "mean": 337.99, "sigma": 0.8,
                "min": 4, "max": 480},
     "pool": 64}

A length distribution gives its ``mean``, as trace studies publish it (the
lognormal's median is then ``mean * exp(-sigma**2 / 2)``), and ``min`` and
``max`` clip it.  Other keys, such as the source, are notes for the
reader.

``arrivals.kind`` is ``closed`` (a backlog the server drains as fast as it
can; ``depth`` requests are kept waiting) or ``poisson`` (an open loop at
``rate`` requests per second).

Every seed serves the same work in another order.  Lengths are the
``pool`` quantiles of their distribution, at ``(i + 0.5) / pool``, and
Poisson gaps the ``pool`` quantiles of the exponential distribution; each
pass through the pool is a fresh permutation drawn from the seed, and the
prompt tokens are drawn from it too.  So runs with different seeds do the
same amount of work and differ only in what a scheduler sees first.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, Iterator

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    offset: float           # seconds after the window opens; 0 for closed
    prompt: np.ndarray      # int32 token ids
    max_new: int


def quantiles(spec: Dict, pool: int) -> np.ndarray:
    """The ``pool`` stratified lengths of a length distribution."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / pool)
                  for i in range(pool)])
    sigma = spec["sigma"]
    median = spec["mean"] * math.exp(-sigma ** 2 / 2)
    raw = np.round(median * np.exp(sigma * z))
    return np.clip(raw, spec["min"], spec["max"]).astype(np.int64)


def gaps(arrivals: Dict, pool: int) -> np.ndarray:
    """The ``pool`` stratified inter-arrival gaps, in seconds."""
    kind = arrivals["kind"]
    if kind == "closed":
        return np.zeros(pool)
    if kind == "poisson":
        u = (np.arange(pool) + 0.5) / pool
        return -np.log1p(-u) / float(arrivals["rate"])
    raise ValueError(f"unknown arrival kind {kind!r}")


def stream(traffic: Dict, seed: int, vocab: int) -> Iterator[Arrival]:
    """Requests in arrival order, without end."""
    pool = int(traffic["pool"])
    prompt_len = quantiles(traffic["prompt"], pool)
    out_len = quantiles(traffic["output"], pool)
    gap = gaps(traffic["arrivals"], pool)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    t = 0.0
    while True:
        p, o, g = (rng.permutation(pool) for _ in range(3))
        for i in range(pool):
            t += float(gap[g[i]])
            yield Arrival(offset=t,
                          prompt=rng.integers(0, vocab, int(prompt_len[p[i]]),
                                              dtype=np.int32),
                          max_new=int(out_len[o[i]]))


def mean_rate(traffic: Dict) -> float:
    """Requests per second over one pass of the pool (inf when closed)."""
    g = gaps(traffic["arrivals"], int(traffic["pool"]))
    return math.inf if not g.sum() else len(g) / float(g.sum())
