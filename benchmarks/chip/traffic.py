"""The one generator that every traffic file feeds.

Lengths are a fixed set: the quantiles of the mix's lognormal at
``(i + 0.5) / n``, clipped, in an order drawn from ``length_seed``. Every
run seed therefore does the same work in the same order (which also fixes
which requests the engine's own sampler shadows), and the run seed only
draws the token ids.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import List, Tuple

import numpy as np


def rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed))


def lognormal_lengths(n: int, spec: dict, order_seed: int) -> np.ndarray:
    q = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.clip(np.round(spec["median"] * np.exp(spec["sigma"] * q)),
                spec["min"], spec["max"]).astype(np.int64)
    np.random.RandomState(order_seed).shuffle(v)
    return v


def closed_loop(traffic: dict, vocab: int, seed: int
                ) -> List[Tuple[np.ndarray, int]]:
    """``(prompt ids, output budget)`` in submission order. The first
    ``clients`` requests open the run together, each cut to a share
    ``(i + 0.5) / clients`` of its lengths so that completions are
    staggered from the start; every later one is sent when a request
    completes."""
    clients = traffic["clients"]
    n = clients * traffic["requests_per_client"]
    prompts = lognormal_lengths(n, traffic["prompt"], traffic["length_seed"])
    outs = lognormal_lengths(n, traffic["output"],
                             traffic["length_seed"] + 1)
    ids = rng(seed)
    reqs = []
    for i in range(n):
        p, o = int(prompts[i]), int(outs[i])
        if i < clients:
            share = (i + 0.5) / clients
            p, o = max(1, round(p * share)), max(1, round(o * share))
        reqs.append((ids.integers(1, vocab, p).astype(np.int32), o))
    return reqs
