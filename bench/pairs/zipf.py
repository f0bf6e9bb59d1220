"""Zipf-skewed repeated pairs (a copy of the repository's ``zipf``
serving mix): a pool of distinct uniform pairs in rank order, and
requests that draw rank r with probability proportional to r**-a."""
from __future__ import annotations

import numpy as np


def make_pool(n_nodes: int, size: int, rng: np.random.Generator) -> np.ndarray:
    """-> [size, 2] int64 distinct (s, t) pairs, s != t, row r = rank
    r + 1.  Over-draws and dedupes in draw order."""
    size = min(size, max(1, n_nodes * (n_nodes - 1)))
    s = rng.integers(0, n_nodes, 2 * size)
    t = rng.integers(0, n_nodes, 2 * size)
    clash = s == t
    t[clash] = (t[clash] + 1
                + rng.integers(0, n_nodes - 1, int(clash.sum()))) % n_nodes
    _, first = np.unique(s * np.int64(n_nodes) + t, return_index=True)
    keep = np.sort(first)[:size]
    return np.stack([s[keep], t[keep]], axis=1).astype(np.int64)


def draw(n_nodes: int, count: int, rng: np.random.Generator,
         params: dict, pool) -> np.ndarray:
    """-> [count, 2] rows of ``pool`` drawn with Zipf(``params["a"]``)
    rank frequencies."""
    p = np.arange(1, len(pool) + 1, dtype=float) ** -float(params["a"])
    p /= p.sum()
    return pool[rng.choice(len(pool), size=count, p=p)]
