"""Uniform origin-destination pairs: s and t uniform over the nodes,
s != t (a copy of the repository's ``uniform`` serving mix)."""
from __future__ import annotations

import numpy as np


def draw(n_nodes: int, count: int, rng: np.random.Generator,
         params: dict, pool) -> np.ndarray:
    """-> [count, 2] int64 pairs."""
    s = rng.integers(0, n_nodes, count)
    t = rng.integers(0, n_nodes, count)
    clash = s == t
    t[clash] = (t[clash] + 1) % n_nodes
    return np.stack([s, t], axis=1).astype(np.int64)
