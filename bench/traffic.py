"""The one traffic generator: reads a mix's parameters and a
configuration's numbers, and draws everything from the run's seed.

A mix file (``bench/mixes/<mix>.json``) names its driver and its pair
kind (``bench/pairs/<kind>.py``) and holds their parameters.  The
deployment's popular pairs, and the hub nodes picked from them, belong
to the configuration: they stand for a traffic log the deployment was
tuned on, so they are the same in every run, and the run's seed draws
which of them are asked, in which order and when.
"""
from __future__ import annotations

import numpy as np

from bench import cells

#: streams of the run's seed, one per thing drawn
PAIRS, ARRIVALS, SAMPLE = 1, 2, 3


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator for one stream of a run's seed (any
    whole number, negative and beyond 64 bits included)."""
    return np.random.default_rng([stream, int(seed) % 2**64])


def popular_pool(config: dict, n_nodes: int) -> np.ndarray:
    """The configuration's popular pairs, in rank order (a Zipf pool
    drawn from the configuration's own seed)."""
    zipf = cells.load_module(cells.ROOT / "bench" / "pairs" / "zipf.py")
    pp = config["popular_pairs"]
    return zipf.make_pool(n_nodes, int(pp["pairs"]),
                          np.random.default_rng(int(pp["seed"])))


def hub_nodes(pool: np.ndarray, budget: int) -> np.ndarray | None:
    """The first distinct endpoints of the pool, in rank order, up to
    ``budget`` nodes (a copy of the serve driver's hub rule); None when
    the budget is 0."""
    if not budget:
        return None
    flat = pool.ravel()          # rank-interleaved (s1, t1, s2, t2, ...)
    _, first = np.unique(flat, return_index=True)
    return flat[np.sort(first)][:budget]


def draw_pairs(cell, n_nodes: int, count: int, rng: np.random.Generator,
               pool: np.ndarray) -> np.ndarray:
    """-> [count, 2] int64 pairs of the cell's mix."""
    return cell.pairs.draw(n_nodes, count, rng, cell.mix["pairs"], pool)
