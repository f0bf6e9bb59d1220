"""The benchmark's road graphs, made from a configuration's numbers.

A copy of the repository's synthetic road generator (a lattice with a
share of its edges deleted for dead ends and rivers, plus a few long
highway shortcuts, integer travel-time weights), kept here so that no
change to the program can change the data a cell is measured on.  The
graph is returned as canonical edge arrays: each undirected edge once,
``u < v``, sorted by ``(u, v)``, parallel edges reduced to the lightest,
restricted to the largest connected component and relabelled densely
in node order.  Both the program under test and the reference are
built from these arrays.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import connected_components


@dataclasses.dataclass(frozen=True)
class Edges:
    """An undirected graph as canonical edge arrays."""
    n: int
    u: np.ndarray   # int32 [m], u < v
    v: np.ndarray   # int32 [m]
    w: np.ndarray   # float64 [m], positive integers

    @property
    def m(self) -> int:
        return int(self.u.size)


def _canonical(u, v, w):
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    order = np.lexsort((w, hi, lo))
    lo, hi, w = lo[order], hi[order], w[order]
    keep = np.ones(lo.size, bool)
    keep[1:] = (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])
    return lo[keep], hi[keep], w[keep]


def road_like(n_target: int, seed: int, *, highway_frac: float = 0.01,
              delete_frac: float = 0.35) -> Edges:
    """Synthetic road network of about ``n_target`` nodes (average
    degree 2.4-3.0, weights 1..999 on streets, 500..4999 on
    highways)."""
    rng = np.random.default_rng(seed)
    side = int(np.sqrt(n_target))
    n = side * side
    ii, jj = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    nid = (ii * side + jj).astype(np.int32)
    u = np.concatenate([nid[:, :-1].ravel(), nid[:-1, :].ravel()])
    v = np.concatenate([nid[:, 1:].ravel(), nid[1:, :].ravel()])
    keep = rng.random(u.size) > delete_frac
    u, v = u[keep], v[keep]
    w = rng.integers(1, 1000, size=u.size).astype(np.float64)
    nh = max(1, int(highway_frac * n))
    hu = rng.integers(0, n, size=nh)
    hv = rng.integers(0, n, size=nh)
    ok = hu != hv
    hu, hv = hu[ok], hv[ok]
    hw = rng.integers(500, 5000, size=hu.size).astype(np.float64)
    lo, hi, w = _canonical(np.concatenate([u, hu]).astype(np.int32),
                           np.concatenate([v, hv]).astype(np.int32),
                           np.concatenate([w, hw]))
    # largest component; components are labelled in order of their
    # lowest node, so argmax keeps the lowest one on a tie
    adj = csr_matrix((np.ones(lo.size), (lo, hi)), shape=(n, n))
    _, comp = connected_components(adj, directed=False)
    big = np.bincount(comp).argmax()
    nodes = np.nonzero(comp == big)[0]
    remap = np.full(n, -1, np.int64)
    remap[nodes] = np.arange(nodes.size)
    inside = (remap[lo] >= 0) & (remap[hi] >= 0)
    # the relabelling keeps node order, so the arrays stay canonical
    return Edges(n=int(nodes.size),
                 u=remap[lo[inside]].astype(np.int32),
                 v=remap[hi[inside]].astype(np.int32),
                 w=w[inside].astype(np.float64))
