"""The plain reference, its control, and the comparison that decides
``correct``.

The reference is Dijkstra in float64 (SciPy's), on the benchmark's own
edge arrays: it imports nothing of the program and takes nothing the
program made.  The deployment's guarantee is exact distances: weights
are integers and every shortest distance stays below 2**24, so the
program's float32 answers must equal the reference's exactly.

The control is the reference computed one precision below the
configuration's float32: each shortest path's weights summed in
bfloat16, hop by hop from the source.  It has to come out as not
correct under the same comparison.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

#: sources per Dijkstra call: bounds the [block, n] float64 result
BLOCK = 64


class Reference:
    def __init__(self, edges):
        self.edges = edges
        self.csr = csr_matrix((edges.w, (edges.u, edges.v)),
                              shape=(edges.n, edges.n))
        # sorted keys of the canonical edges, for weight lookups
        self._keys = edges.u.astype(np.int64) * edges.n + edges.v

    def _by_source(self, s, t, *, predecessors: bool):
        s = np.asarray(s, np.int64)
        t = np.asarray(t, np.int64)
        src, inv = np.unique(s, return_inverse=True)
        for lo in range(0, src.size, BLOCK):
            block = src[lo:lo + BLOCK]
            res = dijkstra(self.csr, directed=False, indices=block,
                           return_predecessors=predecessors)
            for q in np.nonzero((inv >= lo) & (inv < lo + BLOCK))[0]:
                yield q, inv[q] - lo, res

    def distances(self, s, t) -> np.ndarray:
        """float64 shortest distances of the pairs (s[i], t[i])."""
        out = np.empty(len(s), np.float64)
        for q, row, dist in self._by_source(s, t, predecessors=False):
            out[q] = dist[row, t[q]]
        return out

    def weight(self, a, b) -> np.ndarray:
        lo, hi = np.minimum(a, b), np.maximum(a, b)
        idx = np.searchsorted(self._keys, lo * self.edges.n + hi)
        return self.edges.w[idx]

    def control_bf16(self, s, t) -> np.ndarray:
        """The reference with its sums in bfloat16: each pair's
        shortest path, its weights summed from the source in bfloat16."""
        bf16 = ml_dtypes.bfloat16
        out = np.empty(len(s), np.float64)
        for q, row, (_, pred) in self._by_source(s, t, predecessors=True):
            path = [int(t[q])]
            while path[-1] != s[q] and path[-1] >= 0:
                path.append(int(pred[row, path[-1]]))
            if path[-1] < 0:
                out[q] = np.inf
                continue
            nodes = np.asarray(path[::-1], np.int64)
            d = bf16(0)
            for w in self.weight(nodes[:-1], nodes[1:]):
                d = bf16(np.float32(d) + np.float32(bf16(w)))
            out[q] = float(d)
        return out


def mismatches(got, want) -> int:
    """Answers that differ from the reference: exact equality, an
    unreachable pair only by +inf, anything not a number always."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return int(np.sum(~(got == want)))
