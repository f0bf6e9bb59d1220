"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy: on TPU the Pallas kernels compile natively; on CPU the
default is the pure-jnp reference (XLA-compiled, fast) so host-side
pipelines stay usable, while ``force="pallas"`` runs the kernels in
interpret mode — tests use that to exercise tiling/indexing end-to-end.
"""
from __future__ import annotations

from typing import Literal, Optional

import jax

from . import first_hop as _fh
from . import floyd_warshall as _fw
from . import label_merge as _lm
from . import minplus as _mp
from . import minplus_twoside as _ts
from . import ref as _ref

Force = Optional[Literal["pallas", "ref"]]


def _use_pallas(force: Force) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)."""
    if force == "ref":
        return False, False
    on_tpu = jax.default_backend() == "tpu"
    if force == "pallas":
        return True, not on_tpu
    return on_tpu, False


def minplus(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
            bk: int = 128, force: Force = None) -> jax.Array:
    """Tropical GEMM: min_k A[i,k] + B[k,j]."""
    pallas, interp = _use_pallas(force)
    if pallas:
        return _mp.minplus_pallas(a, b, bm=bm, bn=bn, bk=bk,
                                  interpret=interp)
    return _ref.minplus_ref(a, b)


def minplus_twoside(rows: jax.Array, d: jax.Array, rowt: jax.Array, *,
                    bq: int = 128, bk1: int = 128, bk2: int = 128,
                    force: Force = None) -> jax.Array:
    """Fused two-sided contraction: out[q] = min_{x,y} rows[q,x]
    + d[x,y] + rowt[q,y] — the serve-path combine, [q,k,k]-free."""
    pallas, interp = _use_pallas(force)
    if pallas:
        return _ts.minplus_twoside_pallas(rows, d, rowt, bq=bq, bk1=bk1,
                                          bk2=bk2, interpret=interp)
    return _ref.minplus_twoside_ref(rows, d, rowt)


#: rows of one q tile of the grouped contraction
GROUPED_TILE_ROWS = _ts.GROUPED_BQ


def minplus_twoside_grouped(rows: jax.Array, d: jax.Array,
                            rowt: jax.Array, tile_group: jax.Array,
                            live: jax.Array, *, force: Force = None
                            ) -> jax.Array:
    """Grouped two-sided contraction: out[r] = min_{x,y} rows[r,x]
    + d[tile_group[r // bq], x, y] + rowt[r,y] for the rows of the
    first ``live`` q tiles of bq = GROUPED_TILE_ROWS rows, +inf past
    them — one call for every closure of the stack d [G, m2, m2], each
    tile streaming only its own."""
    pallas, interp = _use_pallas(force)
    if pallas:
        return _ts.minplus_twoside_grouped_pallas(
            rows, d, rowt, tile_group, live, interpret=interp)
    return _ref.minplus_twoside_grouped_ref(rows, d, rowt, tile_group,
                                            live, bq=GROUPED_TILE_ROWS)


def minplus_grouped(a: jax.Array, b: jax.Array, tile_group: jax.Array,
                    live: jax.Array, *, force: Force = None) -> jax.Array:
    """Grouped one-sided tropical product: out[r, j] = min_k a[r, k]
    + b[tile_group[r // bq], k, j] for the rows of the first ``live``
    q tiles of bq = GROUPED_TILE_ROWS rows, +inf past them — one call
    for every block of the stack b [G, K, N], each tile streaming only
    its own (the boundary lifts)."""
    pallas, interp = _use_pallas(force)
    if pallas:
        return _ts.minplus_grouped_pallas(a, b, tile_group, live,
                                          interpret=interp)
    return _ref.minplus_grouped_ref(a, b, tile_group, live,
                                    bq=GROUPED_TILE_ROWS)


def label_merge(labs: jax.Array, labt: jax.Array, *, bq: int = 128,
                bj: int = 512, force: Force = None) -> jax.Array:
    """Hub-label merge: out[q] = min_j labs[q,j] + labt[q,j] — the
    hot-tier combine (DESIGN.md §15), O(W) per query."""
    pallas, interp = _use_pallas(force)
    if pallas:
        return _lm.label_merge_pallas(labs, labt, bq=bq, bj=bj,
                                      interpret=interp)
    return _ref.label_merge_ref(labs, labt)


def minplus_twoside_argmin(rows: jax.Array, d: jax.Array,
                           rowt: jax.Array, *, bq: int = 128,
                           bk1: int = 128, bk2: int = 128,
                           force: Force = None
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Witness-returning twoside contraction -> (out, wx, wy): the
    winning (x, y) pair alongside each minimum, -1 where out is +inf.
    The path-reconstruction serve mode's combine step (DESIGN.md §10)."""
    pallas, interp = _use_pallas(force)
    if pallas:
        return _ts.minplus_twoside_argmin_pallas(
            rows, d, rowt, bq=bq, bk1=bk1, bk2=bk2, interpret=interp)
    return _ref.minplus_twoside_argmin_ref(rows, d, rowt)


def use_pallas(force: Force = None) -> bool:
    """Expose the dispatch decision (engines pick layouts with it)."""
    return _use_pallas(force)[0]


def minplus_accum(c: jax.Array, a: jax.Array, b: jax.Array, *,
                  bm: int = 128, bn: int = 128, bk: int = 128,
                  force: Force = None) -> jax.Array:
    """min(C, A (x) B)."""
    pallas, interp = _use_pallas(force)
    if pallas:
        return _mp.minplus_accum_pallas(c, a, b, bm=bm, bn=bn, bk=bk,
                                        interpret=interp)
    return _ref.minplus_accum_ref(c, a, b)


def fw_batch(d: jax.Array, *, force: Force = None) -> jax.Array:
    """Batched dense APSP over [b, n, n] fragment matrices."""
    pallas, interp = _use_pallas(force)
    if pallas:
        return _fw.fw_batch_pallas(d, interpret=interp)
    return _ref.fw_batch_ref(d)


def fw_batch_next(d: jax.Array, *, force: Force = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Witness-carrying batched APSP -> (dist, nxt); dist bit-identical
    to fw_batch, nxt[b, i, j] = first hop of a shortest i -> j path in
    batch entry b (-1: unreachable / diagonal).  One Pallas closure for
    every width (``fw_batch_next_blocked``): its ties depend on the tile
    width and nothing else, so a build and a refresh of one level, which
    close it at one width, always agree."""
    pallas, interp = _use_pallas(force)
    if pallas:
        return _fw.fw_batch_next_blocked(d, interpret=interp)
    return _ref.fw_batch_next_ref(d)


def fw_next_variant(n: int, force: Force = None) -> str:
    """The witness closure ``fw_batch_next`` runs at tile width ``n``:
    "whole_tile" (one pivot block), "blocked" or "ref" (the jnp path)."""
    if not _use_pallas(force)[0]:
        return "ref"
    return "blocked" if n > _fw.FW_BLOCK else "whole_tile"


def fw_next(d: jax.Array, *, force: Force = None
            ) -> tuple[jax.Array, jax.Array]:
    """Witness-carrying APSP for a single [n, n] matrix: a batch of one
    (the dense overlay closure)."""
    pallas, _ = _use_pallas(force)
    if pallas:
        dist, nxt = fw_batch_next(d[None], force=force)
        return dist[0], nxt[0]
    return _ref.fw_next_ref(d)


def first_hops(adj: jax.Array, dist: jax.Array, target: jax.Array, *,
               force: Force = None) -> jax.Array:
    """Canonical first hops: out[i, j] = the smallest k with
    adj[i, k] + dist[k, j] == target[i, j], -1 where none is (the top
    closure's witnesses, ``hierarchy.top_first_hops``)."""
    pallas, interp = _use_pallas(force)
    if pallas:
        return _fh.first_hop_pallas(adj, dist, target, interpret=interp)
    return _ref.first_hops_ref(adj, dist, target)


def fw_apsp(d: jax.Array, *, block: int = 128,
            force: Force = None) -> jax.Array:
    """Blocked APSP for a single [n, n] matrix.

    The CPU path stays single-pivot on purpose: a chunked blocked-panel
    jnp schedule was benchmarked 8x SLOWER at n=625 (the [n, chunk, n]
    broadcast intermediates thrash memory, while XLA fuses the n small
    col+row+min iterations cache-resident).
    """
    pallas, interp = _use_pallas(force)
    if pallas:
        return _fw.fw_blocked(d, block=block, interpret=interp)
    return _ref.fw_ref(d)
