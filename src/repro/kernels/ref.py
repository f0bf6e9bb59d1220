"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def minplus_ref(a: jax.Array, b: jax.Array, *, chunk: int = 16
                ) -> jax.Array:
    """C[i,j] = min_k A[i,k] + B[k,j] (tropical GEMM), k-chunked so the
    peak intermediate is [m, chunk, n], never the [m, k, n] cube."""
    m, k = a.shape
    kp = -(-k // chunk) * chunk
    a_p = jnp.pad(a, ((0, 0), (0, kp - k)), constant_values=jnp.inf)
    b_p = jnp.pad(b, ((0, kp - k), (0, 0)), constant_values=jnp.inf)

    def body(i, acc):
        a_c = jax.lax.dynamic_slice_in_dim(a_p, i * chunk, chunk, axis=1)
        b_c = jax.lax.dynamic_slice_in_dim(b_p, i * chunk, chunk, axis=0)
        return jnp.minimum(acc, jnp.min(a_c[:, :, None] + b_c[None],
                                        axis=1))

    return jax.lax.fori_loop(0, kp // chunk, body,
                             jnp.full((m, b.shape[1]), jnp.inf, a.dtype))


def minplus_accum_ref(c: jax.Array, a: jax.Array, b: jax.Array
                      ) -> jax.Array:
    return jnp.minimum(c, minplus_ref(a, b))


def label_merge_ref(labs: jax.Array, labt: jax.Array) -> jax.Array:
    """out[q] = min_j labs[q,j] + labt[q,j] (hub-label merge)."""
    return jnp.min(labs + labt, axis=1)


def minplus_twoside_ref(rows: jax.Array, d: jax.Array, rowt: jax.Array,
                        *, chunk: int = 16) -> jax.Array:
    """out[q] = min_{x,y} rows[q,x] + d[x,y] + rowt[q,y].

    x-chunked so the peak intermediate is [q, chunk, k2], never the
    full [q, k1, k2] cube (mirrors the Pallas kernel's contract).
    """
    q, k1 = rows.shape
    k2 = rowt.shape[1]
    k1p = -(-k1 // chunk) * chunk
    rows_p = jnp.full((q, k1p), jnp.inf, rows.dtype).at[:, :k1].set(rows)
    d_p = jnp.full((k1p, k2), jnp.inf, d.dtype).at[:k1].set(d)

    def body(i, acc):
        r_c = jax.lax.dynamic_slice_in_dim(rows_p, i * chunk, chunk,
                                           axis=1)
        d_c = jax.lax.dynamic_slice_in_dim(d_p, i * chunk, chunk, axis=0)
        cand = jnp.min(r_c[:, :, None] + d_c[None, :, :], axis=1)
        return jnp.minimum(acc, cand)

    tmp = jax.lax.fori_loop(0, k1p // chunk, body,
                            jnp.full((q, k2), jnp.inf, rows.dtype))
    return jnp.min(tmp + rowt, axis=1)


def minplus_twoside_grouped_ref(rows: jax.Array, d: jax.Array,
                                rowt: jax.Array, tile_group: jax.Array,
                                live: jax.Array, *, bq: int) -> jax.Array:
    """out[r] = min_{x,y} rows[r,x] + d[tile_group[r // bq], x, y]
    + rowt[r,y] for r < live * bq, +inf past it: one plain twoside per
    closure of the stack, each row taking its own tile's."""
    grp = jnp.repeat(tile_group, bq)
    out = jnp.full((rows.shape[0],), jnp.inf, rows.dtype)
    for g in range(d.shape[0]):
        out = jnp.where(grp == g, minplus_twoside_ref(rows, d[g], rowt),
                        out)
    return jnp.where(jnp.arange(rows.shape[0]) < live * bq, out, jnp.inf)


def minplus_grouped_ref(a: jax.Array, b: jax.Array, tile_group: jax.Array,
                        live: jax.Array, *, bq: int) -> jax.Array:
    """out[r, j] = min_k a[r, k] + b[tile_group[r // bq], k, j] for
    r < live * bq, +inf past it: one plain product per block of the
    stack, each row taking its own tile's."""
    grp = jnp.repeat(tile_group, bq)[:, None]
    out = jnp.full((a.shape[0], b.shape[2]), jnp.inf, a.dtype)
    for g in range(b.shape[0]):
        out = jnp.where(grp == g, minplus_ref(a, b[g]), out)
    live_row = jnp.arange(a.shape[0]) < live * bq
    return jnp.where(live_row[:, None], out, jnp.inf)


def minplus_twoside_argmin_ref(rows: jax.Array, d: jax.Array,
                               rowt: jax.Array, *, chunk: int = 16
                               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Witness-tracking twoside contraction: (out, wx, wy) with
    out[q] = rows[q, wx[q]] + d[wx[q], wy[q]] + rowt[q, wy[q]] whenever
    out[q] is finite; wx = wy = -1 otherwise.  Same x-chunked schedule
    as minplus_twoside_ref, carrying the winning x per (q, y) cell."""
    q, k1 = rows.shape
    k2 = rowt.shape[1]
    k1p = -(-k1 // chunk) * chunk
    rows_p = jnp.full((q, k1p), jnp.inf, rows.dtype).at[:, :k1].set(rows)
    d_p = jnp.full((k1p, k2), jnp.inf, d.dtype).at[:k1].set(d)

    def body(i, carry):
        acc, accx = carry
        r_c = jax.lax.dynamic_slice_in_dim(rows_p, i * chunk, chunk,
                                           axis=1)
        d_c = jax.lax.dynamic_slice_in_dim(d_p, i * chunk, chunk, axis=0)
        cube = r_c[:, :, None] + d_c[None, :, :]       # [q, chunk, k2]
        cand = jnp.min(cube, axis=1)
        # smallest chunk-local x achieving the min (tie-stable)
        hit = cube == cand[:, None, :]
        loc = jnp.min(jnp.where(hit,
                                jnp.arange(chunk, dtype=jnp.int32)[None, :,
                                                                   None],
                                jnp.int32(k1p)), axis=1)
        better = cand < acc
        return (jnp.where(better, cand, acc),
                jnp.where(better, i * chunk + loc, accx))

    acc0 = jnp.full((q, k2), jnp.inf, rows.dtype)
    accx0 = jnp.full((q, k2), -1, jnp.int32)
    acc, accx = jax.lax.fori_loop(0, k1p // chunk, body, (acc0, accx0))
    tmp = acc + rowt                                   # [q, k2]
    out = jnp.min(tmp, axis=1)
    hit = tmp == out[:, None]
    wy = jnp.min(jnp.where(hit, jnp.arange(k2, dtype=jnp.int32)[None, :],
                           jnp.int32(k2)), axis=1)
    fin = jnp.isfinite(out)
    wy = jnp.where(fin, wy, -1)
    wx = jnp.where(fin,
                   jnp.take_along_axis(accx, jnp.clip(wy, 0)[:, None],
                                       axis=1)[:, 0], -1)
    return out, wx, wy


def first_hops_ref(adj: jax.Array, dist: jax.Array, target: jax.Array,
                   *, chunk: int = 8) -> jax.Array:
    """out[i, j] = the smallest k with adj[i, k] + dist[k, j] ==
    target[i, j] (-1: none), k-chunked so the peak intermediate is
    [r, chunk, c].  Same contract as first_hop.first_hop_pallas."""
    r, n = adj.shape
    c = dist.shape[1]
    n_p = -(-n // chunk) * chunk
    adj_p = jnp.pad(adj, ((0, 0), (0, n_p - n)), constant_values=jnp.inf)
    dist_p = jnp.pad(dist, ((0, n_p - n), (0, 0)), constant_values=jnp.inf)
    none = jnp.int32(n_p)

    def body(i, acc):
        a_c = jax.lax.dynamic_slice_in_dim(adj_p, i * chunk, chunk, axis=1)
        d_c = jax.lax.dynamic_slice_in_dim(dist_p, i * chunk, chunk, axis=0)
        hit = a_c[:, :, None] + d_c[None, :, :] == target[:, None, :]
        k = i * chunk + jnp.arange(chunk, dtype=jnp.int32)[None, :, None]
        return jnp.minimum(acc, jnp.min(jnp.where(hit, k, none), axis=1))

    acc = jax.lax.fori_loop(0, n_p // chunk, body,
                            jnp.full((r, c), none, jnp.int32))
    return jnp.where(acc == none, -1, acc)


def fw_ref(d: jax.Array) -> jax.Array:
    """Floyd-Warshall APSP on one [n, n] matrix (diag forced to 0)."""
    n = d.shape[0]
    d = jnp.where(jnp.eye(n, dtype=bool), 0.0, d)

    def body(k, mat):
        row = jax.lax.dynamic_slice_in_dim(mat, k, 1, axis=0)
        col = jax.lax.dynamic_slice_in_dim(mat, k, 1, axis=1)
        return jnp.minimum(mat, col + row)

    return jax.lax.fori_loop(0, n, body, d)


def fw_batch_ref(d: jax.Array) -> jax.Array:
    return jax.vmap(fw_ref)(d)


def fw_next_init(d: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(diag-zeroed distances, first-hop successor init) for witness FW.

    nxt[i, j] = j where (i, j) is a direct edge, -1 elsewhere (incl. the
    diagonal) — the classic FW path-reconstruction convention: following
    nxt from i lands one adjacency hop closer to j at every step.
    """
    n = d.shape[-1]
    eye = jnp.eye(n, dtype=bool)
    d0 = jnp.where(eye, 0.0, d)
    cols = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), d.shape)
    nxt0 = jnp.where(jnp.isfinite(d0) & ~eye, cols, -1)
    return d0, nxt0


def fw_next_ref(d: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Witness-carrying Floyd-Warshall on one [n, n] matrix.

    Returns (dist, nxt); dist is bit-identical to fw_ref (the update is
    the same strict-improvement recurrence in the same pivot order), and
    nxt[i, j] is the first hop of a shortest i -> j path (-1 when
    j is unreachable or i == j).
    """
    n = d.shape[0]
    mat0, nxt0 = fw_next_init(d)

    def body(k, carry):
        mat, nxt = carry
        row = jax.lax.dynamic_slice_in_dim(mat, k, 1, axis=0)
        col = jax.lax.dynamic_slice_in_dim(mat, k, 1, axis=1)
        cand = col + row
        nk = jax.lax.dynamic_slice_in_dim(nxt, k, 1, axis=1)  # nxt[:, k]
        better = cand < mat
        return (jnp.where(better, cand, mat),
                jnp.where(better, jnp.broadcast_to(nk, nxt.shape), nxt))

    return jax.lax.fori_loop(0, n, body, (mat0, nxt0))


def fw_batch_next_ref(d: jax.Array) -> tuple[jax.Array, jax.Array]:
    return jax.vmap(fw_next_ref)(d)


# NOTE (measured): a chunked blocked-panel FW variant of fw_ref was
# tried for the CPU overlay closure and came out ~8x slower at n=625 —
# its [n, chunk, n] broadcast intermediates thrash memory, while the n
# small single-pivot iterations above stay cache-resident and fuse.
# The blocked schedule only pays off inside the Pallas kernel
# (floyd_warshall.py), where tiles are explicitly VMEM-resident.
