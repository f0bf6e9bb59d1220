"""Fused two-sided tropical contraction — the DISLAND combine step.

    out[q] = min_{x, y} rows[q, x] + D[x, y] + rowt[q, y]

This is the serve-path case-2 middle term: distances query-source ->
SUPER nodes (rows), SUPER x SUPER APSP (D), SUPER -> query-target
(rowt), contracted over BOTH super indices at once.  The naive
formulation gathers a per-query [mb, mb] block of D (O(q*mb^2) HBM
traffic); here D is streamed tile-by-tile through VMEM exactly once per
query tile and the [q, x, y] intermediate is never materialized.

TPU mapping (VPU work, no MXU form for (min,+)): grid is
(q tiles, y tiles, x tiles) with the two contraction axes innermost and
sequential, so the output tile is min-accumulated across all (x, y)
tile pairs (revisiting pattern).  Each invocation reduces its
[bq, bk1] x [bk1, bk2] x [bq, bk2] triple down to per-lane partial
minima [bq, 128]; the final cross-lane min happens outside the kernel
(a trivial [q, 128] -> [q] reduce).

The grouped form (``minplus_twoside_grouped_pallas``) contracts each
q tile against its own block of a stack of closures: the caller sorts
rows into tiles of one group each, and the closure block of every tile
is picked by scalar prefetch, so one call serves every group and a
tile streams only the closure it needs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .minplus import minplus_tile

_LANES = 128


def _twoside_kernel(rows_ref, d_ref, rowt_ref, out_ref):
    """Min-accumulate one (q, y, x) tile triple into lane partials."""
    _twoside_step(pl.program_id(1), pl.program_id(2), rows_ref, d_ref,
                  rowt_ref, out_ref)


def _twoside_step(yi, xi, rows_ref, d_ref, rowt_ref, out_ref):
    """_twoside_kernel's body at y block ``yi`` and x block ``xi``."""
    @pl.when((yi == 0) & (xi == 0))
    def _init():
        out_ref[...] = jnp.full_like(out_ref, jnp.inf)

    rows = rows_ref[...]          # [bq, bk1]
    rowt = rowt_ref[...]          # [bq, bk2]
    bq, bk2 = rowt.shape
    tmp = minplus_tile(rows, d_ref[...],
                       jnp.full((bq, bk2), jnp.inf, rows.dtype))
    tmp = tmp + rowt              # [bq, bk2]
    # fold the y tile down to the output's lanes (128, or the whole
    # tile where it is narrower); cross-lane min is done by the caller
    # so every store here stays (8, 128)-aligned
    lanes = out_ref.shape[1]
    part = jnp.min(tmp.reshape(bq, bk2 // lanes, lanes), axis=1)
    out_ref[...] = jnp.minimum(out_ref[...], part)


@functools.partial(jax.jit, static_argnames=("bq", "bk1", "bk2",
                                             "interpret"))
def minplus_twoside_pallas(rows: jax.Array, d: jax.Array,
                           rowt: jax.Array, *, bq: int = 128,
                           bk1: int = 128, bk2: int = 128,
                           interpret: bool = False) -> jax.Array:
    """out[q] = min_{x,y} rows[q,x] + d[x,y] + rowt[q,y].

    Shapes: rows [q, k1], d [k1, k2], rowt [q, k2] -> out [q].
    Pads every axis to tile multiples with +inf (absorbing element).
    """
    q, k1 = rows.shape
    k1b, k2 = d.shape
    qb, k2b = rowt.shape
    assert k1 == k1b and k2 == k2b and q == qb, (rows.shape, d.shape,
                                                rowt.shape)
    assert bk2 % _LANES == 0, bk2
    qp = -(-q // bq) * bq
    k1p = -(-k1 // bk1) * bk1
    k2p = -(-k2 // bk2) * bk2
    rows_p = jnp.full((qp, k1p), jnp.inf, rows.dtype).at[:q, :k1].set(rows)
    d_p = jnp.full((k1p, k2p), jnp.inf, d.dtype).at[:k1, :k2].set(d)
    rowt_p = jnp.full((qp, k2p), jnp.inf, rowt.dtype).at[:q, :k2].set(rowt)
    grid = (qp // bq, k2p // bk2, k1p // bk1)
    part = pl.pallas_call(
        _twoside_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bk1), lambda qi, yi, xi: (qi, xi)),
            pl.BlockSpec((bk1, bk2), lambda qi, yi, xi: (xi, yi)),
            pl.BlockSpec((bq, bk2), lambda qi, yi, xi: (qi, yi)),
        ],
        out_specs=pl.BlockSpec((bq, _LANES), lambda qi, yi, xi: (qi, 0)),
        out_shape=jax.ShapeDtypeStruct((qp, _LANES), rows.dtype),
        interpret=interpret,
    )(rows_p, d_p, rowt_p)
    return jnp.min(part, axis=1)[:q]


#: the grouped contraction's tiles: q tiles of GROUPED_BQ rows, closure
#: blocks of GROUPED_BK1 x GROUPED_BK2 (clipped to the closure width).
#: Small q tiles waste few rows on each group's last tile; large closure
#: blocks keep the grid's per-step overhead, which dead tiles pay too,
#: small beside a step's work.  Chosen by a sweep on a v5e at m2 =
#: 1,024, 2,048 and 4,096, where it was within noise of the best
#: everywhere (PERF.md §6).
GROUPED_BQ, GROUPED_BK1, GROUPED_BK2 = 16, 256, 2048


def _grouped_kernel(tile_group_ref, live_ref, rows_ref, d_ref, rowt_ref,
                    out_ref):
    """_twoside_kernel on the live q tiles; a tile at or past the live
    count computes nothing (its blocks repeat the last live tile's, so
    it starts no DMA either)."""
    del tile_group_ref
    yi, xi = pl.program_id(1), pl.program_id(2)

    @pl.when(pl.program_id(0) < live_ref[0])
    def _live():
        _twoside_step(yi, xi, rows_ref, d_ref, rowt_ref, out_ref)


@functools.partial(jax.jit, static_argnames=("bq", "bk1", "bk2",
                                             "interpret"))
def minplus_twoside_grouped_pallas(rows: jax.Array, d: jax.Array,
                                   rowt: jax.Array, tile_group: jax.Array,
                                   live: jax.Array, *,
                                   bq: int = GROUPED_BQ,
                                   bk1: int = GROUPED_BK1,
                                   bk2: int = GROUPED_BK2,
                                   interpret: bool = False) -> jax.Array:
    """out[r] = min_{x,y} rows[r,x] + d[tile_group[r // bq], x, y]
    + rowt[r,y] for r < live * bq, +inf past it.

    Shapes: rows, rowt [T * bq, m2]; d [G, m2, m2]; tile_group int32
    [T] (values in [0, G)); live int32 scalar, the live tiles, which
    come first -> out [T * bq].  bk1, bk2 are clipped to m2 and must
    divide it.  The grid is (T, y blocks, x blocks); tile_group and
    live are prefetched into scalar memory, where the closure's index
    map reads the tile's block of d, so d is never copied or padded.
    """
    r, k1 = rows.shape
    _, k1b, k2 = d.shape
    assert r % bq == 0 and rowt.shape == (r, k2) and k1 == k1b, (
        rows.shape, d.shape, rowt.shape, bq)
    bk1, bk2 = min(bk1, k1), min(bk2, k2)
    assert k1 % bk1 == 0 and k2 % bk2 == 0, (k1, k2, bk1, bk2)
    lanes = min(_LANES, bk2)
    ny, nx = k2 // bk2, k1 // bk1

    def block(qi, yi, xi, nl):
        """A live step's own (q, y, x) block; a dead step repeats the
        last step of the last live tile."""
        dead = qi >= nl[0]
        return (jnp.where(dead, jnp.maximum(nl[0] - 1, 0), qi),
                jnp.where(dead, ny - 1, yi), jnp.where(dead, nx - 1, xi))

    def rows_map(qi, yi, xi, tg, nl):
        q, _, x = block(qi, yi, xi, nl)
        return q, x

    def d_map(qi, yi, xi, tg, nl):
        q, y, x = block(qi, yi, xi, nl)
        return tg[q], x, y

    def rowt_map(qi, yi, xi, tg, nl):
        q, y, _ = block(qi, yi, xi, nl)
        return q, y

    def out_map(qi, yi, xi, tg, nl):
        return block(qi, yi, xi, nl)[0], 0

    part = pl.pallas_call(
        _grouped_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(r // bq, ny, nx),
            in_specs=[
                pl.BlockSpec((bq, bk1), rows_map),
                pl.BlockSpec((pl.Squeezed(), bk1, bk2), d_map),
                pl.BlockSpec((bq, bk2), rowt_map),
            ],
            out_specs=pl.BlockSpec((bq, lanes), out_map),
        ),
        out_shape=jax.ShapeDtypeStruct((r, lanes), rows.dtype),
        interpret=interpret,
    )(tile_group.astype(jnp.int32), jnp.reshape(live, (1,)).astype(
        jnp.int32), rows, d, rowt)
    live_row = jnp.arange(r, dtype=jnp.int32) < live * bq
    return jnp.where(live_row, jnp.min(part, axis=1), jnp.inf)


def _twoside_argmin_kernel(rows_ref, d_ref, rowt_ref, out_ref, wit_ref,
                           *, k2_stride: int):
    """Witness-carrying variant of _twoside_kernel: alongside the lane
    partial minima, carry the winning (x, y) pair packed as
    x * k2_stride + y (global padded coordinates, int32).  Ties resolve
    to the smallest packed witness, deterministically."""
    yi = pl.program_id(1)
    xi = pl.program_id(2)

    @pl.when((yi == 0) & (xi == 0))
    def _init():
        out_ref[...] = jnp.full_like(out_ref, jnp.inf)
        wit_ref[...] = jnp.full_like(wit_ref, -1)

    rows = rows_ref[...]          # [bq, bk1]
    d = d_ref[...]                # [bk1, bk2]
    rowt = rowt_ref[...]          # [bq, bk2]
    bk1 = rows.shape[1]
    bq, bk2 = rowt.shape
    # one x at a time, as in minplus_tile; the strict < keeps the
    # smallest x among equal sums
    acc = jnp.full((bq, bk2), jnp.inf, rows.dtype)
    accx = jnp.full((bq, bk2), -1, jnp.int32)
    for x in range(bk1):
        cand = rows[:, x:x + 1] + d[x:x + 1, :]
        better = cand < acc
        acc = jnp.where(better, cand, acc)
        accx = jnp.where(better, x, accx)
    tmp = acc + rowt              # [bq, bk2]
    # pack the global witness per (q, y) cell, then fold y to 128 lanes
    # keeping value/witness aligned (min-of-where instead of argmin so
    # every op stays lane-shaped)
    y_glob = yi * bk2 + jax.lax.broadcasted_iota(jnp.int32, tmp.shape, 1)
    wxy = (xi * bk1 + accx) * k2_stride + y_glob
    g = bk2 // _LANES
    tmp_r = tmp.reshape(bq, g, _LANES)
    wxy_r = wxy.reshape(bq, g, _LANES)
    part = jnp.min(tmp_r, axis=1)                        # [bq, 128]
    hit = tmp_r == part[:, None, :]
    pwit = jnp.min(jnp.where(hit, wxy_r, jnp.iinfo(jnp.int32).max),
                   axis=1)
    cur = out_ref[...]
    cur_wit = wit_ref[...]
    better = part < cur
    out_ref[...] = jnp.where(better, part, cur)
    wit_ref[...] = jnp.where(better, pwit, cur_wit)


@functools.partial(jax.jit, static_argnames=("bq", "bk1", "bk2",
                                             "interpret"))
def minplus_twoside_argmin_pallas(rows: jax.Array, d: jax.Array,
                                  rowt: jax.Array, *, bq: int = 128,
                                  bk1: int = 128, bk2: int = 128,
                                  interpret: bool = False
                                  ) -> tuple[jax.Array, jax.Array,
                                             jax.Array]:
    """Witness-returning twoside contraction: (out, wx, wy) with
    out[q] = rows[q, wx[q]] + d[wx[q], wy[q]] + rowt[q, wy[q]] for every
    finite out[q]; wx = wy = -1 where out[q] is +inf.  Same tiling and
    revisiting pattern as minplus_twoside_pallas; padded cells are +inf
    so they can never win a witness."""
    q, k1 = rows.shape
    k1b, k2 = d.shape
    qb, k2b = rowt.shape
    assert k1 == k1b and k2 == k2b and q == qb, (rows.shape, d.shape,
                                                rowt.shape)
    assert bk2 % _LANES == 0, bk2
    qp = -(-q // bq) * bq
    k1p = -(-k1 // bk1) * bk1
    k2p = -(-k2 // bk2) * bk2
    rows_p = jnp.full((qp, k1p), jnp.inf, rows.dtype).at[:q, :k1].set(rows)
    d_p = jnp.full((k1p, k2p), jnp.inf, d.dtype).at[:k1, :k2].set(d)
    rowt_p = jnp.full((qp, k2p), jnp.inf, rowt.dtype).at[:q, :k2].set(rowt)
    grid = (qp // bq, k2p // bk2, k1p // bk1)
    part, pwit = pl.pallas_call(
        functools.partial(_twoside_argmin_kernel, k2_stride=k2p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bk1), lambda qi, yi, xi: (qi, xi)),
            pl.BlockSpec((bk1, bk2), lambda qi, yi, xi: (xi, yi)),
            pl.BlockSpec((bq, bk2), lambda qi, yi, xi: (qi, yi)),
        ],
        out_specs=[pl.BlockSpec((bq, _LANES), lambda qi, yi, xi: (qi, 0)),
                   pl.BlockSpec((bq, _LANES), lambda qi, yi, xi: (qi, 0))],
        out_shape=[jax.ShapeDtypeStruct((qp, _LANES), rows.dtype),
                   jax.ShapeDtypeStruct((qp, _LANES), jnp.int32)],
        interpret=interpret,
    )(rows_p, d_p, rowt_p)
    out = jnp.min(part, axis=1)
    hit = part == out[:, None]
    wit = jnp.min(jnp.where(hit, pwit, jnp.iinfo(jnp.int32).max), axis=1)
    fin = jnp.isfinite(out)
    wx = jnp.where(fin, wit // k2p, -1)
    wy = jnp.where(fin, wit % k2p, -1)
    return out[:q], wx[:q], wy[:q]
