"""Tropical (min,+) GEMM Pallas kernel — the DISLAND combine step.

C[i, j] = min_k A[i, k] + B[k, j]

This is the query-time workhorse of the device engine: distances
node->boundary (A) combined with boundary->boundary SUPER distances (B)
is exactly a min-plus product (GraphBLAS shortest-distance semiring).

TPU mapping: (min,+) has no MXU form, so this is VPU work; tiles are
(8,128)-lane aligned and sized so A-tile + B-tile + C-tile stay well
inside the 16 MiB default scoped VMEM.  Within a tile the contraction
runs one k at a time (``minplus_tile``): a column of A broadcast along
lanes plus a row of B broadcast along sublanes.  The K grid axis is
innermost and sequential on TPU, so the output tile is min-accumulated
across K invocations (revisiting pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INIT = jnp.inf


def minplus_tile(a: jax.Array, b: jax.Array, acc: jax.Array) -> jax.Array:
    """min(acc, a (x) b) for one [m, bk] x [bk, n] tile pair, one
    contraction index at a time.  Column k of ``a`` is a static lane
    slice and row k of ``b`` a static sublane slice, which Mosaic
    lowers (it has no dynamic slice of a loaded value).  A min is
    order-free, so the result is bit-identical to any chunking of the
    same sums."""
    for k in range(a.shape[1]):
        acc = jnp.minimum(acc, a[:, k:k + 1] + b[k:k + 1, :])
    return acc


def _minplus_kernel(a_ref, b_ref, c_ref):
    """One (bm x bn) output tile; min-accumulate over the K grid axis."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        c_ref[...] = jnp.full_like(c_ref, _NEG_INIT)

    c_ref[...] = minplus_tile(a_ref[...], b_ref[...], c_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "interpret"))
def minplus_pallas(a: jax.Array, b: jax.Array, *, bm: int = 128,
                   bn: int = 128, bk: int = 128,
                   interpret: bool = False) -> jax.Array:
    """Tropical GEMM via Pallas; pads to tile multiples with +inf."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    kp = -(-k // bk) * bk
    a_p = jnp.full((mp, kp), jnp.inf, a.dtype).at[:m, :k].set(a)
    b_p = jnp.full((kp, np_), jnp.inf, b.dtype).at[:k, :n].set(b)
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        _minplus_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), a.dtype),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]


def _minplus_accum_kernel(c_in_ref, a_ref, b_ref, c_ref):
    """C = min(C_in, A (x) B) — used by the blocked Floyd-Warshall
    phases 2/3, where the output tile must fold into existing distances."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        c_ref[...] = c_in_ref[...]

    c_ref[...] = minplus_tile(a_ref[...], b_ref[...], c_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "interpret"))
def minplus_accum_pallas(c: jax.Array, a: jax.Array, b: jax.Array, *,
                         bm: int = 128, bn: int = 128, bk: int = 128,
                         interpret: bool = False) -> jax.Array:
    """min(C, A (x) B) with +inf padding; shapes C[m,n] A[m,k] B[k,n]."""
    m, k = a.shape
    _, n = b.shape
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    kp = -(-k // bk) * bk
    a_p = jnp.full((mp, kp), jnp.inf, a.dtype).at[:m, :k].set(a)
    b_p = jnp.full((kp, np_), jnp.inf, b.dtype).at[:k, :n].set(b)
    c_p = jnp.full((mp, np_), jnp.inf, c.dtype).at[:m, :n].set(c)
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        _minplus_accum_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), c.dtype),
        interpret=interpret,
    )(c_p, a_p, b_p)
    return out[:m, :n]
