"""Blocked Floyd-Warshall APSP Pallas kernels.

Three entry points:

  * ``fw_batch_pallas``  — grid over a batch of dense matrices
    (DISLAND fragments, pieces and overlay groups, each padded to a
    common size); the whole [nf, nf] tile lives in VMEM and each pivot
    sweeps it in place, row chunk by row chunk.

  * ``fw_batch_next_pallas`` — the same, additionally carrying the
    first-hop successor matrix (int32) for exact path reconstruction
    (DESIGN.md §10); distances come out bit-identical.

  * ``fw_blocked``       — classic 3-phase blocked FW for one larger
    matrix: phase 1 = diagonal-block FW (this kernel), phases 2/3 =
    min-plus accumulate tiles (minplus.minplus_accum_pallas).  Used for
    the SUPER-graph boundary x boundary matrix.

Float32, +inf = unreachable; diagonal forced to 0 on entry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .minplus import minplus_accum_pallas
from .ref import fw_next_init


#: elements per row chunk of the in-place pivot sweep (16 f32 vregs)
_CHUNK_ELEMS = 16 * 1024
#: scoped-VMEM limit of the whole-tile kernels (default 16 MiB).  Each
#: operand's [n, n] block is held twice, one copy in scoped VMEM: the
#: witness kernel's four operands take 16 MiB scoped (32 MiB in all) at
#: n = 1024, road64k's group closures, so the default has no headroom.
#: At n = 2048 they would take 128 MiB in all, the whole VMEM of a v5e
#: core, and do not compile.
_VMEM = pltpu.CompilerParams(vmem_limit_bytes=96 << 20)


def _row_chunk(n: int) -> int:
    """Rows per step of the pivot sweep: the largest multiple of 8 that
    divides n and keeps a chunk within _CHUNK_ELEMS (the whole tile
    when n is not a multiple of 8)."""
    if n % 8:
        return n
    return max(c for c in range(8, n + 1, 8)
               if n % c == 0 and (c == 8 or c * n <= _CHUNK_ELEMS))


def _col(blk: jax.Array, k, fill) -> jax.Array:
    """blk[:, k] as a [rows, 1] column.  Mosaic cannot slice a value's
    lane axis at a dynamic offset, so this is a masked lane-min: one
    lane survives the mask, so the result is that entry, bit for bit."""
    lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    return jnp.min(jnp.where(lane == k, blk, fill), axis=1, keepdims=True)


def _fw_block_kernel(d_ref, o_ref):
    """Floyd-Warshall on one [nf, nf] tile (leading batch of 1), swept in
    place over the output block.  Pivot k reads row k (a sublane slice)
    before the sweep, and each row chunk's column k before that chunk is
    rewritten, so every candidate uses pre-pivot values exactly as the
    functional recurrence does."""
    o_ref[...] = d_ref[...]
    n = o_ref.shape[1]
    b = _row_chunk(n)

    def pivot(k, carry):
        row = o_ref[0, pl.ds(k, 1), :]                   # [1, n]

        def chunk(c, carry):
            rs = pl.ds(pl.multiple_of(c * b, b), b)
            blk = o_ref[0, rs, :]
            o_ref[0, rs, :] = jnp.minimum(blk, _col(blk, k, jnp.inf) + row)
            return carry

        return jax.lax.fori_loop(0, n // b, chunk, carry)

    jax.lax.fori_loop(0, n, pivot, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fw_batch_pallas(d: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Batched APSP: d[b, n, n] -> shortest distances per batch entry."""
    b, n, n2 = d.shape
    assert n == n2
    # zero the diagonals (distance to self)
    eye = jnp.eye(n, dtype=bool)
    d = jnp.where(eye[None], 0.0, d)
    return pl.pallas_call(
        _fw_block_kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, n, n), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, n), d.dtype),
        compiler_params=_VMEM,
        interpret=interpret,
    )(d)


def _fw_next_block_kernel(d_ref, n_ref, do_ref, no_ref):
    """Witness-carrying FW on one [nf, nf] tile: alongside the distance
    recurrence, carry nxt[i, j] = first hop of a shortest i -> j path
    (int32, -1 = unreachable/diagonal).  Same strict-improvement update
    in the same pivot order and the same in-place sweep as
    _fw_block_kernel, so the distance output is bit-identical — path
    tables can ride along any build without perturbing the distances
    the rest of the index is tested against."""
    do_ref[...] = d_ref[...]
    no_ref[...] = n_ref[...]
    n = do_ref.shape[1]
    b = _row_chunk(n)
    no_hop = jnp.iinfo(jnp.int32).max

    def pivot(k, carry):
        row = do_ref[0, pl.ds(k, 1), :]                  # [1, n]

        def chunk(c, carry):
            rs = pl.ds(pl.multiple_of(c * b, b), b)
            blk = do_ref[0, rs, :]
            nblk = no_ref[0, rs, :]
            cand = _col(blk, k, jnp.inf) + row
            better = cand < blk
            do_ref[0, rs, :] = jnp.where(better, cand, blk)
            no_ref[0, rs, :] = jnp.where(better, _col(nblk, k, no_hop),
                                         nblk)
            return carry

        return jax.lax.fori_loop(0, n // b, chunk, carry)

    jax.lax.fori_loop(0, n, pivot, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fw_batch_next_pallas(d: jax.Array, *, interpret: bool = False
                         ) -> tuple[jax.Array, jax.Array]:
    """Batched witness APSP: d[b, n, n] -> (dist, nxt) per batch entry."""
    b, n, n2 = d.shape
    assert n == n2
    d0, nxt0 = fw_next_init(d)
    return pl.pallas_call(
        _fw_next_block_kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, n, n), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, n, n), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, n, n), d.dtype),
                   jax.ShapeDtypeStruct((b, n, n), jnp.int32)],
        compiler_params=_VMEM,
        interpret=interpret,
    )(d0, nxt0)


def _fw_diag(d_kk: jax.Array, interpret: bool) -> jax.Array:
    return fw_batch_pallas(d_kk[None], interpret=interpret)[0]


def fw_blocked(d: jax.Array, *, block: int = 128,
               interpret: bool = False) -> jax.Array:
    """3-phase blocked Floyd-Warshall for one [n, n] matrix.

    Pads to a block multiple with +inf.  Per k-block:
      phase 1: FW on the diagonal block D[kk]
      phase 2: D[k, *] = min(D[k, *], D[kk] (x) D[k, *]);
               D[*, k] = min(D[*, k], D[*, k] (x) D[kk])
      phase 3: D = min(D, D[*, k] (x) D[k, *])
    """
    n = d.shape[0]
    np_ = -(-n // block) * block
    pad = jnp.full((np_, np_), jnp.inf, d.dtype)
    pad = pad.at[:n, :n].set(d)
    eye = jnp.eye(np_, dtype=bool)
    pad = jnp.where(eye, 0.0, pad)
    nb = np_ // block
    for kb in range(nb):
        s = kb * block
        dkk = _fw_diag(jax.lax.dynamic_slice(pad, (s, s), (block, block)),
                       interpret)
        pad = jax.lax.dynamic_update_slice(pad, dkk, (s, s))
        row = jax.lax.dynamic_slice(pad, (s, 0), (block, np_))
        row = minplus_accum_pallas(row, dkk, row, bm=block, bn=block,
                                   bk=block, interpret=interpret)
        pad = jax.lax.dynamic_update_slice(pad, row, (s, 0))
        col = jax.lax.dynamic_slice(pad, (0, s), (np_, block))
        col = minplus_accum_pallas(col, col, dkk, bm=block, bn=block,
                                   bk=block, interpret=interpret)
        pad = jax.lax.dynamic_update_slice(pad, col, (0, s))
        pad = minplus_accum_pallas(pad, col, row, bm=block, bn=block,
                                   bk=block, interpret=interpret)
    return pad[:n, :n]
