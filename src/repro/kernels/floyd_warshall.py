"""Floyd-Warshall APSP Pallas kernels.

Entry points:

  * ``fw_batch_pallas``  — grid over a batch of dense matrices; the
    whole [n, n] tile lives in VMEM and each pivot sweeps it in place,
    row chunk by row chunk (the top closure's diagonal blocks).

  * ``fw_batch_next_blocked`` — the witness-carrying closure for a
    batch of tiles of any width (DISLAND fragments, pieces and overlay
    groups, each padded to a common size), also carrying the first-hop
    successor matrix (int32) for exact path reconstruction (DESIGN.md
    §10).  Per pivot block: the whole-tile witness kernel on the
    diagonal block, then the row and column panels and the remainder as
    (min,+) accumulates that carry the first hop
    (``minplus_next_pallas``).  A tile no wider than one block is the
    diagonal kernel alone.  Only [block, block] and [tile, tile] blocks
    are ever VMEM-resident, so group closures at m2 = 2048 and 4096
    close as 1024 does.

  * ``fw_blocked``       — classic 3-phase blocked FW for one larger
    matrix: phase 1 = diagonal-block FW (this kernel), phases 2/3 =
    min-plus accumulate tiles (minplus.minplus_accum_pallas).  Used for
    the top boundary x boundary matrix.

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
#: n = 1024, so the default has no headroom; at n = 2048 they would
#: take 128 MiB in all, the whole VMEM of a v5e core, and do not
#: compile.  The witness closure therefore runs the whole-tile kernel
#: only on FW_BLOCK-wide diagonal blocks.
_VMEM = pltpu.CompilerParams(vmem_limit_bytes=96 << 20)
#: pivot block of the witness closure (a tile no wider closes whole),
#: and the (min,+) tile of its panels.  On a v5e the blocked closure of
#: [6, 1024, 1024] ran in 0.047 s against the whole tile's 0.074 s
FW_BLOCK = 256
_TILE = 128


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


def _fw_next_tiles(d0: jax.Array, nxt0: jax.Array,
                   interpret: bool) -> tuple[jax.Array, jax.Array]:
    """The whole-tile witness kernel over a batch of initialised
    (distance, first hop) tiles."""
    b, n, _ = d0.shape
    spec = pl.BlockSpec((1, n, n), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _fw_next_block_kernel,
        grid=(b,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((b, n, n), d0.dtype),
                   jax.ShapeDtypeStruct((b, n, n), jnp.int32)],
        compiler_params=_VMEM,
        interpret=interpret,
    )(d0, nxt0)


def _minplus_next_kernel(c_ref, nc_ref, a_ref, na_ref, b_ref, o_ref,
                         no_ref):
    """One output tile of min(C, A (x) B) that carries a first hop: where
    A[i, k] + B[k, j] strictly improves the running minimum, the tile's
    hop becomes NA[i, k], the first hop of the path A[i, k] stands for.
    The strict < keeps the smallest k among equal sums; the K grid axis
    is innermost and sequential, so that holds across K tiles too."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = c_ref[...]
        no_ref[...] = nc_ref[...]

    a, na, b = a_ref[0], na_ref[0], b_ref[0]
    acc, nacc = o_ref[0], no_ref[0]
    for k in range(a.shape[1]):
        cand = a[:, k:k + 1] + b[k:k + 1, :]
        better = cand < acc
        acc = jnp.where(better, cand, acc)
        nacc = jnp.where(better, na[:, k:k + 1], nacc)
    o_ref[0] = acc
    no_ref[0] = nacc


def minplus_next_pallas(c: jax.Array, nc: jax.Array, a: jax.Array,
                        na: jax.Array, b: jax.Array, *,
                        tile: int = _TILE, interpret: bool = False
                        ) -> tuple[jax.Array, jax.Array]:
    """Batched witness (min,+) accumulate: (min(C, A (x) B), its first
    hops), with C, NC [B, m, n], A, NA [B, m, k], B [B, k, n].  Every
    axis is a multiple of its tile (min(tile, axis))."""
    bsz, m, n = c.shape
    kdim = a.shape[2]
    tm, tn, tk = min(tile, m), min(tile, n), min(tile, kdim)
    out_spec = pl.BlockSpec((1, tm, tn), lambda g, i, j, k: (g, i, j))
    a_spec = pl.BlockSpec((1, tm, tk), lambda g, i, j, k: (g, i, k))
    return pl.pallas_call(
        _minplus_next_kernel,
        grid=(bsz, m // tm, n // tn, kdim // tk),
        in_specs=[out_spec, out_spec, a_spec, a_spec,
                  pl.BlockSpec((1, tk, tn), lambda g, i, j, k: (g, k, j))],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(c.shape, jnp.int32)],
        # each output tile is read from C before it is written back, so
        # the closure updates its carry in place
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(c, nc, a, na, b)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fw_batch_next_blocked(d: jax.Array, *, block: int = FW_BLOCK,
                          interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """Batched witness APSP: d[b, n, n] -> (dist, nxt) per batch entry,
    nxt[b, i, j] the first hop of a shortest i -> j path (-1 on the
    diagonal and for unreachable pairs).

    A tile no wider than ``block`` is one diagonal block: the whole-tile
    witness kernel closes it, and nothing else runs.  Wider tiles pad n
    to a block multiple with +inf (isolated nodes, hop -1).  Per pivot
    block kb, with P its index range:
      phase 1: the whole-tile witness kernel on D[P, P];
      phase 2: D[P, :] = min(D[P, :], D[P, P] (x) D[P, :]), hops from
               nxt[P, P]; D[:, P] = min(D[:, P], D[:, P] (x) D[P, P]),
               hops from nxt[:, P];
      phase 3: D = min(D, D[:, P] (x) D[P, :]), hops from nxt[:, P].
    Every update takes the first hop of the path it extends, and a
    distance only falls to a real path's length, so once the distances
    are exact each hop h of (i, j) has adj[i, h] + D[h, j] == D[i, j]:
    a valid first hop of a shortest path, though not always the one a
    whole-tile sweep picks among ties.  Distances are bit-identical to
    fw_batch: every sum is exact in f32 for integer weights below 2**24,
    and the closure does not depend on the order of the sums."""
    bsz, n, _ = d.shape
    if n <= block:
        return _fw_next_tiles(*fw_next_init(d), interpret)
    n_p = -(-n // block) * block
    d0, nxt0 = fw_next_init(
        jnp.pad(d, ((0, 0), (0, n_p - n), (0, n_p - n)),
                constant_values=jnp.inf))
    tile = min(_TILE, block)

    def mp(c, nc, a, na, b):
        return minplus_next_pallas(c, nc, a, na, b, tile=tile,
                                   interpret=interpret)

    def pivot(kb, carry):
        dd, nn = carry
        s = kb * block

        def cut(x, at, shape):
            return jax.lax.dynamic_slice(x, at, shape)

        def put(dd, nn, x, nx, at):
            return (jax.lax.dynamic_update_slice(dd, x, at),
                    jax.lax.dynamic_update_slice(nn, nx, at))

        at, shape = (0, s, s), (bsz, block, block)
        dkk, nkk = _fw_next_tiles(cut(dd, at, shape), cut(nn, at, shape),
                                  interpret)
        dd, nn = put(dd, nn, dkk, nkk, at)
        at, shape = (0, s, 0), (bsz, block, n_p)
        row, nrow = cut(dd, at, shape), cut(nn, at, shape)
        row, nrow = mp(row, nrow, dkk, nkk, row)
        dd, nn = put(dd, nn, row, nrow, at)
        at, shape = (0, 0, s), (bsz, n_p, block)
        col, ncol = cut(dd, at, shape), cut(nn, at, shape)
        col, ncol = mp(col, ncol, col, ncol, dkk)
        dd, nn = put(dd, nn, col, ncol, at)
        # with the closed panels in place, no pivot of P strictly
        # improves an entry of them (nor takes a diagonal's hop -1)
        return mp(dd, nn, col, ncol, row)

    dd, nn = jax.lax.fori_loop(0, n_p // block, pivot, (d0, nxt0))
    return dd[:, :n, :n], nn[:, :n, :n]


def _fw_diag(d_kk: jax.Array, interpret: bool) -> jax.Array:
    return fw_batch_pallas(d_kk[None], interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fw_blocked(d: jax.Array, *, block: int = 128,
               interpret: bool = False) -> jax.Array:
    """3-phase blocked Floyd-Warshall for one [n, n] matrix, one program
    (a loop over pivot blocks, updated in place).

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

    def pivot(kb, pad):
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
        return minplus_accum_pallas(pad, col, row, bm=block, bn=block,
                                    bk=block, interpret=interpret)

    return jax.lax.fori_loop(0, np_ // block, pivot, pad)[:n, :n]
