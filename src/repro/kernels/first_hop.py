"""Canonical first hops from an adjacency and its exact closure.

    out[i, j] = the smallest k with adj[i, k] finite and
                adj[i, k] + dist[k, j] == target[i, j]   (-1: none)

The top closure's witness table (``hierarchy.l2_stage``): a pure
function of (adjacency, closed distances), so the scratch build and
every refresh path derive the same table whatever produced the
distances.  The caller puts +inf on the diagonal of ``adj`` (k == i
never witnesses) and masks unreachable targets.

TPU mapping (VPU work, like every (min,+) kernel here): grid
(i tiles, j tiles, k tiles) with k innermost and sequential.  The k
tiles are visited from the last to the first and each tile's columns
from its last to its first, so the last hit written is the smallest k:
three VPU operations per (i, j, k), with no running minimum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _first_hop_kernel(a_ref, d_ref, t_ref, o_ref, *, nk: int):
    kt = pl.program_id(2)

    @pl.when(kt == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, -1, jnp.int32)

    a, d, tgt = a_ref[...], d_ref[...], t_ref[...]
    tk = a.shape[1]
    k0 = (nk - 1 - kt) * tk
    acc = o_ref[...]
    for k in reversed(range(tk)):
        acc = jnp.where(a[:, k:k + 1] + d[k:k + 1, :] == tgt, k0 + k, acc)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def first_hop_pallas(adj: jax.Array, dist: jax.Array, target: jax.Array,
                     *, tile: int = 128, interpret: bool = False
                     ) -> jax.Array:
    """adj [r, n], dist [n, c], target [r, c] -> int32 [r, c]; every
    axis padded to a tile multiple with +inf (a padded k never hits a
    finite target; padded cells are cut off)."""
    r, n = adj.shape
    c = dist.shape[1]
    rp, np_, cp = (-(-x // tile) * tile for x in (r, n, c))

    def pad(x, rows, cols):
        return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])),
                       constant_values=jnp.inf)

    nk = np_ // tile
    out = pl.pallas_call(
        functools.partial(_first_hop_kernel, nk=nk),
        grid=(rp // tile, cp // tile, nk),
        in_specs=[
            pl.BlockSpec((tile, tile), lambda i, j, k: (i, nk - 1 - k)),
            pl.BlockSpec((tile, tile), lambda i, j, k: (nk - 1 - k, j)),
            pl.BlockSpec((tile, tile), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((tile, tile), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, cp), jnp.int32),
        interpret=interpret,
    )(pad(adj, rp, np_), pad(dist, np_, cp), pad(target, rp, cp))
    return out[:r, :c]
