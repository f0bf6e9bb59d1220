"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles, in
interpret mode (force='pallas'), plus semiring property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dijkstra
from repro.core.graph import random_graph
from repro.kernels import floyd_warshall as fw
from repro.kernels import ops, ref


def _rand(shape, rng, inf_frac=0.2, dtype=np.float32):
    x = rng.random(shape).astype(np.float32) * 10
    x[rng.random(shape) < inf_frac] = np.inf
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("m,k,n", [(1, 1, 1), (5, 7, 3), (64, 200, 64),
                                   (130, 128, 257), (128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_minplus_matches_ref(m, k, n, dtype):
    rng = np.random.default_rng(m * 1000 + k + n)
    a = _rand((m, k), rng, dtype=dtype)
    b = _rand((k, n), rng, dtype=dtype)
    got = ops.minplus(a, b, bm=8, bn=128, bk=8, force="pallas")
    want = ref.minplus_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("m,k,n", [(5, 7, 3), (64, 100, 33)])
def test_minplus_accum_matches_ref(m, k, n):
    rng = np.random.default_rng(0)
    a = _rand((m, k), rng)
    b = _rand((k, n), rng)
    c = _rand((m, n), rng, inf_frac=0.5)
    got = ops.minplus_accum(c, a, b, bm=8, bn=128, bk=8, force="pallas")
    np.testing.assert_allclose(got, ref.minplus_accum_ref(c, a, b),
                               rtol=1e-6)


@pytest.mark.parametrize("q,k1,k2", [(1, 1, 1), (5, 7, 3), (37, 130, 201),
                                     (128, 128, 128), (100, 300, 129)])
@pytest.mark.parametrize("force", ["ref", "pallas"])
def test_minplus_twoside_matches_naive(q, k1, k2, force):
    """Fused two-sided contraction vs the direct [q,k1,k2] cube, on
    shapes that are deliberately NOT tile multiples."""
    rng = np.random.default_rng(q * 1000 + k1 + k2)
    rows = _rand((q, k1), rng)
    d = _rand((k1, k2), rng)
    rowt = _rand((q, k2), rng)
    naive = np.min(np.asarray(rows)[:, :, None] + np.asarray(d)[None]
                   + np.asarray(rowt)[:, None, :], axis=(1, 2))
    got = ops.minplus_twoside(rows, d, rowt, bq=8, bk1=16, bk2=128,
                              force=force)
    np.testing.assert_allclose(np.asarray(got), naive, rtol=1e-5)


@pytest.mark.parametrize("q,k1,k2", [(9, 70, 53), (130, 257, 139),
                                     (1, 333, 7)])
def test_minplus_twoside_default_tiles_odd_shapes(q, k1, k2):
    """Padding correctness at the DEFAULT tile sizes (bq=bk1=bk2=128):
    mb/S shapes that are not multiples of any tile dimension — the
    shapes the serve/refresh path actually produces, since mb is padded
    to 8 (not 128) and S+1 is arbitrary.  The +inf padding is the
    semiring's absorbing element, so fillers must never win a min."""
    rng = np.random.default_rng(q * 7919 + k1 * 31 + k2)
    rows = _rand((q, k1), rng)
    d = _rand((k1, k2), rng)
    rowt = _rand((q, k2), rng)
    naive = np.min(np.asarray(rows)[:, :, None] + np.asarray(d)[None]
                   + np.asarray(rowt)[:, None, :], axis=(1, 2))
    for force in ("ref", "pallas"):
        got = ops.minplus_twoside(rows, d, rowt, force=force)
        np.testing.assert_allclose(np.asarray(got), naive, rtol=1e-5)
        assert not np.isnan(np.asarray(got)).any()


def test_minplus_twoside_all_inf():
    """Disconnected case: every path +inf stays +inf (no NaN from
    inf-inf arithmetic in the padding)."""
    rows = jnp.full((4, 10), jnp.inf)
    d = jnp.full((10, 6), jnp.inf)
    rowt = jnp.full((4, 6), jnp.inf)
    for force in ("ref", "pallas"):
        got = np.asarray(ops.minplus_twoside(rows, d, rowt, bq=8, bk1=16,
                                             bk2=128, force=force))
        assert np.isinf(got).all() and not np.isnan(got).any()


@pytest.mark.parametrize("counts,extra", [
    ((3, 21, 9), 1),        # uneven group sizes, one dead tile
    ((5, 0, 7), 1),         # an empty group
    ((0, 30, 0), 0),        # every row in one group
    ((0, 0, 0), 2),         # no live tile at all: all +inf
    ((16, 32, 16), 0),      # n_g an exact multiple of bq
    ((4, 9, 2), 3),         # finite rows past the live count
])
def test_minplus_twoside_grouped(counts, extra):
    """The grouped contraction (kernel in interpret mode, at the
    default blocks and at 128 x 128 blocks, and the jnp reference) vs a
    direct NumPy cube per row: live tiles answer against their own
    group's closure, padding rows of a live tile and every row past the
    live count read +inf, whatever those rows hold."""
    from repro.kernels import minplus_twoside as ts

    bq, m2, groups = ops.GROUPED_TILE_ROWS, 256, len(counts)
    rng = np.random.default_rng(sum(counts) * 31 + extra)
    tiles = [-(-n // bq) for n in counts]
    live = sum(tiles)
    tile_group = np.repeat(np.arange(groups), tiles).astype(np.int32)
    tile_group = np.concatenate([tile_group, np.full(extra, groups - 1,
                                                     np.int32)])
    n_rows = (live + extra) * bq
    d, rows, rowt = (np.array(_rand(shape, rng, inf_frac=0.3))
                     for shape in ((groups + 1, m2, m2), (n_rows, m2),
                                   (n_rows, m2)))
    filled = np.zeros(n_rows, bool)
    start = 0
    for n, k in zip(counts, tiles):
        filled[start:start + n] = True
        start += k * bq
    filled[live * bq:] = True          # dead tiles hold finite rows too
    rows[~filled] = np.inf
    rowt[~filled] = np.inf
    want = np.full(n_rows, np.inf, np.float32)
    for r in range(live * bq):
        g = tile_group[r // bq]
        want[r] = np.min(rows[r][:, None] + d[g] + rowt[r][None, :])
    args = [jnp.asarray(x) for x in (rows, d, rowt, tile_group)]
    nl = jnp.int32(live)
    for force in ("ref", "pallas"):
        got = ops.minplus_twoside_grouped(*args, nl, force=force)
        np.testing.assert_array_equal(np.asarray(got), want)
    got = ts.minplus_twoside_grouped_pallas(*args, nl, bk1=128, bk2=128,
                                            interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert live == 0 or np.isfinite(want).any()


@pytest.mark.parametrize("counts,extra,n", [
    ((3, 21, 9), 1, 200),   # uneven groups, one dead tile, n % 128 != 0
    ((5, 0, 7), 1, 128),    # an empty group
    ((0, 30, 0), 0, 72),    # every row in one group, narrower than a lane
    ((0, 0, 0), 2, 200),    # no live tile at all: all +inf
    ((16, 32, 16), 0, 256),  # n_g an exact multiple of bq
    ((4, 9, 2), 3, 328),    # finite rows past the live count
])
def test_minplus_grouped(counts, extra, n):
    """The grouped one-sided product (kernel in interpret mode, at the
    default blocks and at 128-wide k blocks, and the jnp reference) vs
    NumPy per row: live tiles multiply against their own group's block
    of the stack, and padding rows of a live tile and every row past
    the live count read +inf, whatever those rows hold.  Output widths
    need not be multiples of 128: the whole width is one block."""
    from repro.kernels import minplus_twoside as ts

    bq, k, groups = ops.GROUPED_TILE_ROWS, 256, len(counts)
    rng = np.random.default_rng(sum(counts) * 37 + extra + n)
    tiles = [-(-c // bq) for c in counts]
    live = sum(tiles)
    tile_group = np.repeat(np.arange(groups), tiles).astype(np.int32)
    tile_group = np.concatenate([tile_group, np.full(extra, groups - 1,
                                                     np.int32)])
    n_rows = (live + extra) * bq
    b = np.array(_rand((groups + 1, k, n), rng, inf_frac=0.3))
    a = np.array(_rand((n_rows, k), rng, inf_frac=0.3))
    filled = np.zeros(n_rows, bool)
    start = 0
    for c, t in zip(counts, tiles):
        filled[start:start + c] = True
        start += t * bq
    filled[live * bq:] = True          # dead tiles hold finite rows too
    a[~filled] = np.inf
    want = np.full((n_rows, n), np.inf, np.float32)
    for r in range(live * bq):
        want[r] = np.min(a[r][:, None] + b[tile_group[r // bq]], axis=0)
    args = [jnp.asarray(x) for x in (a, b, tile_group)]
    nl = jnp.int32(live)
    for force in ("ref", "pallas"):
        got = ops.minplus_grouped(*args, nl, force=force)
        np.testing.assert_array_equal(np.asarray(got), want)
    got = ts.minplus_grouped_pallas(*args, nl, bk=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert live == 0 or np.isfinite(want).any()


@pytest.mark.parametrize("force", ["ref", "pallas"])
def test_fw_all_inf_padding_blocks(force):
    """The refresh path's pow2 padding (`_fw_bucket(pad_pow2=True)`)
    feeds ALL-+inf dummy blocks through the witness FW kernels.  Audit
    pin: the FW recurrence only adds (inf+inf = inf, never inf-inf),
    so a padding block must come out diag-0 / off-diag-inf / nxt -1
    with no NaN anywhere, and must not perturb its batch neighbours
    (FW is row-independent across the batch)."""
    rng = np.random.default_rng(99)
    real = np.asarray(_rand((1, 16, 16), rng, inf_frac=0.3))[0]
    batch = np.stack([np.full((16, 16), np.inf, np.float32), real])
    dist, nxt = map(np.asarray,
                    ops.fw_batch_next(jnp.asarray(batch), force=force))
    assert not np.isnan(dist).any()
    pad_d, pad_n = dist[0], nxt[0]
    off = ~np.eye(16, dtype=bool)
    assert (pad_d[off] == np.inf).all() and (np.diag(pad_d) == 0).all()
    assert (pad_n == -1).all()
    solo_d, solo_n = map(np.asarray,
                         ops.fw_batch_next(jnp.asarray(real[None]),
                                           force=force))
    np.testing.assert_array_equal(dist[1], solo_d[0])
    np.testing.assert_array_equal(nxt[1], solo_n[0])
    # distance-only kernel agrees bit for bit, NaN-free too
    d2 = np.asarray(ops.fw_batch(jnp.asarray(batch), force=force))
    np.testing.assert_array_equal(dist, d2)


def test_fw_bucket_all_inf_guard():
    """_fw_bucket's loud NaN guard + end-to-end all-INF padding: a
    pow2-padded piece batch (2 real pieces -> 8 with +inf dummies)
    yields exact blocks and trips no guard."""
    from repro.core.device_engine import _fw_bucket

    rng = np.random.default_rng(5)
    adjs = [np.asarray(_rand((8, 8), rng, inf_frac=0.5)) for _ in range(2)]
    blocks, nexts = _fw_bucket(adjs, pad_pow2=True)
    want, _ = map(np.asarray, ops.fw_batch_next(jnp.asarray(np.stack(adjs))))
    np.testing.assert_array_equal(blocks, want)
    assert not np.isnan(blocks).any()


def test_minplus_twoside_argmin_all_inf():
    """All-disconnected witness contraction: +inf out, -1 witnesses,
    no NaN — the padding regime serve_cross_w hits when a query batch
    is pure filler."""
    rows = jnp.full((4, 10), jnp.inf)
    d = jnp.full((10, 6), jnp.inf)
    rowt = jnp.full((4, 6), jnp.inf)
    for force in ("ref", "pallas"):
        out, wx, wy = map(np.asarray, ops.minplus_twoside_argmin(
            rows, d, rowt, force=force))
        assert np.isinf(out).all() and not np.isnan(out).any()
        assert (wx == -1).all() and (wy == -1).all()


@pytest.mark.parametrize("q,k1,k2", [(5, 7, 3), (37, 130, 201),
                                     (64, 128, 128)])
@pytest.mark.parametrize("force", ["ref", "pallas"])
def test_minplus_twoside_argmin_witness(q, k1, k2, force):
    """Witness mode: identical minima to the distance-only kernel, and
    every finite minimum's (wx, wy) pair actually achieves it."""
    rng = np.random.default_rng(q * 131 + k1 + k2)
    rows = _rand((q, k1), rng, inf_frac=0.4)
    d = _rand((k1, k2), rng, inf_frac=0.4)
    rowt = _rand((q, k2), rng, inf_frac=0.4)
    want = np.asarray(ops.minplus_twoside(rows, d, rowt, force=force))
    out, wx, wy = ops.minplus_twoside_argmin(rows, d, rowt, force=force)
    out, wx, wy = map(np.asarray, (out, wx, wy))
    np.testing.assert_array_equal(out, want)
    rows_n, d_n, rowt_n = map(np.asarray, (rows, d, rowt))
    for i in range(q):
        if np.isinf(out[i]):
            assert wx[i] == -1 and wy[i] == -1
        else:
            assert 0 <= wx[i] < k1 and 0 <= wy[i] < k2
            assert (rows_n[i, wx[i]] + d_n[wx[i], wy[i]]
                    + rowt_n[i, wy[i]]) == out[i]


@pytest.mark.parametrize("b,n", [(2, 8), (3, 24), (2, 64)])
@pytest.mark.parametrize("force", ["ref", "pallas"])
def test_fw_batch_next_witness(b, n, force):
    """Witness FW: bit-identical distances to fw_batch, and walking the
    successor matrix reproduces every finite distance exactly."""
    rng = np.random.default_rng(b * 100 + n)
    # integer weights so the walk's left-to-right f32 accumulation is
    # exact regardless of FW's summation order (the repo's graphs use
    # integer weights for the same reason)
    d = rng.integers(1, 60, (b, n, n)).astype(np.float32)
    d[rng.random((b, n, n)) < 0.6] = np.inf
    d = np.minimum(d, np.transpose(d, (0, 2, 1)))    # symmetric, like adj
    want = np.asarray(ops.fw_batch(jnp.asarray(d), force=force))
    dist, nxt = ops.fw_batch_next(jnp.asarray(d), force=force)
    dist, nxt = np.asarray(dist), np.asarray(nxt)
    np.testing.assert_array_equal(dist, want)
    adj = d.copy()
    for i in range(n):
        adj[:, i, i] = 0.0
    for bi in range(b):
        for i in range(n):
            for j in range(n):
                if i == j:
                    assert nxt[bi, i, j] == -1
                    continue
                if np.isinf(dist[bi, i, j]):
                    assert nxt[bi, i, j] == -1
                    continue
                u, acc, hops = i, 0.0, 0
                while u != j:
                    h = int(nxt[bi, u, j])
                    assert h >= 0, (bi, i, j, u)
                    acc += adj[bi, u, h]
                    u = h
                    hops += 1
                    assert hops <= n
                assert acc == dist[bi, i, j], (bi, i, j)


def test_fw_next_single_matches_batch():
    rng = np.random.default_rng(7)
    d = np.asarray(_rand((24, 24), rng, inf_frac=0.5))
    for force in ("ref", "pallas"):
        dist_b, nxt_b = ops.fw_batch_next(jnp.asarray(d[None]),
                                          force=force)
        dist, nxt = ops.fw_next(jnp.asarray(d), force=force)
        np.testing.assert_array_equal(np.asarray(dist),
                                      np.asarray(dist_b)[0])
        np.testing.assert_array_equal(np.asarray(nxt),
                                      np.asarray(nxt_b)[0])


@pytest.mark.parametrize("b,n", [(1, 8), (3, 16), (2, 64)])
def test_fw_batch_matches_ref(b, n):
    rng = np.random.default_rng(b * 100 + n)
    d = _rand((b, n, n), rng, inf_frac=0.5)
    d = jnp.minimum(d, jnp.transpose(d, (0, 2, 1)))
    got = ops.fw_batch(d, force="pallas")
    np.testing.assert_allclose(got, ref.fw_batch_ref(d), rtol=1e-6)


@pytest.mark.parametrize("n,block", [(10, 8), (100, 32), (64, 64)])
def test_fw_blocked_matches_ref(n, block):
    rng = np.random.default_rng(n)
    d = _rand((n, n), rng, inf_frac=0.6)
    d = jnp.minimum(d, d.T)
    got = ops.fw_apsp(d, block=block, force="pallas")
    np.testing.assert_allclose(got, ref.fw_ref(d), rtol=1e-6)


def test_fw_integer_weights_exact():
    """Integer weights -> bitwise-exact FW distances in f32: the
    invariant the refresh differential harness (incremental == scratch,
    array-equal) rests on."""
    rng = np.random.default_rng(77)
    d = _rand((60, 60), rng, inf_frac=0.5)
    d = jnp.minimum(d, d.T)
    di = jnp.where(jnp.isfinite(d), jnp.round(d * 8), jnp.inf)
    a = np.asarray(ops.fw_apsp(di))
    b = np.asarray(ref.fw_ref(di))
    np.testing.assert_array_equal(a, b)
    assert (np.asarray(a)[np.isfinite(a)] % 1 == 0).all()


def test_fw_matches_dijkstra():
    """APSP kernel vs heap Dijkstra on a real graph."""
    g = random_graph(40, 80, seed=9)
    adj = np.full((g.n, g.n), np.inf, np.float32)
    adj[g.edge_u, g.edge_v] = g.edge_w
    adj[g.edge_v, g.edge_u] = g.edge_w
    got = np.asarray(ops.fw_apsp(jnp.asarray(adj), block=16,
                                 force="pallas"))
    for s in range(0, g.n, 7):
        want = dijkstra.sssp(g, s)
        fin = np.isfinite(want)
        np.testing.assert_allclose(got[s][fin], want[fin], rtol=1e-5)


# ---- property tests --------------------------------------------------------
@given(st.integers(0, 10_000))
@settings(max_examples=15)
def test_fw_idempotent(seed):
    """APSP is a fixpoint: fw(fw(D)) == fw(D)."""
    rng = np.random.default_rng(seed)
    d = _rand((1, 12, 12), rng, inf_frac=0.4)
    d = jnp.minimum(d, jnp.transpose(d, (0, 2, 1)))
    once = ops.fw_batch(d, force="ref")
    twice = ops.fw_batch(once, force="ref")
    np.testing.assert_allclose(once, twice, rtol=1e-6)


@given(st.integers(0, 10_000))
@settings(max_examples=15)
def test_minplus_associative(seed):
    """(A (x) B) (x) C == A (x) (B (x) C) — semiring associativity."""
    rng = np.random.default_rng(seed)
    a = _rand((6, 5), rng)
    b = _rand((5, 7), rng)
    c = _rand((7, 4), rng)
    left = ref.minplus_ref(ref.minplus_ref(a, b), c)
    right = ref.minplus_ref(a, ref.minplus_ref(b, c))
    np.testing.assert_allclose(left, right, rtol=1e-5)


@given(st.integers(0, 10_000))
@settings(max_examples=15)
def test_minplus_triangle_inequality(seed):
    """D (x) D <= D for any APSP matrix D (metric closure)."""
    rng = np.random.default_rng(seed)
    d = _rand((1, 10, 10), rng, inf_frac=0.3)
    d = jnp.minimum(d, jnp.transpose(d, (0, 2, 1)))
    apsp = ops.fw_batch(d, force="ref")[0]
    sq = ref.minplus_ref(apsp, apsp)
    assert bool(jnp.all(sq >= apsp - 1e-4))


# ---- wide tiles: the blocked witness closure and the top first hops -------
def _tied_adj(b, n, rng, inf_frac=0.8):
    """Symmetric integer adjacencies with weights 1..4: short paths of
    equal length everywhere, so the witnesses meet many ties."""
    d = rng.integers(1, 5, (b, n, n)).astype(np.float32)
    d[rng.random((b, n, n)) < inf_frac] = np.inf
    return np.minimum(d, np.transpose(d, (0, 2, 1)))


def _assert_valid_hops(adj, dist, nxt):
    """Every hop h of (i, j) is adjacent to i and starts a shortest
    path: adj[i, h] + dist[h, j] == dist[i, j]; -1 exactly on the
    diagonal and the unreachable pairs."""
    n = adj.shape[-1]
    for a, d, h in zip(adj, dist, nxt):
        a = a.copy()
        np.fill_diagonal(a, np.inf)
        fin = np.isfinite(d) & ~np.eye(n, dtype=bool)
        np.testing.assert_array_equal(h[~fin], -1)
        hc = np.clip(h, 0, n - 1)
        ah = np.take_along_axis(a, hc, axis=1)
        dh = d[hc, np.arange(n)[None, :]]
        assert ((h >= 0) & np.isfinite(ah) & (ah + dh == d))[fin].all()


@pytest.mark.parametrize("b,n,block", [(2, 64, 8), (3, 40, 8), (1, 64, 16)])
def test_fw_batch_next_blocked_witness(b, n, block):
    """The blocked witness closure (interpret mode, block scaled down):
    distances bit-equal to the reference, every witness a valid first
    hop of a shortest path, ties included; n = 40 pads to the block."""
    rng = np.random.default_rng(b * 1000 + n + block)
    adj = _tied_adj(b, n, rng)
    dist, nxt = map(np.asarray, fw.fw_batch_next_blocked(
        jnp.asarray(adj), block=block, interpret=True))
    want, _ = map(np.asarray, ops.fw_batch_next(jnp.asarray(adj),
                                                force="ref"))
    np.testing.assert_array_equal(dist, want)
    _assert_valid_hops(adj, dist, nxt)


def test_fw_batch_next_one_block_is_the_whole_tile():
    """A tile no wider than the pivot block is closed by the whole-tile
    witness kernel alone (same distances and hops); wider tiles take
    the panels too.  ops.fw_batch_next runs this one closure."""
    assert ops.fw_next_variant(fw.FW_BLOCK, "pallas") == "whole_tile"
    assert ops.fw_next_variant(fw.FW_BLOCK + 1, "pallas") == "blocked"
    assert ops.fw_next_variant(4096, "ref") == "ref"
    adj = jnp.asarray(_tied_adj(2, 24, np.random.default_rng(3)))
    want = fw._fw_next_tiles(*ref.fw_next_init(adj), True)
    for got in (fw.fw_batch_next_blocked(adj, interpret=True),
                ops.fw_batch_next(adj, force="pallas")):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _first_hops_np(adj, dist, rows=None, cols=None):
    """The plain reference of the canonical first hops: the smallest
    k != i with adj[i, k] finite and adj[i, k] + dist[k, j] ==
    dist[i, j], -1 where none is or dist[i, j] is +inf."""
    n = dist.shape[0]
    rows = np.arange(n) if rows is None else rows
    cols = np.arange(n) if cols is None else cols
    a = adj.astype(np.float32, copy=True)
    np.fill_diagonal(a, np.inf)
    out = np.full((rows.size, cols.size), -1, np.int32)
    for r, i in enumerate(rows):
        tgt = dist[i, cols]
        ok = (np.isfinite(a[i])[:, None]
              & (a[i][:, None] + dist[:, cols] == tgt[None, :]))
        out[r] = np.where(ok.any(axis=0) & np.isfinite(tgt),
                          np.argmax(ok, axis=0), -1)
    return out


@pytest.mark.parametrize("force", ["ref", "pallas"])
@pytest.mark.parametrize("n", [37, 150])
def test_top_first_hops_match_the_host_reference(n, force):
    """Top witnesses on the device (kernel in interpret mode, and the
    jnp reference) array-equal to the host reference on random closures
    with planted ties and an unreachable part, whole and restricted to
    rows or to rows x columns."""
    from repro.core.hierarchy import top_first_hops

    rng = np.random.default_rng(n)
    adj = _tied_adj(1, n, rng, inf_frac=0.9)[0]
    adj[: n // 5, n // 5:] = adj[n // 5:, : n // 5] = np.inf
    dist = np.asarray(ref.fw_ref(jnp.asarray(adj)))
    assert np.isinf(dist).any()
    got = np.asarray(top_first_hops(adj, dist, force=force))
    np.testing.assert_array_equal(got, _first_hops_np(adj, dist))
    rows = np.sort(rng.choice(n, 9, replace=False))
    cols = np.sort(rng.choice(n, 13, replace=False))
    np.testing.assert_array_equal(
        np.asarray(top_first_hops(adj, dist, rows=rows, force=force)),
        _first_hops_np(adj, dist, rows=rows))
    np.testing.assert_array_equal(
        np.asarray(top_first_hops(adj, dist, rows=rows, cols=cols,
                                  force=force)),
        _first_hops_np(adj, dist, rows=rows, cols=cols))
