"""Device SSSP (Bellman-Ford against Dijkstra) and the seeded query
generators."""
import jax.numpy as jnp
import numpy as np

from repro.core import dijkstra
from repro.core.graph import road_like
from repro.core.sssp import apsp_from_sources, bellman_ford, sources_init
from repro.data import grid_distance_queries
from repro.data.queries import workload_pairs


# ---- device SSSP -------------------------------------------------------------
def test_bellman_ford_matches_dijkstra():
    g = road_like(600, seed=11)
    src = jnp.asarray(np.concatenate([g.edge_u, g.edge_v]), jnp.int32)
    dst = jnp.asarray(np.concatenate([g.edge_v, g.edge_u]), jnp.int32)
    w = jnp.asarray(np.concatenate([g.edge_w, g.edge_w]), jnp.float32)
    sources = jnp.asarray([0, 5, 17], jnp.int32)
    got = np.asarray(apsp_from_sources(src, dst, w, sources, n=g.n))
    for i, s in enumerate([0, 5, 17]):
        want = dijkstra.sssp(g, s)
        fin = np.isfinite(want)
        np.testing.assert_allclose(got[i][fin], want[fin], rtol=1e-5)
        assert np.isinf(got[i][~fin]).all()


def test_bellman_ford_padding_edges_are_inert():
    src = jnp.asarray([0, 1, 0], jnp.int32)
    dst = jnp.asarray([1, 2, 0], jnp.int32)
    w = jnp.asarray([1.0, 2.0, np.inf], jnp.float32)
    out = bellman_ford(src, dst, w, sources_init(
        jnp.asarray([0], jnp.int32), 3), n=3)
    np.testing.assert_allclose(np.asarray(out)[0], [0.0, 1.0, 3.0])


# ---- query generators ------------------------------------------------------
def test_grid_queries_bucketed():
    g = road_like(2000, seed=14)
    qs = grid_distance_queries(g, n_per_set=20, n_sets=6, seed=0)
    assert set(qs) == set(range(1, 7))
    for i, pairs in qs.items():
        assert pairs.shape[1] == 2


def test_generators_deterministic():
    g = road_like(800, seed=13)
    a = grid_distance_queries(g, n_per_set=10, n_sets=4, seed=3)
    b = grid_distance_queries(g, n_per_set=10, n_sets=4, seed=3)
    assert a.keys() == b.keys()
    for i in a:
        np.testing.assert_array_equal(a[i], b[i])
    for mix in ("uniform", "geo"):
        np.testing.assert_array_equal(workload_pairs(g, mix, 64, seed=5),
                                      workload_pairs(g, mix, 64, seed=5))
