"""The benchmark's copies of the data and traffic generators give what
the program's originals give for a fixed seed, and what a run draws
depends only on its seed."""
import argparse

import bench_tiny
import numpy as np
import pytest

from bench import cells, graphgen, traffic, window

ZIPF = cells.load_module(bench_tiny.REPO / "bench/pairs/zipf.py")
UNIFORM = cells.load_module(bench_tiny.REPO / "bench/pairs/uniform.py")


@pytest.mark.parametrize("n,seed", [(400, 0), (2000, 0), (2000, 3)])
def test_road_graph_copy_matches_the_program(n, seed):
    from repro.core.graph import road_like

    g = road_like(n, seed=seed)
    e = graphgen.road_like(n, seed)
    assert (e.n, e.m) == (g.n, g.m)
    assert np.array_equal(e.u, g.edge_u) and np.array_equal(e.v, g.edge_v)
    assert np.array_equal(e.w, g.edge_w)


def test_uniform_copy_matches_the_program():
    from repro.data.queries import workload_pairs

    e = graphgen.road_like(2000, 0)
    g = _graph(e)
    want = workload_pairs(g, "uniform", 3000, seed=7)
    got = UNIFORM.draw(e.n, 3000, np.random.default_rng(7), {}, None)
    assert np.array_equal(got, want)
    assert np.all(got[:, 0] != got[:, 1])


def test_zipf_copy_matches_the_program():
    from repro.data.queries import zipf_pairs, zipf_pool

    e = graphgen.road_like(2000, 0)
    g = _graph(e)
    assert np.array_equal(ZIPF.make_pool(e.n, 2048,
                                         np.random.default_rng(5)),
                          zipf_pool(g, seed=5))
    rng = np.random.default_rng(9)
    pool = ZIPF.make_pool(e.n, 512, rng)
    got = ZIPF.draw(e.n, 4000, rng, {"a": 1.2}, pool)
    assert np.array_equal(got, zipf_pairs(g, 4000, a=1.2, pool=512,
                                          seed=9))


@pytest.mark.parametrize("budget", [16, 2048])
def test_hub_rule_matches_the_program(budget):
    from repro.launch.serve import _hub_selection

    e = graphgen.road_like(2000, 0)
    g = _graph(e)
    want = _hub_selection(g, argparse.Namespace(hub_budget=budget, seed=0))
    pool = traffic.popular_pool({"popular_pairs": {"pairs": 2048,
                                                   "seed": 4}}, e.n)
    assert np.array_equal(traffic.hub_nodes(pool, budget), want)
    assert traffic.hub_nodes(pool, 0) is None


@pytest.mark.parametrize("seed", [0, 12345, 2**31 + 7, 2**40 + 1, -3])
def test_draws_depend_only_on_the_seed(seed):
    pool = ZIPF.make_pool(1000, 256, np.random.default_rng(4))

    def draws(s):
        pairs = ZIPF.draw(1000, 500, traffic.rng_for(s, traffic.PAIRS),
                          {"a": 1.2}, pool)
        arr = window.uniform_arrivals(
            800.0, 2.5, traffic.rng_for(s, traffic.ARRIVALS))
        return pairs, arr

    (p1, a1), (p2, a2) = draws(seed), draws(seed)
    assert np.array_equal(p1, p2) and np.array_equal(a1, a2)
    p3, a3 = draws(seed + 1)
    assert not np.array_equal(p1, p3)
    # every seed offers the same number of requests in the window
    assert a1.size == a3.size == 2000
    assert np.all(np.diff(a1) >= 0) and 0 <= a1[0] and a1[-1] < 2.5


def _graph(e):
    from repro.core.graph import Graph

    return Graph.from_edges(e.n, e.u, e.v, e.w)
