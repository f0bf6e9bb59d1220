"""QueryPlanner edge cases: degenerate batches, empty buckets, and
pow2-padding filler hygiene (DESIGN.md §5), on every index layout.

The planner pads each case bucket to a power of two with (0, 0)
self-query filler; none of that filler may ever leak into returned
distances, for any batch composition.  ``world`` runs each case on the
dense overlay, on one and two grouping levels, and on two grouping
levels with the Pallas kernels (interpret mode on the CPU), where the
same-group legs run as grouped contractions with a filler key.
"""
import functools

import numpy as np
import pytest

from repro.core import dijkstra
from repro.core.device_engine import build_device_index
from repro.core.dist_engine import QueryPlanner, _pad_pow2
from repro.core.graph import road_like
from repro.core.supergraph import build_index

#: layout id -> (hierarchy_levels, planner force); None levels is the
#: dense overlay of a graph under the auto threshold
LAYOUTS = {"dense": (None, None), "hier2": (2, None), "hier3": (3, None),
           "hier3-pallas": (3, "pallas")}
#: the cases every layout reaches; cross_res needs ``world_res``
WORLD_CASES = tuple(c for c in QueryPlanner.CASES if c != "cross_res")


@functools.lru_cache(maxsize=None)
def _hier_world(levels: int):
    """A hierarchical index of ``levels`` levels whose every grouping
    level has at least two real groups, so the layout cannot quietly
    collapse to dense."""
    g = road_like(2500, seed=3)
    dix = build_device_index(build_index(g), hierarchy_levels=levels)
    assert len(dix.sf_of) == levels - 1
    assert all(c.shape[0] - 1 >= 2 for c in dix.sf_closure)
    return g, dix


@pytest.fixture(scope="module", params=list(LAYOUTS))
def world(request):
    levels, force = LAYOUTS[request.param]
    if levels is None:
        g = road_like(1000, seed=41)
        dix = build_device_index(build_index(g))
        assert len(dix.sf_of) == 0
    else:
        g, dix = _hier_world(levels)
    return g, dix, QueryPlanner(dix, force=force)


@pytest.fixture(scope="module")
def world_res():
    """A hierarchical index with resident pre-lifted rows in two top
    groups, so the cross_res bucket is actually reachable (a dense
    index has no residency and its cross_res bucket is provably
    empty)."""
    g, dix = _hier_world(3)
    rf = getattr(dix, "host_res_frag", None)
    if rf is None or np.asarray(dix.res_rows).shape[0] <= 1:
        pytest.skip("no resident rows at this size")
    rf = np.asarray(rf)
    tg = np.asarray(dix.host_topgrp_frag)
    hot = np.nonzero(rf >= 0)[0]
    if hot.size < 2 or np.unique(tg[hot]).size < 2:
        pytest.skip("no resident pair across top groups at this size")
    return g, dix, QueryPlanner(dix)


def _want(g, pairs):
    return np.array([dijkstra.pair(g, int(a), int(b)) for a, b in pairs])


def _check(g, planner, pairs):
    pairs = np.asarray(pairs)
    got = planner(pairs[:, 0], pairs[:, 1])
    want = _want(g, pairs)
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5)
    assert np.isinf(got[~fin]).all()
    return got


def _pairs_of_case(g, planner, case, n):
    """n query pairs all belonging to one planner case."""
    dix = planner.dix
    agent_of = np.asarray(dix.agent_of)
    frag_of = np.asarray(dix.frag_of)
    fa = frag_of[agent_of]
    out = []
    if case == "same_dra":
        agents, counts = np.unique(agent_of, return_counts=True)
        a = agents[np.argmax(counts)]
        members = np.nonzero(agent_of == a)[0]
        assert members.size >= 2
        for i in range(n):
            out.append((int(members[i % members.size]),
                        int(members[(i + 1) % members.size])))
    elif case == "same_frag":
        for f in np.unique(fa[fa >= 0]):
            nodes = np.nonzero(fa == f)[0]
            us = agent_of[nodes]
            if np.unique(us).size >= 2:
                j = int(np.argmax(us != us[0]))
                for i in range(n):
                    out.append((int(nodes[0]), int(nodes[j])))
                break
    elif case == "cross_res":
        rf = np.asarray(dix.host_res_frag)
        tg = np.asarray(dix.host_topgrp_frag)
        hot = np.nonzero(rf >= 0)[0]
        f0 = int(hot[0])
        f1 = int(hot[np.argmax(tg[hot] != tg[f0])])
        assert tg[f1] != tg[f0], "no resident pair across top groups"
        a = int(np.nonzero(fa == f0)[0][0])
        b = int(np.nonzero(fa == f1)[0][0])
        for i in range(n):
            out.append((a, b))
    else:  # cross_frag
        valid = np.nonzero(fa >= 0)[0]
        f0 = fa[valid[0]]
        other = fa[valid] != f0
        rf = getattr(dix, "host_res_frag", None)
        tg = getattr(dix, "host_topgrp_frag", None)
        if rf is not None and tg is not None:
            # two resident fragments in different top groups dispatch
            # as cross_res: keep the second fragment cold or in the
            # first one's top group
            rf, tg = np.asarray(rf), np.asarray(tg)
            other &= (rf[fa[valid]] < 0) | (rf[f0] < 0) \
                | (tg[fa[valid]] == tg[f0])
        assert other.any(), "no cross_frag pair"
        b = valid[np.argmax(other)]
        for i in range(n):
            out.append((int(valid[0]), int(b)))
    assert len(out) == n, f"could not build {case} pairs"
    out = np.asarray(out)
    assert planner.plan(out[:, 0], out[:, 1])[case].size == n
    return out


def _batch_of_one(g, planner, case):
    pairs = _pairs_of_case(g, planner, case, 1)
    _check(g, planner, pairs)
    counts = dict(planner.last_counts)
    assert counts[case] == 1
    assert sum(counts.values()) == 1


@pytest.mark.parametrize("case", WORLD_CASES)
def test_batch_of_one(world, case):
    g, _, planner = world
    _batch_of_one(g, planner, case)


def test_batch_of_one_cross_res(world_res):
    g, _, planner = world_res
    _batch_of_one(g, planner, "cross_res")


def _single_case_batch(g, planner, case):
    """A batch entirely of one case: the other sub-programs must
    not be dispatched at all (empty-bucket skip)."""
    pairs = _pairs_of_case(g, planner, case, 13)   # odd size -> pow2 pad
    _check(g, planner, pairs)
    for c, n in planner.last_counts.items():
        assert n == (13 if c == case else 0)


@pytest.mark.parametrize("case", WORLD_CASES)
def test_single_case_batches(world, case):
    g, _, planner = world
    _single_case_batch(g, planner, case)


def test_single_case_batches_cross_res(world_res):
    g, _, planner = world_res
    _single_case_batch(g, planner, "cross_res")


def test_empty_batch(world):
    g, dix, planner = world
    got = planner(np.empty(0, np.int32), np.empty(0, np.int32))
    assert got.shape == (0,)
    assert all(n == 0 for n in planner.last_counts.values())


@pytest.mark.parametrize("size", [1, 2, 3, 5, 17, 100])
def test_pow2_filler_never_leaks(world, size):
    """Non-pow2 batch sizes force filler slots; outputs must equal
    per-pair host Dijkstra regardless — including the degenerate query
    (0, 0) appearing *legitimately* inside the batch."""
    g, dix, planner = world
    rng = np.random.default_rng(size)
    pairs = rng.integers(0, g.n, size=(size, 2))
    pairs[0] = (0, 0)          # a real query identical to the filler
    got = _check(g, planner, pairs)
    assert got[0] == 0.0
    # padded sizes are pow2 internally but output length is exact
    assert got.shape == (size,)
    assert _pad_pow2(size) >= size


def test_self_queries_everywhere(world):
    g, dix, planner = world
    s = np.arange(0, g.n, 97, dtype=np.int32)
    got = planner(s, s)
    np.testing.assert_array_equal(got, np.zeros(s.size, np.float32))


def test_epoch_swap_reuses_compiled_programs(world):
    """set_index on a same-shaped index must not recompile any
    sub-program (epoch swaps are pointer flips, DESIGN.md §9)."""
    g, dix, planner = world
    rng = np.random.default_rng(0)
    pairs = rng.integers(0, g.n, size=(32, 2))
    planner(pairs[:, 0], pairs[:, 1])            # compile at this size
    compiles_before = {c: fn._cache_size() for c, fn in
                       planner._fns.items()}
    planner.set_index(dix)                       # same epoch re-publish
    planner(pairs[:, 0], pairs[:, 1])
    compiles_after = {c: fn._cache_size() for c, fn in
                      planner._fns.items()}
    assert compiles_before == compiles_after
