"""Differential harness for the two-level overlay hierarchy
(DESIGN.md §12).

The contract:

  1. ``hierarchy_levels=2`` serves distances ARRAY-EQUAL to the dense
     closure — every planner bucket, the monolithic program, and
     one-to-all — and exact against host Dijkstra;
  2. witness serving + host unwinding produce exact edge-valid paths
     whose overlay legs cross hierarchy levels;
  3. incremental refresh == from-scratch rebuild, array-for-array,
     for every per-level table, with rollback on failure;
  4. ``hierarchy_levels=1`` (and "auto" below the threshold) keeps the
     dense index bit-identical to the pre-hierarchy build — the
     road4000 compatibility guarantee.

Graphs here are small (forced levels=2), so both closures are cheap
and the dense one is the oracle.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dijkstra, hierarchy
from repro.core import dist_engine
from repro.core.device_engine import (_hier_leg, _leg_key,
                                      _leg_schedule, _lift_compact,
                                      _lift_res,
                                      build_device_index,
                                      build_device_index_with_plan,
                                      index_fields_equal,
                                      overlay_slot_table,
                                      refresh_index,
                                      resolve_hierarchy_levels,
                                      serve_cross, serve_one_to_all,
                                      serve_step)
from repro.core.dist_engine import EpochedEngine, QueryPlanner
from repro.core.graph import road_like, traffic_updates
from repro.core.paths import path_weight
from repro.core.supergraph import build_index, reweight_index
from repro.launch.serve import REFRESHED_FIELDS

HIER_FIELDS = ("sf_closure", "sf_next", "l2row", "d2", "d2_next")


@pytest.fixture(scope="module")
def built():
    g = road_like(700, seed=7)
    ix = build_index(g)
    dense = build_device_index_with_plan(ix, hierarchy_levels=1)
    hier = build_device_index_with_plan(ix, hierarchy_levels=2)
    return g, ix, dense, hier


def test_resolve_levels_knob():
    thr = hierarchy.AUTO_THRESHOLD
    assert resolve_hierarchy_levels(thr, "auto") == 1
    assert resolve_hierarchy_levels(thr + 1, "auto") == 2
    assert resolve_hierarchy_levels(50, 2) == 2
    assert resolve_hierarchy_levels(50, 3) == 3
    assert resolve_hierarchy_levels(50, hierarchy.MAX_LEVELS) \
        == hierarchy.MAX_LEVELS
    assert resolve_hierarchy_levels(0, 2) == 1      # empty overlay
    with pytest.raises(ValueError):
        resolve_hierarchy_levels(50, 0)
    with pytest.raises(ValueError):
        resolve_hierarchy_levels(50, hierarchy.MAX_LEVELS + 1)
    with pytest.raises(ValueError):
        resolve_hierarchy_levels(50, "deep")


def test_auto_small_graph_stays_dense(built):
    """'auto' below the threshold builds the exact dense index —
    bit-identical d_super/super_next, 1-sized hierarchy dummies."""
    g, ix, (dix1, _p1), _ = built
    auto_dix = build_device_index(ix)               # default: auto
    assert auto_dix.hierarchy_levels == 1
    np.testing.assert_array_equal(np.asarray(auto_dix.d_super),
                                  np.asarray(dix1.d_super))
    np.testing.assert_array_equal(np.asarray(auto_dix.super_next),
                                  np.asarray(dix1.super_next))
    assert auto_dix.sf_of == ()            # no grouping levels at all
    assert auto_dix.hierarchy_levels == 1
    assert auto_dix.d2.shape == (1, 1)
    assert auto_dix.res_rows.shape == (1, 1, 1)


def test_hier_structure_invariants(built):
    """Every overlay node lands in exactly one super-fragment, the
    grouping is fragment-aligned (cliques never split), and the
    level-2 boundary is exactly the cross-super-fragment slot
    endpoints."""
    _g, _ix, (_d1, p1), (dix2, p2) = built
    h = p2.hier[0]
    assert dix2.hierarchy_levels == 2
    S = p2.S
    assert h.sf_of.shape == (S,) and (h.sf_of >= 0).all()
    assert h.sf_of.max() + 1 == h.nsf
    # members table round-trips sf_of/pos_in_sf
    for sid in range(S):
        assert h.sf_members[h.sf_of[sid], h.pos_in_sf[sid]] == sid
    # fragment-aligned: a fragment's boundary nodes share one sf
    fi_idx, b_idx = np.nonzero(p2.bvalid)
    sids = p2.bnd_super[fi_idx, b_idx]
    for fi in np.unique(fi_idx):
        assert np.unique(h.sf_of[sids[fi_idx == fi]]).size == 1
    # level-2 boundary = endpoints of sf-crossing slots
    crossing = h.slot_sf < 0
    want_b2 = np.unique(np.concatenate(
        [p2.sup_src[crossing], p2.sup_dst[crossing]]))
    np.testing.assert_array_equal(h.bnd2_ids, want_b2)
    # intra-sf slots carry valid local coords
    intra = ~crossing
    assert (h.slot_p2u[intra] >= 0).all()
    assert (h.sf_of[p2.sup_src[intra]]
            == h.sf_of[p2.sup_dst[intra]]).all()


def test_hier_distances_equal_dense(built):
    """Monolithic + planner-bucketed + one-to-all distances are
    array-equal between the dense and hierarchical closures, and exact
    vs Dijkstra on a sample."""
    g, _ix, (dix1, _p1), (dix2, _p2) = built
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.integers(0, g.n, 256), jnp.int32)
    t = jnp.asarray(rng.integers(0, g.n, 256), jnp.int32)
    o1 = np.asarray(serve_step(dix1, s, t))
    o2 = np.asarray(serve_step(dix2, s, t))
    np.testing.assert_array_equal(o1, o2)
    for i in range(32):
        want = dijkstra.pair(g, int(s[i]), int(t[i]))
        assert not dijkstra.mismatches_oracle(want, float(o2[i]))
    for src in (0, 123, g.n - 1):
        np.testing.assert_array_equal(
            np.asarray(serve_one_to_all(dix1, src)),
            np.asarray(serve_one_to_all(dix2, src)))


def test_hier_pallas_layout_parity(built):
    """The TPU layout (Pallas kernels in interpret mode) of the
    hierarchical combine matches the jnp reference layout exactly, on
    random pairs and planner filler rows."""
    g, _ix, _dense, (dix2, _p2) = built
    rng = np.random.default_rng(3)
    # ending in planner filler rows (s = t = 0)
    s = jnp.asarray(np.r_[rng.integers(0, g.n, 64), np.zeros(8)],
                    jnp.int32)
    t = jnp.asarray(np.r_[rng.integers(0, g.n, 64), np.zeros(8)],
                    jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(serve_step(dix2, s, t)),
        np.asarray(serve_step(dix2, s, t, force="pallas")))


@pytest.fixture(scope="module")
def deep():
    """Two grouping levels (hierarchy_levels=3), each with at least two
    real groups."""
    g = road_like(2500, seed=7)
    dix = build_device_index(build_index(g), hierarchy_levels=3)
    assert len(dix.sf_of) == 2
    assert all(c.shape[0] - 1 >= 2 for c in dix.sf_closure)
    return g, dix


def _side_groups(dix, frag):
    """Per grouping level, the group of each fragment's side (-1: none),
    walked up exactly as _combine_mid_h lifts a side."""
    ids = np.asarray(dix.bnd_super)[frag]
    out = []
    for li in range(len(dix.sf_of)):
        sf_of = np.asarray(dix.sf_of[li])
        grp = sf_of[ids]
        out.append(np.where(grp[:, 0] == sf_of[-1], -1, grp[:, 0]))
        ids = np.asarray(dix.bnd2_sid[li])[grp[:, 0]]
    return out


@pytest.mark.parametrize("which", ["built", "deep"])
def test_each_side_lies_in_one_group(which, request):
    """The invariant the same-group contraction rests on: at every
    grouping level, all valid slots of a fragment's side lie in ONE
    group, and slot 0 is valid whenever any slot is."""
    fix = request.getfixturevalue(which)
    dix = fix[3][0] if which == "built" else fix[1]
    ids = np.asarray(dix.bnd_super)
    for li in range(len(dix.sf_of)):
        sf_of = np.asarray(dix.sf_of[li])
        valid = ids != sf_of.shape[0] - 1
        grp = sf_of[ids]
        assert (valid[:, 0] | ~valid.any(axis=1)).all(), li
        for f in range(ids.shape[0]):
            assert np.unique(grp[f][valid[f]]).size <= 1, (li, f)
        ids = np.asarray(dix.bnd2_sid[li])[grp[:, 0]]


def _leg_inputs(dix, s, t):
    """Per grouping level, _hier_leg's (row, grp, pos) for both sides,
    as _combine_mid_h feeds it, with four rows appended: a sentinel-
    only s side, a sentinel-only t side, both, and row 0 again with
    every sentinel slot's value set to 0.  All their values are
    finite, so only the sentinel handling keeps the first three +inf
    and the fourth equal to row 0."""
    us, ut = dix.agent_of[s], dix.agent_of[t]
    fs, ft = dix.frag_of[us], dix.frag_of[ut]
    rs = dix.brow[fs, dix.pos_in_frag[us]]
    rt = dix.brow[ft, dix.pos_in_frag[ut]]
    ids_s, ids_t = dix.bnd_super[fs], dix.bnd_super[ft]
    levels = []
    for li in range(len(dix.sf_of)):
        sent = dix.sf_of[li].shape[0] - 1
        ns, nt = jnp.full_like(ids_s[:1], sent), jnp.full_like(ids_t[:1],
                                                               sent)
        zs, zt = jnp.zeros_like(rs[:1]), jnp.zeros_like(rt[:1])
        a_s = jnp.concatenate([ids_s, ns, ids_s[:1], ns, ids_s[:1]])
        a_t = jnp.concatenate([ids_t, ids_t[:1], nt, nt, ids_t[:1]])
        b_s = jnp.concatenate([rs, zs, rs[:1], zs, jnp.where(
            ids_s[:1] == sent, 0.0, rs[:1])])
        b_t = jnp.concatenate([rt, rt[:1], zt, zt, jnp.where(
            ids_t[:1] == sent, 0.0, rt[:1])])
        levels.append((b_s, dix.sf_of[li][a_s], dix.pos_in_sf[li][a_s],
                       b_t, dix.sf_of[li][a_t], dix.pos_in_sf[li][a_t]))
        gs, gt = dix.sf_of[li][ids_s], dix.sf_of[li][ids_t]
        rs = _lift_compact(dix, li, rs, gs, dix.pos_in_sf[li][ids_s])
        rt = _lift_compact(dix, li, rt, gt, dix.pos_in_sf[li][ids_t])
        ids_s = dix.bnd2_sid[li][gs[:, 0]]
        ids_t = dix.bnd2_sid[li][gt[:, 0]]
    return levels


def test_hier_leg_contraction_equals_gather(deep):
    """_hier_leg's contraction layout (Pallas, interpret mode) is
    bit-equal to its gather layout at every level, on sides in the
    same group, in different groups, and sentinel-only.  Planner filler
    rows (s = t = 0, not ``valid``) are no live rows: their keys are
    the sentinel, they read +inf, and the rows the schedule streams are
    the tile rule's count over the real pairs alone, the planner's
    ``leg_rows``."""
    g, dix = deep
    rng = np.random.default_rng(11)
    frag = np.asarray(dix.frag_of)[np.asarray(dix.agent_of)]
    nodes = np.nonzero(frag >= 0)[0]
    s = rng.choice(nodes, 96).astype(np.int32)
    t = rng.choice(nodes, 96).astype(np.int32)
    # row 0: two nodes of one fragment that has sentinel slots, so its
    # sides share a group at every level and carry sentinels
    bs = np.asarray(dix.bnd_super)
    padded = np.nonzero((bs == dix.sf_of[0].shape[0] - 1).any(axis=1))[0]
    s[0], t[0] = rng.choice(np.nonzero(frag == padded[0])[0], 2)
    # the host counts the real pairs, and row 0 again (_leg_inputs'
    # last row); the filler rows come after the real ones
    counts = dist_engine._same_group_counts(
        np.r_[s, s[:1]], np.r_[t, t[:1]], dist_engine._host_maps(dix))
    s = np.r_[s, np.zeros(5, np.int32)]
    t = np.r_[t, np.zeros(5, np.int32)]
    real = np.r_[np.arange(s.size) < 96, np.ones(4, bool)]
    valid = jnp.asarray(real)
    groups_s = _side_groups(dix, frag[s])
    groups_t = _side_groups(dix, frag[t])
    for li, args in enumerate(_leg_inputs(dix, jnp.asarray(s),
                                          jnp.asarray(t))):
        same = groups_s[li] == groups_t[li]
        assert same.any() and not same.all(), li
        assert (args[1][0] != args[1][0, 0]).any(), li   # has sentinels
        ref = np.asarray(_hier_leg(dix, li, *args, force="ref"))
        kern = np.asarray(_hier_leg(dix, li, *args, valid=valid,
                                    force="pallas"))
        np.testing.assert_array_equal(kern[real], ref[real])
        assert np.isinf(kern[~real]).all()
        assert np.isinf(ref[-4:-1]).all() and ref[-1] == ref[0]
        assert np.isfinite(ref[0])
        assert np.isinf(ref[:-4][~same]).all()
        assert np.isfinite(ref[:-4][same]).any()
        ng = dix.sf_closure[li].shape[0] - 1
        key = _leg_key(args[1], args[4], valid, ng)
        assert (np.asarray(key)[~real] == ng).all()
        assert (np.asarray(key) < ng).sum() == counts[li].sum()
        bq, _, _, live = _leg_schedule(key, ng)
        assert int(live) * bq == \
            dist_engine._leg_rows([counts[li]])["leg_rows"]


def test_hier_leg_contraction_oracle(deep):
    """Eight pairs through serve_cross on the contraction layout, two
    for each leg that can answer them (same fragment; same level-0
    group; same level-1 group only; different level-1 groups), each
    == float64 Dijkstra."""
    g, dix = deep
    rng = np.random.default_rng(5)
    frag = np.asarray(dix.frag_of)[np.asarray(dix.agent_of)]
    agent = np.asarray(dix.agent_of)
    nodes = np.nonzero(frag >= 0)[0]
    kinds: dict = {}
    while sum(len(v) for v in kinds.values()) < 8:
        s, t = (int(x) for x in rng.choice(nodes, 2))
        if agent[s] == agent[t]:
            continue
        g0, g1 = _side_groups(dix, frag[[s, t]])
        kind = ("frag" if frag[s] == frag[t] else
                "l0" if g0[0] == g0[1] else
                "l1" if g1[0] == g1[1] else "top")
        if len(kinds.setdefault(kind, [])) < 2:
            kinds[kind].append((s, t))
    for kind, pairs in kinds.items():
        s, t = (jnp.asarray(x, jnp.int32) for x in zip(*pairs))
        got = serve_cross(dix, s, t, with_local=kind == "frag",
                          force="pallas")
        for (a, b), d in zip(pairs, np.asarray(got)):
            assert float(d) == dijkstra.pair(g, a, b), (kind, a, b)


def _lift_rows(dix, nodes):
    """A side's compact boundary row and ids for every node, with two
    rows appended: a sentinel-only side and the first side again with
    every sentinel slot's value set to 0.  Both hold finite values, so
    only the sentinel handling keeps the first +inf and the second
    equal to the first side."""
    a = dix.agent_of[jnp.asarray(nodes, jnp.int32)]
    f = dix.frag_of[a]
    row, ids = dix.brow[f, dix.pos_in_frag[a]], dix.bnd_super[f]
    sent = dix.sf_of[0].shape[0] - 1
    ids = jnp.concatenate([ids, jnp.full_like(ids[:1], sent), ids[:1]])
    row = jnp.concatenate([row, jnp.zeros_like(row[:1]),
                           jnp.where(ids[:1] == sent, 0.0, row[:1])])
    return row, ids


def _has_loop(jaxpr) -> bool:
    """Whether a jaxpr runs a loop: a while, or the scan a fori_loop
    with static bounds traces to."""
    return re.search(r"\b(while|scan)\[", str(jaxpr)) is not None


@pytest.mark.parametrize("which", ["deep", "four"])
def test_hier_lift_contraction_equals_gather(which, request):
    """_lift_compact's grouped-product layout (Pallas, interpret mode)
    is bit-equal to its gather layout at every level, on sides with
    sentinel slots and on sentinel-only sides (+inf), and its program
    runs no loop: the gather's chunk loop is gone."""
    fix = request.getfixturevalue(which)
    g, dix = (fix[0], fix[1]) if which == "deep" else (fix[0], fix[2])
    rng = np.random.default_rng(13)
    frag = np.asarray(dix.frag_of)[np.asarray(dix.agent_of)]
    bs = np.asarray(dix.bnd_super)
    padded = np.nonzero((bs == dix.sf_of[0].shape[0] - 1).any(axis=1))[0]
    # node 0 of the batch lies in a fragment with sentinel slots
    nodes = np.r_[np.nonzero(frag == padded[0])[0][:1],
                  rng.choice(np.nonzero(frag >= 0)[0], 80)]
    row, ids = _lift_rows(dix, nodes)
    for li in range(len(dix.sf_of)):
        grp, pos = dix.sf_of[li][ids], dix.pos_in_sf[li][ids]
        assert (np.asarray(grp)[0] != np.asarray(grp)[0, 0]).any(), li
        ref = np.asarray(_lift_compact(dix, li, row, grp, pos, force="ref"))
        kern = np.asarray(_lift_compact(dix, li, row, grp, pos,
                                        force="pallas"))
        np.testing.assert_array_equal(kern, ref)
        assert np.isinf(ref[-2]).all()
        np.testing.assert_array_equal(ref[-1], ref[0])
        assert np.isfinite(ref[:-2]).any(axis=1).all(), li

        def loops(force, li=li, row=row, grp=grp, pos=pos):
            return _has_loop(jax.make_jaxpr(lambda r: _lift_compact(
                dix, li, r, grp, pos, force=force))(row))

        assert not loops("pallas") and loops("ref")
        row, ids = jnp.asarray(ref), dix.bnd2_sid[li][grp[:, 0]]


def test_res_lift_contraction_equals_gather(deep):
    """_lift_res's grouped-product layout (Pallas, interpret mode) is
    bit-equal to its full-width gather on an index with resident rows,
    on every resident group, on a cold side (the sentinel row R: +inf)
    and on a side whose sentinel slots hold finite values (both layouts
    read them at position 0; a served row holds +inf there); its
    program runs no loop."""
    g, dix = deep
    R = dix.res_rows.shape[0] - 1
    assert R >= 2
    rng = np.random.default_rng(17)
    frag = np.asarray(dix.frag_of)[np.asarray(dix.agent_of)]
    nodes = rng.choice(np.nonzero(frag >= 0)[0], 80)
    row, ids = _lift_rows(dix, nodes)
    ridx = dix.res_of_frag[jnp.asarray(np.r_[frag[nodes], frag[nodes[:1]],
                                              frag[nodes[:1]]])]
    ridx = ridx.at[1].set(R)                      # a cold side
    assert np.unique(np.asarray(ridx)).size == R + 1
    pos = dix.pos_in_sf[0][ids]
    ref = np.asarray(_lift_res(dix, row, pos, ridx, force="ref"))
    kern = np.asarray(_lift_res(dix, row, pos, ridx, force="pallas"))
    np.testing.assert_array_equal(kern, ref)
    assert np.isinf(ref[1]).all()
    assert np.isfinite(ref[2:-2]).any(axis=1).all()
    sent = np.asarray(ids[:-2]) == dix.sf_of[0].shape[0] - 1
    assert sent.any() and np.isinf(np.asarray(row[:-2])[sent]).all()
    assert not _has_loop(jax.make_jaxpr(lambda r: _lift_res(
        dix, r, pos, ridx, force="pallas"))(row))


def test_ov_slot_map_matches_dense_table(built):
    """The sparse OvSlotMap (hierarchical epochs' sub-quadratic slot
    provenance) agrees with the dense overlay_slot_table on every
    adjacency pair, including min-merged parallel slots."""
    _g, _ix, (_d1, p1), _h = built
    dense = overlay_slot_table(p1)
    m = hierarchy.ov_slot_map(p1)
    S = p1.S
    adj = np.nonzero(dense >= 0)
    assert adj[0].size > 0
    for a, b in zip(*adj):
        ds = int(dense[a, b])
        ms = m.lookup(int(a), int(b))
        # both must name a slot of the same weight between (a, b)
        assert p1.sup_w[ms] == p1.sup_w[ds]
    # non-adjacent pair
    empty = np.nonzero(dense < 0)
    if empty[0].size:
        assert m.lookup(int(empty[0][0]), int(empty[1][0])) == -1
    assert m.lookup(S, S) == -1


def _paths_exact(engine, g, rng, n=120):
    s = rng.integers(0, g.n, n).astype(np.int32)
    t = rng.integers(0, g.n, n).astype(np.int32)
    dist, paths = engine.query_path(s, t)
    for i in range(n):
        want = dijkstra.pair(g, int(s[i]), int(t[i]))
        if np.isinf(want):
            assert paths[i] is None
            continue
        w = path_weight(g, paths[i])       # raises on a broken hop
        assert w == float(dist[i]) == want, (int(s[i]), int(t[i]))


def test_hier_paths_exact_across_levels():
    """Witness serving + host unwinding on the hierarchical overlay:
    every sampled path is edge-valid and its weight equals both the
    served distance and Dijkstra — overlay legs resolved through
    sf_next / d2_next / slot provenance across levels."""
    g = road_like(650, seed=21)
    engine = EpochedEngine(g, hierarchy_levels=2, paths=True)
    assert engine.dix.hierarchy_levels == 2
    _paths_exact(engine, g, np.random.default_rng(1))


def test_hier_refresh_differential():
    """Refresh == rebuild array-for-array on the hierarchical index,
    across jam/clear rounds, with exact serving and paths per epoch;
    an update touching no overlay weight carries the per-level tables
    by reference (no spurious re-close)."""
    g = road_like(600, seed=33)
    engine = EpochedEngine(g, hierarchy_levels=2, paths=True)
    rng = np.random.default_rng(4)
    for r in range(3):
        u, v, w = traffic_updates(engine.g, frac=0.05, seed=60 + r,
                                  localized=bool(r % 2))
        engine.apply_updates(u, v, w)
        sdix = build_device_index(reweight_index(engine.ix, engine.g),
                                  hierarchy_levels=2)
        eq = index_fields_equal(engine.dix, sdix, REFRESHED_FIELDS)
        bad = [f for f, ok in eq.items() if not ok]
        assert not bad, f"epoch {engine.epoch}: {bad}"
        _paths_exact(engine, engine.g, rng, n=40)
    # piece-only (or overlay-untouched) update: hier tables must be
    # the SAME arrays (immutability-based double buffering, no FW)
    plan = engine.plan
    fa = plan.frag_of
    inner = np.nonzero((fa[engine.g.edge_u] >= 0)
                       & (fa[engine.g.edge_u] == fa[engine.g.edge_v])
                       & (plan.piece_gid[engine.g.edge_u] < 0)
                       & (plan.piece_gid[engine.g.edge_v] < 0))[0]
    # pick an intra-fragment edge whose fragment has NO overlay slot
    # dependence change: re-assign its CURRENT weight (no-op update)
    e = inner[0]
    before = engine.dix
    engine.apply_updates(engine.g.edge_u[[e]], engine.g.edge_v[[e]],
                         engine.g.edge_w[[e]])
    for f in HIER_FIELDS:
        assert getattr(engine.dix, f) is getattr(before, f), f


def test_hier_refresh_rollback():
    """A failure mid-refresh must restore the hierarchy weight caches
    (sf_adj, l2_w) along with the level-1 ones, so the next refresh
    still composes to the scratch answer."""
    g = road_like(500, seed=9)
    engine = EpochedEngine(g, hierarchy_levels=2)
    plan = engine.plan
    before = [(h.sf_adj.copy(), h.l2_w.copy()) for h in plan.hier]
    u, v, w = traffic_updates(g, frac=0.05, seed=2)
    has_piece = any(plan.piece_gid[a] >= 0 or plan.piece_gid[b] >= 0
                    for a, b in zip(u, v))
    if has_piece:
        with pytest.raises(AttributeError):
            refresh_index(engine.dix, plan, object(), u, v, w)
        for h, (sf_adj_b, l2_w_b) in zip(plan.hier, before):
            np.testing.assert_array_equal(h.sf_adj, sf_adj_b)
            np.testing.assert_array_equal(h.l2_w, l2_w_b)
    engine.apply_updates(u, v, w)
    sdix = build_device_index(reweight_index(engine.ix, engine.g),
                              hierarchy_levels=2)
    eq = index_fields_equal(engine.dix, sdix, REFRESHED_FIELDS)
    assert all(eq.values()), [f for f, ok in eq.items() if not ok]


def test_overlay_bytes_accounting():
    """hier_overlay_stats reports the resident table bytes the exp10
    sub-quadratic claim is judged on."""
    g = road_like(700, seed=7)
    _dix, plan = build_device_index_with_plan(build_index(g),
                                              hierarchy_levels=2)
    stats = hierarchy.hier_overlay_stats(plan.hier, plan.S)
    h = plan.hier[0]
    nsf1 = h.nsf + 1
    want = (2 * nsf1 * h.m2 * h.m2 * 4 + nsf1 * h.m2 * h.mb2 * 4
            + 2 * (h.S2 + 1) ** 2 * 4)
    assert stats["overlay_bytes"] == want
    assert stats["overlay_dense_bytes"] == 2 * (plan.S + 1) ** 2 * 4
    assert stats["hierarchy_levels"] == 2 and stats["S"] == plan.S


def test_refresh_replace_keeps_sidecars():
    """dataclasses.replace drops host sidecars; refresh_index must
    re-attach provenance consistent with the epoch it publishes."""
    g = road_like(550, seed=13)
    engine = EpochedEngine(g, hierarchy_levels=2)
    u, v, w = traffic_updates(g, frac=0.05, seed=8)
    engine.apply_updates(u, v, w)
    assert isinstance(getattr(engine.dix, "host_ov_slot", None),
                      hierarchy.OvSlotMap)
    assert getattr(engine.dix, "host_l2_slot", None) is not None
    # and the dense path still carries its dense table
    eng1 = EpochedEngine(road_like(400, seed=2), hierarchy_levels=1)
    assert isinstance(eng1.dix.host_ov_slot, np.ndarray)


@pytest.fixture(scope="module")
def four():
    """hierarchy_levels=4: three grouping levels, each with at least
    two real groups, and the dense index of the same graph."""
    g = road_like(6000, seed=7)
    ix = build_index(g)
    dix4 = build_device_index(ix, hierarchy_levels=4)
    dix1 = build_device_index(ix, hierarchy_levels=1)
    assert len(dix4.sf_of) == 3
    assert all(c.shape[0] - 1 >= 2 for c in dix4.sf_closure)
    rng = np.random.default_rng(17)
    pairs = rng.integers(0, g.n, (96, 2)).astype(np.int32)
    return g, ix, dix4, dix1, pairs


def test_four_levels_serve_dijkstra(four):
    """Three grouping levels through QueryPlanner on the kernel layout
    (interpret mode): every sampled pair == float64 Dijkstra, and some
    pairs need each of the three levels' same-group legs."""
    g, _ix, dix4, _dix1, pairs = four
    got = QueryPlanner(dix4, force="pallas").query(pairs[:, 0],
                                                   pairs[:, 1])
    for (s, t), d in zip(pairs, got):
        assert float(d) == dijkstra.pair(g, int(s), int(t)), (s, t)
    frag = np.asarray(dix4.frag_of)[np.asarray(dix4.agent_of)]
    gs, gt = _side_groups(dix4, frag[pairs[:, 0]]), \
        _side_groups(dix4, frag[pairs[:, 1]])
    for li in range(3):
        assert ((gs[li] == gt[li]) & (gs[li] >= 0)).any(), li


def test_four_levels_equal_dense(four):
    """The four-level index answers array-equal to the dense closure,
    through the planner on the kernel layout."""
    _g, _ix, dix4, dix1, pairs = four
    s, t = pairs[:, 0], pairs[:, 1]
    np.testing.assert_array_equal(
        QueryPlanner(dix4, force="pallas").query(s, t),
        QueryPlanner(dix1, force="pallas").query(s, t))


def test_four_levels_refresh_equals_rebuild(four):
    """Refresh == rebuild on every per-level table of the three
    grouping levels and the top closure, across a jam and a clearance."""
    g = four[0]
    engine = EpochedEngine(g, hierarchy_levels=4)
    assert engine.dix.hierarchy_levels == 4
    for r in range(2):
        u, v, w = traffic_updates(engine.g, frac=0.03, seed=80 + r,
                                  localized=bool(r % 2))
        engine.apply_updates(u, v, w)
        sdix = build_device_index(reweight_index(engine.ix, engine.g),
                                  hierarchy_levels=4)
        eq = index_fields_equal(engine.dix, sdix, REFRESHED_FIELDS)
        assert not [f for f, ok in eq.items() if not ok], engine.epoch
