"""The planner on the profiler's clock (DESIGN.md §16): every planner
program has a module name of its own, the named scopes reach the op
metadata without changing the compiled code, and one query leaves the
span tree ``planner.query`` > ``planner.plan``, ``planner.bucket`` >
``planner.device_wait`` in the buffer and on the profiler's host
plane."""
import functools
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.device_engine import (build_device_index, serve_cross,
                                      serve_cross_res, serve_hub)
from repro.core import dist_engine
from repro.core.dist_engine import QueryPlanner, pad_pow2
from repro.core.graph import road_like
from repro.core.supergraph import build_index
from repro.kernels import ops
from repro.obs import trace as trace_mod

CASES = QueryPlanner.CASES
NAMES = ([f"planner_{c}" for c in CASES]
         + [f"planner_{c}_w" for c in CASES] + ["planner_hub"])


@pytest.fixture(scope="module")
def world():
    g = road_like(700, seed=5)
    dix = build_device_index(build_index(g), hierarchy_levels=2)
    planner = QueryPlanner(dix, paths=True)
    rng = np.random.default_rng(1)
    pairs = rng.integers(0, g.n, (200, 2)).astype(np.int32)
    return g, dix, planner, pairs


def _programs(planner) -> dict:
    progs = {f"planner_{c}": f for c, f in planner._fns.items()}
    progs.update({f"planner_{c}_w": f for c, f in planner._wfns.items()})
    progs["planner_hub"] = planner._hub_fn
    return progs


def _lower(fn, dix, m=8):
    z = jnp.zeros(m, jnp.int32)
    return fn.lower(dix, z, z)


@pytest.mark.parametrize("name", NAMES)
def test_each_planner_program_has_its_module_name(world, name):
    _, dix, planner, _ = world
    text = _lower(_programs(planner)[name], dix).as_text()
    assert text.startswith(f"module @jit_{name} ")
    assert "jit__unknown" not in text


@pytest.mark.parametrize("name,fn,kw", [
    ("planner_cross_frag", serve_cross, {"with_local": False}),
    ("planner_same_frag", serve_cross, {"with_local": True}),
    ("planner_cross_res", serve_cross_res, {}),
    ("planner_hub", serve_hub, {}),
])
def test_the_name_changes_nothing_but_the_name(world, name, fn, kw):
    """The named program lowers to the text an unnamed partial lowers
    to (``jit__unknown``), but for the module's name."""
    _, dix, planner, _ = world
    named = _lower(_programs(planner)[name], dix).as_text()
    bare = _lower(jax.jit(functools.partial(fn, force=None, **kw)),
                  dix).as_text()
    assert "jit__unknown" in bare
    assert named == bare.replace("jit__unknown", f"jit_{name}")


@pytest.mark.parametrize("name,scopes", [
    ("planner_cross_frag", ("hier_leg_l0", "lift_l0", "top_combine")),
    ("planner_same_frag", ("hier_leg_l0", "top_combine", "local_fold")),
    ("planner_cross_res", ("lift_res",)),
])
def test_named_scopes_reach_the_op_metadata(world, name, scopes):
    _, dix, planner, _ = world
    text = _lower(_programs(planner)[name], dix).as_text(debug_info=True)
    for scope in scopes:
        assert f"jit({name})/{scope}/" in text, scope


def _spans_of(call, s, t) -> list:
    """The default tracer's events of one ``call(s, t)``."""
    tr = trace_mod.get_tracer()
    tr.clear()
    tr.enable()
    try:
        call(s, t)
        return tr.drain()
    finally:
        tr.enable(False)
        tr.clear()


def test_one_query_leaves_the_planner_span_tree(world):
    _, _, planner, pairs = world
    events = _spans_of(planner.query, pairs[:, 0], pairs[:, 1])
    by_name: dict = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (query,), (plan,) = by_name["planner.query"], by_name["planner.plan"]
    qid = query["args"]["id"]
    assert query["args"]["n"] == len(pairs)
    assert plan["args"]["parent"] == qid
    buckets = by_name["planner.bucket"]
    assert all(b["args"]["parent"] == qid for b in buckets)
    assert sum(b["args"]["n"] for b in buckets) == len(pairs)
    assert {b["args"]["case"]: b["args"]["n"] for b in buckets} \
        == {c: k for c, k in planner.last_counts.items() if k}
    for b in buckets:
        assert b["args"]["padded"] == pad_pow2(b["args"]["n"])
    waits = by_name["planner.device_wait"]
    assert sorted(w["args"]["parent"] for w in waits) \
        == sorted(b["args"]["id"] for b in buckets)
    for w in waits:
        b = next(b for b in buckets if b["args"]["id"] == w["args"]["parent"])
        assert b["ts"] <= w["ts"] + 1e-3
        assert w["ts"] + w["dur"] <= b["ts"] + b["dur"] + 1e-3
    assert set(by_name) == {"planner.query", "planner.plan",
                            "planner.bucket", "planner.device_wait"}


def test_query_hub_spans_one_bucket(world):
    _, _, planner, pairs = world
    events = _spans_of(planner.query_hub, pairs[:5, 0], pairs[:5, 1])
    names = [e["name"] for e in events]
    assert names == ["planner.device_wait", "planner.bucket",
                     "planner.query"]
    wait, bucket, query = (e["args"] for e in events)
    assert bucket["case"] == "hub" and bucket["n"] == 5
    assert bucket["padded"] == pad_pow2(5) and query["n"] == 5
    assert wait["parent"] == bucket["id"]
    assert bucket["parent"] == query["id"]


def _level0_group_of_frag(dix) -> np.ndarray:
    """Each fragment's level-0 group, from all its valid boundary
    slots (-1: no boundary)."""
    sf_of = np.asarray(dix.sf_of[0])
    ids = np.asarray(dix.bnd_super)
    valid = ids != sf_of.shape[0] - 1
    grp = np.where(valid, sf_of[ids], sf_of.shape[0])
    return np.where(valid.any(axis=1), grp.min(axis=1), -1)


def test_leg_buckets_carry_same_group_counts(world):
    """Each bucket whose program runs the same-group legs is tagged
    ``same_l<li>``: how many of its pairs share the level-li group; the
    other buckets carry no such tag."""
    _, dix, planner, pairs = world
    s, t = pairs[:, 0], pairs[:, 1]
    events = _spans_of(planner.query, s, t)
    plan = planner.plan(s, t)
    grp = _level0_group_of_frag(dix)
    frag = np.asarray(dix.frag_of)[np.asarray(dix.agent_of)]
    buckets = [e["args"] for e in events if e["name"] == "planner.bucket"]
    assert {b["case"] for b in buckets} >= set(QueryPlanner.LEG_CASES)
    for b in buckets:
        legs = {k: v for k, v in b.items() if k.startswith("same_l")}
        if b["case"] not in QueryPlanner.LEG_CASES:
            assert legs == {}
            continue
        idx = plan[b["case"]]
        fs, ft = frag[s[idx]], frag[t[idx]]
        ok = (fs >= 0) & (ft >= 0)
        gs, gt = grp[np.where(ok, fs, 0)], grp[np.where(ok, ft, 0)]
        want = int(np.count_nonzero(ok & (gs == gt) & (gs >= 0)))
        assert legs == {"same_l0": want}
        if b["case"] == "same_frag":
            assert want == b["n"]


def test_leg_counts_cost_nothing_unrecorded(world, monkeypatch):
    """The same-group counts are computed only while spans are live."""
    _, _, planner, pairs = world
    calls = []
    real = dist_engine._same_group_counts
    monkeypatch.setattr(dist_engine, "_same_group_counts",
                        lambda *a: calls.append(a) or real(*a))
    tr = trace_mod.get_tracer()
    assert not tr.enabled and not trace_mod.recording()
    planner.query(pairs[:, 0], pairs[:, 1])
    assert calls == []
    _spans_of(planner.query, pairs[:, 0], pairs[:, 1])
    assert len(calls) == sum(1 for c in QueryPlanner.LEG_CASES
                             if planner.last_counts[c])


def test_leg_buckets_carry_leg_rows(world):
    """Each bucket whose program runs the same-group legs is tagged
    ``leg_rows``: the rows its grouped contraction streams, each real
    group's pairs rounded up to whole q tiles of the tile rule's rows,
    summed over groups (the world has one grouping level), never below
    the pairs that can win."""
    _, dix, planner, pairs = world
    s, t = pairs[:, 0], pairs[:, 1]
    events = _spans_of(planner.query, s, t)
    plan = planner.plan(s, t)
    grp = _level0_group_of_frag(dix)
    frag = np.asarray(dix.frag_of)[np.asarray(dix.agent_of)]
    bq = ops.GROUPED_TILE_ROWS
    buckets = [e["args"] for e in events if e["name"] == "planner.bucket"]
    assert {b["case"] for b in buckets} >= set(QueryPlanner.LEG_CASES)
    for b in buckets:
        if b["case"] not in QueryPlanner.LEG_CASES:
            assert "leg_rows" not in b
            continue
        idx = plan[b["case"]]
        fs, ft = frag[s[idx]], frag[t[idx]]
        ok = (fs >= 0) & (ft >= 0)
        gs = np.where(ok, grp[np.where(ok, fs, 0)], -1)
        gt = np.where(ok, grp[np.where(ok, ft, 0)], -1)
        n_g = [int(np.count_nonzero((gs == g) & (gt == g)))
               for g in range(np.asarray(dix.sf_closure[0]).shape[0] - 1)]
        assert b["leg_rows"] == sum(-(-n // bq) * bq for n in n_g)
        assert b["leg_rows"] >= b["same_l0"]


def test_leg_rows_cost_nothing_unrecorded(world, monkeypatch):
    """``leg_rows`` is computed only while spans are live."""
    _, _, planner, pairs = world
    calls = []
    real = dist_engine._leg_rows
    monkeypatch.setattr(dist_engine, "_leg_rows",
                        lambda *a: calls.append(a) or real(*a))
    assert not trace_mod.recording()
    planner.query(pairs[:, 0], pairs[:, 1])
    assert calls == []
    _spans_of(planner.query, pairs[:, 0], pairs[:, 1])
    assert len(calls) == sum(1 for c in QueryPlanner.LEG_CASES
                             if planner.last_counts[c])


def test_nothing_is_buffered_without_a_tracer_or_profiler(world):
    _, _, planner, pairs = world
    tr = trace_mod.get_tracer()
    tr.clear()
    assert not tr.enabled
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert trace_mod.span("planner.query") is trace_mod._NULL_SPAN
    planner.query(pairs[:, 0], pairs[:, 1])
    assert tr.events() == []


def test_a_profile_holds_the_planner_spans(world, tmp_path):
    """A CPU profiler capture: the planner's spans sit on the host
    plane, nested inside the caller's annotation, and the disabled
    tracer's buffer took them too."""
    from jax.profiler import ProfileData

    _, _, planner, pairs = world
    tr = trace_mod.get_tracer()
    tr.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("caller.batch"):
            planner.query(pairs[:, 0], pairs[:, 1])
    finally:
        jax.profiler.stop_trace()
        buffered = tr.drain()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events]
    spans = {}
    for name, a, b in host:
        if name.startswith(("planner.", "caller.")):
            spans.setdefault(name, []).append((a, b))
    (batch,) = spans["caller.batch"]
    (query,) = spans["planner.query"]
    assert batch[0] <= query[0] and query[1] <= batch[1]
    for name in ("planner.plan", "planner.bucket", "planner.device_wait"):
        assert spans[name]
        for a, b in spans[name]:
            assert query[0] <= a and b <= query[1]
    assert len(spans["planner.bucket"]) \
        == sum(1 for k in planner.last_counts.values() if k)
    assert sorted(e["name"] for e in buffered if e["name"] ==
                  "planner.query") == ["planner.query"]
