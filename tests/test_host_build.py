"""Staged host build pipeline tests (DESIGN.md §17).

Four contracts pinned here:

* the fragment substrate — ``Graph.subgraph`` / ``extract_fragments`` /
  the shared-CSR views — round-trips ids and weights and is
  deterministic under input permutation (property tests, padding-suite
  style);
* serial parity: ``build_index(build_workers=N)`` is array-equal to the
  serial build on every ``DislandIndex`` table, and the ``DeviceIndex``
  built from each agrees field for field (the differential behind the
  "workers only relocate work" claim);
* the streaming handoff: ``start_build`` exposes a structurally
  complete index before the covers land, and ``finish`` fills the same
  object in place, idempotently;
* the failure contract: a fragment cover that raises surfaces the
  original exception from ``finish`` with the pool reaped and the
  shared block released — no hang, no orphaned shared memory.
"""
import dataclasses
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.device_engine import build_device_index, index_fields_equal
from repro.core.graph import Graph, random_graph, road_like
from repro.core.landmarks import hybrid_cover
from repro.core.supergraph import (_graph_equal, build_index,
                                   index_arrays_equal, start_build)


def _edge_dict(eu, ev, ew):
    return {(int(a), int(b)): float(w) for a, b, w in zip(eu, ev, ew)}


# ---------------------------------------------------------------------------
# fragment substrate properties
# ---------------------------------------------------------------------------
@given(st.integers(0, 10_000))
@settings(max_examples=25)
def test_subgraph_round_trip(seed):
    """G[nodes] contains exactly the induced edges, weights intact,
    with old_ids mapping local ids back to the originals."""
    g = random_graph(60, 90, seed=seed)
    rng = np.random.default_rng(seed + 1)
    nodes = np.flatnonzero(rng.random(g.n) < 0.6).astype(np.int32)
    sg, ids = g.subgraph(nodes)
    assert np.array_equal(ids, np.unique(nodes))
    sel = np.zeros(g.n, dtype=bool)
    sel[nodes] = True
    both = sel[g.edge_u] & sel[g.edge_v]
    want = _edge_dict(g.edge_u[both], g.edge_v[both], g.edge_w[both])
    got = _edge_dict(ids[sg.edge_u], ids[sg.edge_v], sg.edge_w)
    assert got == want


@given(st.integers(0, 10_000))
@settings(max_examples=25)
def test_subgraph_deterministic_under_permutation(seed):
    """Shuffling (or duplicating) the node list changes nothing: the
    worker-side re-extraction leans on this canonicalization."""
    g = random_graph(50, 80, seed=seed)
    rng = np.random.default_rng(seed + 2)
    nodes = np.flatnonzero(rng.random(g.n) < 0.5)
    scrambled = rng.permutation(np.concatenate([nodes, nodes]))
    a, ida = g.subgraph(nodes)
    b, idb = g.subgraph(scrambled)
    assert np.array_equal(ida, idb)
    assert _graph_equal(a, b)


@given(st.integers(0, 10_000))
@settings(max_examples=25)
def test_extract_fragments_matches_per_label_subgraph(seed):
    """The batched extraction equals k independent ``subgraph`` calls —
    the equivalence fragment_stage and the cover workers both rest on."""
    g = random_graph(70, 110, seed=seed)
    rng = np.random.default_rng(seed + 3)
    k = int(rng.integers(1, 7))
    labels = rng.integers(0, k, g.n)
    labels[:k] = np.arange(k)        # every fragment non-empty label id
    frags = g.extract_fragments(labels)
    assert len(frags) == k
    for i, (fg, fids) in enumerate(frags):
        want_g, want_ids = g.subgraph(np.flatnonzero(labels == i))
        assert np.array_equal(fids, want_ids)
        assert _graph_equal(fg, want_g)


def test_extract_fragments_rejects_bad_labels():
    g = random_graph(10, 15, seed=0)
    with pytest.raises(ValueError, match="every node"):
        g.extract_fragments(np.zeros(g.n - 1, dtype=np.int64))
    bad = np.zeros(g.n, dtype=np.int64)
    bad[3] = -1
    with pytest.raises(ValueError, match="complete partition"):
        g.extract_fragments(bad)


@given(st.integers(0, 10_000))
@settings(max_examples=10)
def test_shared_csr_round_trip(seed):
    """to_shared/from_shared: zero-copy views equal the source arrays,
    are read-only, and support the worker-side subgraph re-extraction."""
    g = random_graph(40, 60, seed=seed)
    handle = g.to_shared()
    try:
        attached = Graph.from_shared(handle.meta)
        try:
            sg = attached.graph
            assert _graph_equal(g, sg)
            assert not sg.indices.flags.writeable
            with pytest.raises(ValueError):
                sg.edge_w[0] = 99.0
            # a worker's view supports fragment extraction unchanged
            nodes = np.arange(0, g.n, 2, dtype=np.int32)
            a, _ = g.subgraph(nodes)
            b, _ = sg.subgraph(nodes)
            assert _graph_equal(a, b)
        finally:
            attached.close()
    finally:
        handle.close()
        handle.unlink()


# ---------------------------------------------------------------------------
# serial parity differential (the tentpole's acceptance contract)
# ---------------------------------------------------------------------------
def _assert_parity(g, workers):
    serial = build_index(g)
    parallel = build_index(g, build_workers=workers)
    eq = index_arrays_equal(serial, parallel)
    assert all(eq.values()), \
        f"workers={workers} diverges from serial on " \
        f"{[k for k, v in eq.items() if not v]}"
    return serial, parallel


def test_parallel_build_matches_serial_road4000():
    g = road_like(4000, seed=0)
    serial, parallel = _assert_parity(g, workers=2)
    _assert_parity(g, workers=4)
    # the DeviceIndex is a pure function of the host index, but pin the
    # end product too: every device table field-equal between the two
    dser = build_device_index(serial)
    dpar = build_device_index(parallel)
    names = [f.name for f in dataclasses.fields(dser)]
    deq = index_fields_equal(dser, dpar, names)
    assert all(deq.values()), \
        f"device tables diverge on {[k for k, v in deq.items() if not v]}"


@pytest.mark.skipif(os.environ.get("CHECK_SKIP_SCALE") == "1",
                    reason="road64k differential skipped "
                           "(CHECK_SKIP_SCALE=1)")
def test_parallel_build_matches_serial_road64k():
    """Scale leg of the parity differential (full check runs only).
    Host tables only: the device build is a pure function of the host
    index (pinned at road4000 above), and a road64k device FW closure
    is minutes of CPU — the host differential is what workers touch."""
    g = road_like(64_000, seed=0)
    _assert_parity(g, workers=8)


# ---------------------------------------------------------------------------
# streaming handoff
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("workers", [1, 2])
def test_streaming_handoff_fills_index_in_place(workers):
    g = road_like(1000, seed=1)
    hb = start_build(g, build_workers=workers)
    six = hb.structural_index()
    # structurally complete: everything the device build reads exists
    assert six.super_graph is None
    assert six.fragments and all(f.cover is None for f in six.fragments)
    assert six.shrink is not None and six.partition is not None
    ix = hb.finish()
    assert ix is six
    assert ix.super_graph is not None
    assert all(f.cover is not None for f in ix.fragments)
    assert "hybrid_covers" in ix.timings
    assert hb.finish() is ix                      # idempotent
    # and the streamed product equals the one-shot build
    eq = index_arrays_equal(ix, build_index(g))
    assert all(eq.values())


# ---------------------------------------------------------------------------
# worker failure contract
# ---------------------------------------------------------------------------
class _InjectedCoverFailure(RuntimeError):
    pass


def _boom_cover(fg, boundary_local, use_cost_model):
    # deterministic: every fragment with a real boundary fails, so the
    # first completed future raises regardless of scheduling order
    if boundary_local.size >= 2:
        raise _InjectedCoverFailure(
            f"injected cover failure ({boundary_local.size} boundary)")
    return hybrid_cover(fg, boundary_local, use_cost_model)


def _shm_names():
    try:
        return {f for f in os.listdir("/dev/shm") if f.startswith("psm_")}
    except FileNotFoundError:            # non-Linux: skip the leak check
        return set()


@pytest.mark.parametrize("workers", [1, 4])
def test_failed_cover_surfaces_original_exception(workers):
    """A raising fragment cover must fail the build promptly with the
    original exception — futures cancelled, pool reaped, shared block
    released — for both the serial and the worker-pool paths."""
    g = road_like(1000, seed=2)
    before = _shm_names()
    with pytest.raises(_InjectedCoverFailure, match="injected"):
        build_index(g, build_workers=workers, cover_fn=_boom_cover)
    assert _shm_names() <= before        # no leaked shared-memory block


# ---------------------------------------------------------------------------
# host independence
# ---------------------------------------------------------------------------
_DIGEST = """
import hashlib, sys
sys.path.insert(0, %r)
from repro.core.graph import road_like
from repro.core.supergraph import build_index
ix = build_index(road_like(4000, seed=5))
h = hashlib.sha1()
for a in (ix.partition.labels, ix.frag_of, ix.shrink_ids):
    h.update(a.tobytes())
print(h.hexdigest())
"""


def test_build_is_independent_of_simd_dispatch():
    """numpy sorts ties differently on each SIMD path (AVX-512 or not),
    so an unstable sort in the build makes the index depend on the
    host's CPU.  The build with numpy's AVX-512 kernels turned off must
    equal the build with whatever this CPU offers."""
    import subprocess
    import sys

    from numpy._core._multiarray_umath import __cpu_features__

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    prog = _DIGEST % src
    off = " ".join(f for f, on in __cpu_features__.items()
                   if on and f.startswith("AVX512"))
    runs = [subprocess.run([sys.executable, "-c", prog], env=env,
                           capture_output=True, text=True, timeout=300)
            for env in (dict(os.environ),
                        dict(os.environ, NPY_DISABLE_CPU_FEATURES=off))]
    for r in runs:
        assert r.returncode == 0, r.stderr
    assert runs[0].stdout == runs[1].stdout
