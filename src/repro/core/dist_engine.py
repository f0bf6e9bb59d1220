"""Distributed DISLAND serving + offline build (shard_map) + planner.

Serving layout (production posture, DESIGN.md §5): the index tensors are
*replicated* — on 16 GB chips the index is ~1/2 the input graph, so every
device holds it and the query batch is sharded across the whole mesh
(pure DP; zero query-time collectives; linear scaling with chips).

The QueryPlanner is the host-side front end: it buckets each incoming
batch by case (same-DRA / same-fragment / cross-fragment) and runs one
specialized jitted program per bucket, so same-DRA queries never pay
for the SUPER combine and cross-fragment queries never touch the piece
tables (DESIGN.md §5).  It also fronts the hub-label hot tier
(DESIGN.md §15): ``hub_mask`` gates pairs both of whose endpoints
carry labels in the pinned epoch, ``query_hub`` answers them with one
O(W) label merge — NOT a planner case; the serving runtime dispatches
it, and ``query`` stays the untouched differential reference the
merge must equal bit-for-bit.

Owned invariants: ``plan()``'s buckets cover every query exactly once;
``set_index`` publishes the epoch's host maps atomically (one tuple
swap); warmup compiles every executable any flush can request, so an
epoch swap never pays XLA compile in its tail latency (DESIGN.md §9).

Offline build is the heavy part (batched FW over fragments, batched BF
over SUPER sources): both are sharded over their batch dimension with a
shard_map, which is where the multi-pod mesh earns its keep.
"""
from __future__ import annotations

import functools
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops
from ..obs import trace
from . import padding, refresh_pipeline, sssp
from .device_engine import (DeviceIndex, RefreshStats,
                            build_device_index_with_plan, leg_tiles,
                            refresh_index,
                            serve_cross, serve_cross_res, serve_cross_w,
                            serve_hub, serve_same_dra, serve_same_dra_w,
                            serve_step, warmup_refresh)
from .paths import PathUnwinder
from .supergraph import DislandIndex, start_build


# ---------------------------------------------------------------------------
# query planner
# ---------------------------------------------------------------------------
# canonical padding rules live in core/padding.py; the planner's
# bucket rule and the serving scheduler's occupancy histogram share
# this exact spelling (scheduler imports pad_pow2 from here)
_pad_pow2 = padding.pad_pow2
pad_pow2 = padding.pad_pow2


def _program(name: str, fn, **kw):
    """``jax.jit`` of ``fn`` with ``kw`` bound, named ``name``: its XLA
    module is ``jit_<name>`` in a profile (a bare partial's would be
    ``jit__unknown``).  The name changes nothing in the compiled code."""
    p = functools.partial(fn, **kw)
    p.__name__ = p.__qualname__ = name
    return jax.jit(p)


def _frag_groups(dix: DeviceIndex) -> tuple:
    """Per grouping level, fragment -> its level-li group (-1 for a
    fragment with no boundary), read off the index's own arrays: a
    fragment's level-0 group is that of its first boundary slot, and a
    level-li group's group one level up that of the first id of its
    bnd2_sid row (slots are valid-first; groups nest)."""
    ids = np.asarray(dix.bnd_super)[:, 0]
    out = []
    for li in range(len(dix.sf_of)):
        sf_of = np.asarray(dix.sf_of[li])
        grp = sf_of[ids]                       # sentinel id -> sentinel group
        out.append(np.where(grp == sf_of[-1], -1, grp))
        ids = np.asarray(dix.bnd2_sid[li])[grp, 0]
    return tuple(out)


def _host_maps(dix: DeviceIndex) -> tuple:
    """(dix, agent_of, frag_of, res_frag, topgrp, hub_agent, groups):
    the host copies the planner buckets, gates and tags with."""
    return (dix, np.asarray(dix.agent_of), np.asarray(dix.frag_of),
            getattr(dix, "host_res_frag", None),
            getattr(dix, "host_topgrp_frag", None),
            getattr(dix, "host_hub_agent", None), _frag_groups(dix))


def _same_group_counts(s, t, maps) -> list[np.ndarray]:
    """Per grouping level, the number of (s, t) pairs per real level-li
    group whose two fragments share it: the rows of each level's
    same-group contraction that can do useful work ([] on a dense
    epoch)."""
    _, agent_of, frag_of, *_, groups = maps
    if not groups:                                 # dense epoch
        return []
    fs, ft = frag_of[agent_of[s]], frag_of[agent_of[t]]
    valid = (fs >= 0) & (ft >= 0)
    fs, ft = np.where(valid, fs, 0), np.where(valid, ft, 0)
    out = []
    for g in groups:
        same = valid & (g[fs] == g[ft]) & (g[fs] >= 0)
        out.append(np.bincount(g[fs][same], minlength=int(g.max()) + 1))
    return out


def _leg_rows(counts: list) -> dict:
    """{"leg_rows": rows the same-group legs stream for a bucket whose
    pairs per real group and level are ``counts``
    (_same_group_counts)}: on an accelerator each level packs the rows
    of group g into ceil(n_g / bq) tiles of bq rows (leg_tiles, the
    device schedule's own rule), so the sum over levels and groups of
    tiles x bq, never below the pairs counted; {} on a dense epoch.
    Beside ``same_l<li>`` it gives the share of those rows that does
    useful work."""
    if not counts:
        return {}
    return {"leg_rows": _tile_rows(counts)}


def _tile_rows(counts: list) -> int:
    """The rows grouped contractions stream, one per array of
    ``counts`` (rows per real key): each key's rows in ceil(n / bq)
    tiles of bq rows (leg_tiles, the device schedule's own rule)."""
    rows = 0
    for c in counts:
        bq, tiles = leg_tiles(c)
        rows += int(tiles.sum()) * bq
    return rows


def _lift_counts(s, t, maps, case: str) -> list[np.ndarray]:
    """Per grouped lift of a ``case`` bucket's program, the number of
    its (s, t) pairs per real key: each side at each grouping level,
    keyed by that side's group (cross_res: each side's one resident
    lift, keyed by its resident group).  Pairs with a side outside
    every fragment are keyless, as on the device.  [] on a dense
    epoch."""
    _, agent_of, frag_of, res_frag, _, _, groups = maps
    keys = [res_frag] if case == "cross_res" else groups
    if not groups or keys[0] is None:
        return []
    fs, ft = frag_of[agent_of[s]], frag_of[agent_of[t]]
    valid = (fs >= 0) & (ft >= 0)
    out = []
    for key in keys:
        for f in (fs, ft):
            k = key[np.where(valid, f, 0)]
            out.append(np.bincount(k[valid & (k >= 0)]))
    return out


class QueryPlanner:
    """Bucket a query batch by case and dispatch per-case programs.

    Bucket sizes are padded to powers of two (self-queries as filler)
    so each sub-program compiles for O(log batch) distinct shapes.

    The index is passed to each jitted sub-program as an *argument*,
    not closed over: an epoch swap (``set_index``) is then just a
    pointer replacement — the new epoch's tensors have identical
    shapes/dtypes, so every cached executable is reused and no XLA
    compile lands anywhere near the serving path (DESIGN.md §9).
    """

    CASES = ("same_dra", "same_frag", "cross_frag", "cross_res")
    #: the cases whose distance programs run the per-level same-group
    #: legs (_combine_mid_h); their planner.bucket spans carry same_l<li>
    #: and leg_rows
    LEG_CASES = ("same_frag", "cross_frag")
    #: the cases whose distance programs run grouped lifts
    #: (_lift_compact, _lift_res); their planner.bucket spans carry
    #: lift_rows
    LIFT_CASES = ("same_frag", "cross_frag", "cross_res")

    def __init__(self, dix: DeviceIndex, *, force=None,
                 paths: bool = False):
        self._fns = {
            "same_dra": _program("planner_same_dra", serve_same_dra),
            "same_frag": _program("planner_same_frag", serve_cross,
                                  with_local=True, force=force),
            "cross_frag": _program("planner_cross_frag", serve_cross,
                                   with_local=False, force=force),
            # resident fast path: both endpoints in pre-lifted hot
            # super-fragments of *different* top-level groups, so the
            # whole query is one fused twoside against the top closure
            "cross_res": _program("planner_cross_res", serve_cross_res,
                                  force=force),
        }
        # hub-label hot tier (DESIGN.md §15): NOT a planner case — the
        # serving runtime gates pairs with hub_mask and dispatches them
        # through query_hub, above/instead of the planner.  Jitting is
        # free until called, so the program exists on every index and
        # warmup() only compiles it when the epoch carries real labels.
        self._hub_fn = _program("planner_hub", serve_hub, force=force)
        # witness-returning (return_witness mode) sub-programs; jit
        # wrappers are free until called, so these always exist and
        # ``paths`` only decides whether warmup() compiles them.
        # cross_res deliberately maps to the full-lift witness program:
        # the resident rows re-associate f32 min-plus, so an argmin over
        # them may disagree with the unwinder's exact re-find — witness
        # queries keep the exact pipeline (distances are equal anyway)
        self._wfns = {
            "same_dra": _program("planner_same_dra_w", serve_same_dra_w),
            "same_frag": _program("planner_same_frag_w", serve_cross_w,
                                  with_local=True, force=force),
            "cross_frag": _program("planner_cross_frag_w", serve_cross_w,
                                   with_local=False, force=force),
            "cross_res": _program("planner_cross_res_w", serve_cross_w,
                                  with_local=False, force=force),
        }
        self.paths = paths
        self.last_counts: dict = {}
        self.set_index(dix)

    def set_index(self, dix: DeviceIndex) -> None:
        """Publish a new index epoch.  In-flight batches keep the old
        arrays alive (immutable); subsequent calls plan and serve
        against the new epoch with zero recompilation."""
        self.dix = dix
        # partition maps cached as one tuple keyed by index identity,
        # so an explicitly pinned dispatch (query(dix=...)) can always
        # bucket with ITS epoch's maps even if this publish lands
        # mid-flush (weight-only refreshes share these arrays across
        # epochs, but the epoch-pin contract must not depend on that)
        self._maps = _host_maps(dix)

    def _maps_of(self, dix: DeviceIndex | None) -> tuple:
        """The host maps of ``dix`` (default: current epoch): the
        cached tuple, or — pinned to an epoch that is no longer
        current, only reachable when a publish lands between the pin
        and this dispatch — derived afresh from that index."""
        cached = self._maps          # single atomic read of the tuple
        if dix is None or cached[0] is dix:
            return cached
        return _host_maps(dix)

    @staticmethod
    def bucket_sizes(batch_size: int) -> list[int]:
        """The padded (pow2) bucket sizes a batch of ``batch_size`` can
        produce — exactly the shapes ``warmup`` compiles.  Introspection
        hook for the serving runtime: a micro-batcher that caps its
        flushes at ``bucket_sizes(b)[-1]`` never triggers a fresh XLA
        compile, and the occupancy histogram buckets by these sizes."""
        m = _pad_pow2(1)
        sizes = []
        while m <= _pad_pow2(batch_size):
            sizes.append(m)
            m *= 2
        return sizes

    def warmup(self, batch_size: int) -> None:
        """Compile every sub-program at every padded bucket size that a
        batch of ``batch_size`` can produce, so no XLA compile lands in
        the serving (timed) path."""
        sizes = self.bucket_sizes(batch_size)
        z = np.zeros(max(sizes), np.int32)
        # the resident program only exists on indices that carry real
        # pre-lifted rows (shape[0] > 1; the cold dummy is (1, 1, 1)) —
        # its bucket is provably empty otherwise, so skip the compile
        has_res = np.asarray(self.dix.res_rows).shape[0] > 1
        fns = [fn for case, fn in self._fns.items()
               if has_res or case != "cross_res"]
        if self.paths:
            fns += [fn for case, fn in self._wfns.items()
                    if has_res or case != "cross_res"]
        # same guard for the hub tier: the label program only exists on
        # epochs carrying real rows (the cold dummy is (1, 1))
        if np.asarray(self.dix.hub_rows).shape[0] > 1:
            fns = fns + [self._hub_fn]
        for fn in fns:
            for size in sizes:
                jax.block_until_ready(fn(self.dix, jnp.asarray(z[:size]),
                                         jnp.asarray(z[:size])))

    def plan(self, s: np.ndarray, t: np.ndarray,
             dix: DeviceIndex | None = None) -> dict:
        """-> {case: index array} partition of the batch, bucketed by
        ``dix``'s own membership maps (default: current epoch)."""
        _, agent_of, frag_of, res_frag, topgrp, _, _ = self._maps_of(dix)
        us, ut = agent_of[s], agent_of[t]
        fs, ft = frag_of[us], frag_of[ut]
        case1 = us == ut
        case2 = ~case1 & (fs == ft)
        case3 = ~case1 & ~case2
        if res_frag is not None and topgrp is not None:
            # hot split of cross_frag: both fragments pre-lifted AND in
            # different top-level groups (the exactness gate for the
            # resident rows: nested grouping means different top groups
            # imply different groups at every level, so no same-group
            # leg can shortcut the route and the pre-composed lift
            # covers the confined prefix completely)
            valid = (fs >= 0) & (ft >= 0)
            hot = case3 & valid & (res_frag[np.where(valid, fs, 0)] >= 0) \
                & (res_frag[np.where(valid, ft, 0)] >= 0) \
                & (topgrp[np.where(valid, fs, 0)]
                   != topgrp[np.where(valid, ft, 0)])
            case3 = case3 & ~hot
        else:
            hot = np.zeros(s.shape, bool)
        return {
            "same_dra": np.nonzero(case1)[0],
            "same_frag": np.nonzero(case2)[0],
            "cross_frag": np.nonzero(case3)[0],
            "cross_res": np.nonzero(hot)[0],
        }

    def hub_mask(self, s: np.ndarray, t: np.ndarray,
                 dix: DeviceIndex | None = None) -> np.ndarray:
        """Host-side gate for the hub-label hot tier (DESIGN.md §15):
        True where both endpoints' agents are labeled AND the exactness
        gate holds — different fragments, and on hierarchical epochs
        different TOP groups (only then must every route touch the top
        boundary the labels enumerate).  Everything else falls through
        to the planner.  Reads the same atomically-published map tuple
        as plan(), so a pinned dispatch gates with ITS epoch's labels."""
        dix_, agent_of, frag_of, _, topgrp, hub_agent, _ = \
            self._maps_of(dix)
        s = np.asarray(s, np.int64)
        t = np.asarray(t, np.int64)
        if hub_agent is None:
            return np.zeros(s.shape, bool)
        us, ut = agent_of[s], agent_of[t]
        fs, ft = frag_of[us], frag_of[ut]
        ok = ((s != t) & (fs >= 0) & (ft >= 0) & (fs != ft)
              & (hub_agent[us] >= 0) & (hub_agent[ut] >= 0))
        if len(dix_.sf_of) > 0:
            # hierarchical: same-top-group routes may never touch the
            # top boundary — the labels are silent about them
            if topgrp is None:
                return np.zeros(s.shape, bool)
            ok &= (topgrp[np.where(ok, fs, 0)]
                   != topgrp[np.where(ok, ft, 0)])
        return ok

    def query_hub(self, s, t, *, dix: DeviceIndex | None = None
                  ) -> np.ndarray:
        """Vectorized hub-label merge for hub_mask-gated pairs — one
        pow2-padded program (label gathers + O(W) merge), bypassing the
        planner's case split entirely.  A mis-gated pair gathers the
        all-INF sentinel row and returns +inf, never a wrong finite
        distance.  Bit-equal to query() on gated pairs (the §15
        differential harness pins this)."""
        dix = self.dix if dix is None else dix
        s = np.asarray(s, np.int32)
        t = np.asarray(t, np.int32)
        if s.size == 0:
            return np.zeros(s.shape, np.float32)
        m = _pad_pow2(s.size)
        with trace.span("planner.query", n=int(s.size)), \
                trace.span("planner.bucket", case="hub", n=int(s.size),
                           padded=m):
            sp = np.zeros(m, np.int32)
            tp = np.zeros(m, np.int32)
            sp[:s.size] = s
            tp[:t.size] = t
            res = self._hub_fn(dix, jnp.asarray(sp), jnp.asarray(tp))
            with trace.span("planner.device_wait"):
                res = np.asarray(res)
            return res[:s.size]

    def _dispatch(self, fns, s, t, outs, dix=None) -> None:
        """Shared bucket/pad/dispatch loop: partition (s, t), pad each
        bucket to a power of two, run its sub-program from ``fns`` and
        scatter every output array into the matching array of ``outs``.

        ``dix`` pins the epoch; defaulting to the planner's current
        pointer, read ONCE so a concurrent set_index between bucket
        dispatches cannot split one batch across two epochs.

        Spans (DESIGN.md §16): ``planner.query`` over the call,
        ``planner.plan`` over the bucketing, one ``planner.bucket``
        per non-empty case (tags ``case``, ``n``, ``padded``) and
        inside it ``planner.device_wait`` over the blocking copy back;
        the planner's host time is the query span less its waits.
        """
        dix = self.dix if dix is None else dix
        with trace.span("planner.query", n=int(s.size)):
            with trace.span("planner.plan"):
                plan = self.plan(s, t, dix)
            self.last_counts = {c: int(ix.size) for c, ix in plan.items()}
            for case, idx in plan.items():
                if idx.size == 0:
                    continue
                m = _pad_pow2(idx.size)
                tags = {}
                if case in self.LEG_CASES and trace.recording():
                    counts = _same_group_counts(s[idx], t[idx],
                                                self._maps_of(dix))
                    tags = {**{f"same_l{li}": int(c.sum())
                               for li, c in enumerate(counts)},
                            **_leg_rows(counts)}
                if case in self.LIFT_CASES and trace.recording():
                    lifts = _lift_counts(s[idx], t[idx],
                                         self._maps_of(dix), case)
                    if lifts:       # the rows the grouped lifts stream
                        tags["lift_rows"] = _tile_rows(lifts)
                with trace.span("planner.bucket", case=case,
                                n=int(idx.size), padded=m, **tags):
                    sp = np.zeros(m, np.int32)
                    tp = np.zeros(m, np.int32)
                    sp[:idx.size] = s[idx]
                    tp[:idx.size] = t[idx]
                    res = fns[case](dix, jnp.asarray(sp), jnp.asarray(tp))
                    if len(outs) == 1:
                        res = (res,)
                    with trace.span("planner.device_wait"):
                        res = [np.asarray(r) for r in res]
                    for out, r in zip(outs, res):
                        out[idx] = r[:idx.size]

    def __call__(self, s, t) -> np.ndarray:
        return self.query(s, t)

    def query(self, s, t, *, dix=None) -> np.ndarray:
        """Planner-bucketed batched distances.  Pass ``dix`` to serve
        against an explicit epoch instead of the planner's current
        pointer — the serving runtime pins one epoch per micro-batch
        flush so a concurrent ``set_index`` cannot tear a flush across
        epochs (its cache entries are keyed to the same pin)."""
        s = np.asarray(s, np.int32)
        t = np.asarray(t, np.int32)
        out = np.full(s.shape, np.inf, np.float32)
        self._dispatch(self._fns, s, t, (out,), dix=dix)
        return out

    def query_witness(self, s, t, *, dix=None
                      ) -> tuple[np.ndarray, np.ndarray]:
        """Planner-bucketed return_witness serving -> (dist, wit).

        Same bucketing/padding as __call__, dispatching the witness
        sub-programs; wit follows the per-case encoding documented in
        device_engine (WIT_* / packed SUPER pair).  Self queries get
        distance 0 and WIT_NONE (nothing to unwind — the unwinder
        special-cases s == t first).  Pass ``dix`` to serve against an
        explicit epoch (EpochedEngine.query_path pairs it with the
        matching unwinder snapshot).
        """
        s = np.asarray(s, np.int32)
        t = np.asarray(t, np.int32)
        out = np.full(s.shape, np.inf, np.float32)
        wit = np.full(s.shape, -1, np.int32)
        self._dispatch(self._wfns, s, t, (out, wit), dix=dix)
        same = s == t
        out[same] = 0.0
        wit[same] = -1
        return out, wit


# ---------------------------------------------------------------------------
# epoch-swapped serving over a live-updating index
# ---------------------------------------------------------------------------
class EpochedEngine:
    """Serve batched queries while absorbing live edge-weight updates.

    Double-buffered epochs (DESIGN.md §9): queries always run against
    the current *immutable* DeviceIndex; ``apply_updates`` runs the
    incremental rebuild (device_engine.refresh_index) off to the side
    and then publishes the result as epoch e+1 with a single planner
    pointer swap.  Batches already in flight finish on epoch e — the
    old arrays stay alive exactly as long as something references them.

    Because the planner's jitted sub-programs take the index as an
    argument, an epoch swap compiles nothing; refresh cost is the only
    pause-free background work, and serving never blocks on it.
    """

    def __init__(self, g, *, c: int = 2, seed: int = 0, force=None,
                 ix: DislandIndex | None = None,
                 warm_refresh: bool = True, paths: bool = False,
                 hierarchy_levels: int | str = "auto",
                 resident_mb: float | str = "auto",
                 hub_nodes=None, build_workers: int = 1):
        self.g = g
        # streaming handoff (DESIGN.md §17): the device build needs only
        # the structural index (it never reads covers — make_build_plan
        # regathers all overlay weights from frag_apsp), so it runs
        # while the worker pool is still computing covers; finish()
        # joins them before the engine is returned to the caller.
        host_build = None
        if ix is None:
            host_build = start_build(g, c=c, seed=seed,
                                     build_workers=build_workers)
            ix = host_build.structural_index()
        self.ix = ix
        self.dix, self.plan = build_device_index_with_plan(
            self.ix, force=force, hierarchy_levels=hierarchy_levels,
            resident_mb=resident_mb, hub_nodes=hub_nodes)
        if host_build is not None:
            host_build.finish()
        self.planner = QueryPlanner(self.dix, force=force, paths=paths)
        self.epoch = 0
        # one-tuple publish (epoch, dix, graph, staleness): snapshot()
        # readers get a mutually consistent quadruple with a single
        # reference read, never a torn mix of old epoch number and new
        # index (or of an epoch and another epoch's staleness tag)
        self._published = (0, self.dix, self.g, refresh_pipeline.FRESH)
        self.force = force
        self.last_stats: RefreshStats | None = None
        # (dix, PathUnwinder) pair, replaced atomically (unwinder())
        self._unwinder: tuple | None = None
        self._lock = threading.Lock()
        if warm_refresh:
            # compile the refresh FW programs now, not mid-update
            warmup_refresh(self.plan, force=force)
            self._warm_refresh_path()

    def _warm_refresh_path(self) -> None:
        """Trace/compile the full delta path with a no-op update batch
        (existing edges re-assigned their current weights): exercises
        classification, the padded FW scatter/gather programs, and the
        piece rewrite, all without changing any distance — so the first
        real apply_updates runs entirely on warm programs."""
        plan = self.plan
        g = self.g
        fa = plan.frag_of
        picks: list = []
        # one edge in each of up to 8 distinct fragments (covers the
        # pow2-padded scatter shapes 4 and 8) ...
        m_frag = (fa[g.edge_u] >= 0) & (fa[g.edge_u] == fa[g.edge_v])
        e_frag = np.nonzero(m_frag)[0]
        if e_frag.size:
            _, first = np.unique(fa[g.edge_u[e_frag]], return_index=True)
            picks += list(e_frag[first[:8]])
        # ... and one edge in a piece of each bucket size in use
        gid_e = np.where(plan.piece_gid[g.edge_u] >= 0,
                         plan.piece_gid[g.edge_u],
                         plan.piece_gid[g.edge_v])
        e_piece = np.nonzero(gid_e >= 0)[0]
        if e_piece.size:
            _, first = np.unique(plan.piece_cap[gid_e[e_piece]],
                                 return_index=True)
            picks += list(e_piece[first])
        if not picks:
            return
        idx = np.asarray(sorted(set(picks)))
        refresh_index(self.dix, plan, g, g.edge_u[idx], g.edge_v[idx],
                      g.edge_w[idx], force=self.force)

    def query(self, s, t) -> np.ndarray:
        """Planner-bucketed batched queries on the current epoch."""
        return self.planner(s, t)

    def snapshot(self) -> tuple:
        """Atomic ``(epoch, dix, graph, staleness)`` read of the
        published state.

        The quadruple is replaced as one tuple by ``apply_updates``, so
        a reader can pin an epoch for a whole micro-batch flush — serve
        against ``dix``, key cache entries by ``epoch``, validate
        against ``graph``, tag responses with ``staleness`` — without
        holding any lock and without ever observing epoch e's number
        next to epoch e+1's arrays or another epoch's staleness tag.
        """
        return self._published

    def unwinder(self, dix: DeviceIndex | None = None) -> PathUnwinder:
        """A PathUnwinder paired with ``dix`` (default: the currently
        published epoch).  Cached by index identity, so repeated
        query_path calls within one epoch reuse the snapshot and a
        concurrent epoch publish can never mismatch witnesses with
        tables — the unwinder is keyed to the exact index object its
        witnesses were served from."""
        dix = self.dix if dix is None else dix
        cached = self._unwinder          # single atomic read: (dix, uw)
        if cached is not None and cached[0] is dix:
            return cached[1]
        uw = PathUnwinder(dix, self.plan)
        # publish as one tuple and return the locally built instance,
        # never the cache slot: a concurrent epoch publish may
        # overwrite the slot with another epoch's unwinder in between
        self._unwinder = (dix, uw)
        return uw

    def query_path(self, s, t) -> tuple[np.ndarray, list]:
        """Batched exact shortest *paths*.

        Returns (dist [q] f32, paths): paths[i] is the node sequence
        s_i -> t_i whose edge weights sum to exactly dist[i], or None
        when t_i is unreachable.  Distances come from the witness
        sub-programs (device); unwinding is host-side table chasing
        (DESIGN.md §10).  The epoch is pinned once: witnesses and
        unwinder both bind to the same index snapshot, so an
        apply_updates landing mid-call cannot tear them apart.
        """
        dix = self.planner.dix
        dist, wit = self.planner.query_witness(s, t, dix=dix)
        uw = self.unwinder(dix)
        return dist, uw.unwind_many(s, t, dist, wit)

    def warmup(self, batch_size: int) -> None:
        self.planner.warmup(batch_size)

    def apply_updates(self, u, v, w, *,
                      staleness: "refresh_pipeline.Staleness | None"
                      = None) -> RefreshStats:
        """Absorb a weight-update batch and publish the next epoch.

        Serving continues on the old epoch until the final swap; the
        lock only serializes concurrent updaters, never readers.
        ``staleness`` is the recency descriptor a staged caller
        (core.refresh_pipeline.RefreshPipeline) attaches to the
        published epoch; a direct (monolithic) call publishes a
        complete tag — the epoch reflects everything it was handed.
        """
        with self._lock:
            w_old = self.g.edge_w[self.g.edge_ids(u, v)]
            g_new = self.g.with_edge_weights(u, v, w)
            new_dix, stats = refresh_index(self.dix, self.plan, g_new,
                                           u, v, w, w_old=w_old,
                                           force=self.force)
            # an epoch publishes fully materialized: readers must never
            # stall on a lazily-executing refresh
            jax.block_until_ready(new_dix)
            # publish: readers see (epoch, dix) flip atomically per ref
            self.g = g_new
            self.dix = new_dix
            self.planner.set_index(new_dix)
            self.epoch += 1
            if staleness is None:
                prev = self._published[3]
                sub = max(prev.submitted, prev.watermark) + 1
                staleness = refresh_pipeline.Staleness(
                    watermark=sub, submitted=sub)
            self._published = (self.epoch, new_dix, g_new, staleness)
            self.last_stats = stats
            return stats


# ---------------------------------------------------------------------------
# sharded serving
# ---------------------------------------------------------------------------
def serve_sharded(mesh: Mesh, dix: DeviceIndex, s: jax.Array,
                  t: jax.Array, *,
                  batch_axes: Sequence[str] | None = None) -> jax.Array:
    """Batched queries sharded over ``batch_axes`` (default: all axes).

    The body is purely local (replicated index, no collectives), so the
    varying-manual-axes check is off: it would reject serve_step's
    loops, whose initial carries are fresh unvarying constants while
    their bodies mix in the sharded query rows."""
    axes = tuple(batch_axes) if batch_axes else tuple(mesh.axis_names)

    @functools.partial(
        shard_map, mesh=mesh, check_vma=False,
        in_specs=(P(), P(axes), P(axes)), out_specs=P(axes))
    def _local(dix_, s_, t_):
        return serve_step(dix_, s_, t_)

    return _local(dix, s, t)


def serve_jit(mesh: Mesh, dix_like, *,
              batch_axes: Sequence[str] | None = None):
    """jit'd sharded serve step with explicit in/out shardings, suitable
    for AOT lowering (dry-run).  ``dix_like`` is any DeviceIndex pytree
    (arrays or ShapeDtypeStructs) used to build the replicated specs."""
    axes = tuple(batch_axes) if batch_axes else tuple(mesh.axis_names)
    rep = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P(axes))
    dix_shardings = jax.tree_util.tree_map(lambda _: rep, dix_like)

    def step(dix: DeviceIndex, s: jax.Array, t: jax.Array) -> jax.Array:
        return serve_step(dix, s, t)

    return jax.jit(step, in_shardings=(dix_shardings, shard, shard),
                   out_shardings=shard)


# ---------------------------------------------------------------------------
# sharded offline build
# ---------------------------------------------------------------------------
def fw_fragments_sharded(mesh: Mesh, frag_adj: jax.Array,
                         axis: str = "data") -> jax.Array:
    """Offline per-fragment APSP with the fragment batch sharded."""

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=P(axis), out_specs=P(axis))
    def _local(adj):
        return ops.fw_batch(adj)

    return _local(frag_adj)


def super_apsp_sharded(mesh: Mesh, src: jax.Array, dst: jax.Array,
                       w: jax.Array, n_super: int,
                       axis: str = "data") -> jax.Array:
    """Offline SUPER APSP: BF sources sharded, edge list replicated.
    Purely local like serve_sharded, whose note on the varying-axes
    check applies to the BF loop's carry here too."""
    srcs = jnp.arange(n_super, dtype=jnp.int32)

    @functools.partial(shard_map, mesh=mesh, check_vma=False,
                       in_specs=(P(), P(), P(), P(axis)),
                       out_specs=P(axis))
    def _local(src_, dst_, w_, sources_):
        return sssp.apsp_from_sources(src_, dst_, w_, sources_, n=n_super)

    return _local(src, dst, w, srcs)
