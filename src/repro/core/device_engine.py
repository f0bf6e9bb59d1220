"""Device (TPU) DISLAND engine: fixed-shape batched query answering.

Hardware adaptation of the paper's per-query Dijkstra (DESIGN.md §2):
every query path becomes gathers + (min,+) algebra over padded tensors.

Offline (build_device_index, device-resident products):
  * per-fragment dense APSP        [k, maxf, maxf]   (Pallas blocked FW)
  * boundary-row table             [k, maxf, mb]     (node -> boundary)
  * SUPER boundary x boundary APSP [S+1, S+1]        (dense FW closure)
  * per-piece APSP, flattened      [sum_b P_b*mp_b^2] (+ per-node
    base/stride so one gather answers any same-piece query)
  * per-node lookup vectors        agent/fragment/piece ids + positions

Online (serve_step — one jitted program per query batch):
  dist(s,t) = same-DRA answer                                (case 1)
            | d(s,u_s) + min(local, combine) + d(u_t,t)      (case 2)
  combine = min_{b1,b2} row_s[b1] + D_super[b1,b2] + row_t[b2],
computed without ever materializing a [q, mb, mb] block: on TPU the
boundary rows are scattered into SUPER coordinates and contracted by
the fused minplus_twoside Pallas kernel (D_super tiles stay resident
in VMEM); on CPU an x-chunked gather keeps the peak intermediate at
[q, 8, mb] (DESIGN.md §4).

Also owned here: the hub-label hot tier's build (``hub_stage``) and
serve (``serve_hub``) halves — 2-hop labels over the closed hierarchy
for a pinned traffic-head node set, derived by batched (min,+)
products from the same tables above, no new graph searches
(DESIGN.md §15) — and ``refresh_index``, the staged delta path that
re-derives every table (labels included) array-equal to a scratch
rebuild on each epoch (DESIGN.md §9).

Everything is exact (validated against the host engine): integer
weights make every f32 (min,+) sum exactly representable, so "exact"
means bit-for-bit, regardless of association order.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..obs import trace
from . import hierarchy, padding
from .supergraph import DislandIndex

INF = np.float32(np.inf)
#: padded DRA piece sizes (the witness closure takes any width)
PIECE_BUCKETS = (8, 32, 128, 512, 2048)


def _dummy(shape, fill, dtype):
    return lambda: jnp.full(shape, fill, dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceIndex:
    # per-node lookups [n]
    agent_of: jax.Array          # int32
    dist_to_agent: jax.Array     # f32
    frag_of: jax.Array           # int32 (fragment of each *shrink* node)
    pos_in_frag: jax.Array       # int32
    piece_gid: jax.Array         # int32 global piece id (-1 if none)
    pos_in_piece: jax.Array      # int32
    piece_base: jax.Array        # int32 offset of piece block in flat
    piece_stride: jax.Array      # int32 row stride (= padded piece size)
    # fragments
    frag_apsp: jax.Array         # f32 [k, maxf, maxf]
    frag_next: jax.Array         # int32 [k, maxf, maxf] FW first hop (-1)
    brow: jax.Array              # f32 [k, maxf, mb] node->boundary rows
    bpos: jax.Array              # int32 [k, mb] boundary position in frag
    bvalid: jax.Array            # bool [k, mb]
    bnd_super: jax.Array         # int32 [k, mb] super id (S = sentinel)
    # super graph (dense overlay; authoritative at hierarchy_levels=1)
    d_super: jax.Array           # f32 [S+1, S+1] (+inf sentinel row/col)
    super_next: jax.Array        # int32 [S+1, S+1] overlay first hop (-1)
    # pieces: every bucketed APSP tensor, flattened end to end
    piece_flat: jax.Array        # f32 [sum_b P_b * mp_b * mp_b]
    piece_next: jax.Array        # int32, same layout as piece_flat (-1)
    # hierarchical overlay (hierarchy_levels=N, DESIGN.md §12-13).  One
    # tuple entry per grouping level, bottom first; the dense pair
    # above shrinks to a [1, 1] dummy and these per-level tables take
    # over, with d2/d2_next holding the TOP (last level's boundary)
    # closure.  At levels=1 the tuples are empty.  Serve/unwind code
    # dispatches on len(sf_of) — a static trace-time fact (tuple
    # lengths live in the pytree treedef), so no flags thread through
    # jit.
    sf_of: tuple = ()        # int32 [S_l+1] each (group count = sentinel)
    pos_in_sf: tuple = ()    # int32 [S_l+1]
    sf_members: tuple = ()   # int32 [ng+1, m2] (S_l = pad)
    sf_closure: tuple = ()   # f32 [ng+1, m2, m2]
    sf_next: tuple = ()      # int32 [ng+1, m2, m2]
    l2row: tuple = ()        # f32 [ng+1, m2, mb2]
    bnd2_sid: tuple = ()     # int32 [ng+1, mb2] (S_{l+1} = pad)
    d2: jax.Array = dataclasses.field(             # f32 [S_top+1, S_top+1]
        default_factory=_dummy((1, 1), INF, jnp.float32))
    d2_next: jax.Array = dataclasses.field(        # int32 [S_top+1, S_top+1]
        default_factory=_dummy((1, 1), -1, jnp.int32))
    # epoch-resident pre-lifted rows (DESIGN.md §13): for each hot
    # level-1 super-fragment, its members' exact confined distances to
    # every TOP boundary node — so a hot cross-top-group query is one
    # fused minplus_twoside against d2 with no per-level lifting.
    # res_rows row [R] is the all-INF sentinel; res_of_frag maps every
    # fragment to its group's resident row (R when not resident).
    res_rows: jax.Array = dataclasses.field(       # f32 [R+1, m2, S_top+1]
        default_factory=_dummy((1, 1, 1), INF, jnp.float32))
    res_of_frag: jax.Array = dataclasses.field(    # int32 [k]
        default_factory=_dummy((1,), 0, jnp.int32))
    # fragment -> TOP-level group (device twin of the planner sidecar
    # host_topgrp_frag): the CPU serve path uses it to contract only
    # against each endpoint's own top-group boundary columns
    topgrp_of_frag: jax.Array = dataclasses.field(  # int32 [k]
        default_factory=_dummy((1,), 0, jnp.int32))
    # 2-hop hub labels for the hot serving tier (DESIGN.md §15): row
    # hub_of_agent[a] of hub_rows is agent a's label — its exact
    # overlay distance to every TOP closure coordinate (dense epochs:
    # every SUPER node).  The last row is the all-INF sentinel and
    # unlabeled agents map to it, so a mis-gated merge degrades to
    # +inf, never a wrong finite distance.  Derived by hub_stage from
    # (brow, per-level tables, d2); refresh re-derives it whenever any
    # of those inputs move, keeping refresh == rebuild array-equal.
    hub_rows: jax.Array = dataclasses.field(        # f32 [H+1, W]
        default_factory=_dummy((1, 1), INF, jnp.float32))
    hub_of_agent: jax.Array = dataclasses.field(    # int32 [n]
        default_factory=_dummy((1,), 0, jnp.int32))

    @property
    def hierarchy_levels(self) -> int:
        return 1 + len(self.sf_of)

    def tree_flatten(self):
        fields = dataclasses.fields(self)
        children = tuple(getattr(self, f.name) for f in fields)
        return children, tuple(f.name for f in fields)

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(**dict(zip(names, children)))


# ---------------------------------------------------------------------------
# offline build, staged (DESIGN.md §2, §9)
#
# The build is decomposed into per-stage functions over a host-side
# BuildPlan so the incremental refresh path (refresh_index) can re-run
# exactly the stage subset a weight-update batch dirties, while a
# from-scratch build composes every stage.  Both paths run the same
# per-item tensor programs, which is what makes "incremental rebuild ==
# from-scratch rebuild" hold array-for-array (tests/test_refresh.py).
# ---------------------------------------------------------------------------
# canonical padding rules live in padding.py (shared with the planner
# and the serving scheduler); the old private names stay as aliases
_pad_to = padding.pad_to
_pow2 = padding.pow2


@dataclasses.dataclass
class BuildPlan:
    """Host-side skeleton of the device index.

    Everything the refresh path needs that serve-time tensors do not
    carry: the mutable weight caches (``frag_adj``, ``sup_w``), the
    fixed SUPER edge-list *structure* with per-slot provenance, and the
    piece registry.  Structure (DRAs, fragments, SUPER topology) is
    weight-invariant, so a weight-update batch mutates only the caches
    and the plan survives arbitrarily many epochs.
    """

    n: int
    k: int
    maxf: int
    mb: int
    S: int
    # per-node host lookups (update classification)
    agent_of: np.ndarray
    frag_of: np.ndarray          # original id -> fragment (-1: represented)
    pos_in_frag: np.ndarray
    piece_gid: np.ndarray
    pos_in_piece: np.ndarray
    # fragments
    frag_adj: np.ndarray         # f32 [k, maxf, maxf] current weights
    bpos: np.ndarray
    bvalid: np.ndarray
    bnd_super: np.ndarray
    # SUPER edge slots (undirected, compact ids; structure is fixed)
    sup_src: np.ndarray          # int32 [Es]
    sup_dst: np.ndarray          # int32 [Es]
    sup_w: np.ndarray            # f32 [Es] current weights
    sup_fi: np.ndarray           # int32 [Es] owning fragment (-1: E_B)
    sup_pu: np.ndarray           # int32 [Es] frag-local gather row
    sup_pv: np.ndarray           # int32 [Es] frag-local gather col
    eb_key: np.ndarray           # int64 sorted lo*n+hi keys of E_B slots
    eb_slot: np.ndarray          # int64 slot per key
    # piece registry (gid order)
    piece_members: List[np.ndarray]   # sorted original ids, incl. agent
    piece_agent: np.ndarray           # int32 [P]
    piece_agent_pos: np.ndarray       # int32 [P]
    piece_cap: np.ndarray             # int32 [P] padded size
    piece_base: np.ndarray            # int64 [P] offset into piece_flat
    # overlay hierarchy (DESIGN.md §12-13): 1 = dense d_super closure,
    # N >= 2 = per-group closures at N-1 grouping levels (``hier``, one
    # HierPlan per level, bottom first) + dense TOP boundary closure
    hierarchy_levels: int = 1
    hier: "List[hierarchy.HierPlan] | None" = None
    # resident pre-lift budget in MiB (0 disables; DESIGN.md §13)
    resident_mb: float = 0.0
    # hub-label hot tier (DESIGN.md §15): the pinned node set whose
    # agents get 2-hop labels (None/empty disables).  Selection is a
    # *build input*, not derived state — refresh re-labels exactly this
    # set, which is what keeps refresh == rebuild array-equal; a new
    # traffic-driven selection is a new plan, not a refresh.
    hub_nodes: "np.ndarray | None" = None
    # per-stage wall times of the build that produced this plan
    # (DESIGN.md §16; filled by build_device_index_with_plan through
    # the trace.timed span API, same measurement the trace events see)
    build_timings: "dict | None" = None

    @property
    def n_pieces(self) -> int:
        return len(self.piece_members)


def make_build_plan(ix: DislandIndex) -> BuildPlan:
    """Stage 0: host-side structure assembly (no device work).

    The device SUPER overlay is rebuilt here from first principles
    rather than taken from ``ix.super_graph.graph``: its node universe
    is exactly the boundary nodes (all bnd_super can ever reference),
    E_B slots are the cross-fragment shrink edges, and each fragment
    contributes its full boundary-to-boundary clique whose weights are
    *gathered from frag_apsp* (super_weights) — never stored
    authoritatively.  The host index keeps the paper's hybrid landmark
    covers (§V-A) for its space story; the device overlay cannot,
    because a cover's pair structure encodes which node lies on a
    shortest path — a weight-dependent fact that a live update batch
    silently invalidates (DESIGN.md §9).  The clique structure is
    weight-invariant, so scratch build and incremental refresh obtain
    every overlay weight by the same gather.
    """
    g = ix.g
    n = g.n
    k = len(ix.fragments)

    # ---- fragments + boundary universe ---------------------------------
    maxf = _pad_to(max((f.graph.n for f in ix.fragments), default=1))
    mb = _pad_to(max((f.boundary_local.size for f in ix.fragments),
                     default=1))
    frag_adj = np.full((k, maxf, maxf), INF, dtype=np.float32)
    frag_of = -np.ones(n, dtype=np.int32)
    pos_in_frag = np.zeros(n, dtype=np.int32)
    bpos = np.zeros((k, mb), dtype=np.int32)
    bvalid = np.zeros((k, mb), dtype=bool)
    bnd_ids = np.unique(np.concatenate(
        [f.nodes[f.boundary_local] for f in ix.fragments]
        or [np.empty(0, np.int64)]))
    S = bnd_ids.size
    bnd_super = np.full((k, mb), S, dtype=np.int32)
    super_id_of = -np.ones(n, dtype=np.int64)
    super_id_of[bnd_ids] = np.arange(S)
    for fi, f in enumerate(ix.fragments):
        fg = f.graph
        frag_of[f.nodes] = fi
        pos_in_frag[f.nodes] = np.arange(f.nodes.size)
        frag_adj[fi, fg.edge_u, fg.edge_v] = fg.edge_w.astype(np.float32)
        frag_adj[fi, fg.edge_v, fg.edge_u] = fg.edge_w.astype(np.float32)
        nb = f.boundary_local.size
        bpos[fi, :nb] = f.boundary_local
        bvalid[fi, :nb] = True
        bnd_super[fi, :nb] = super_id_of[f.nodes[f.boundary_local]]

    # ---- SUPER edge slots (vectorized; slot order is E_B in shrink
    # edge order, then per-fragment cliques row-major — the exact
    # layout the per-slot Python loops this replaces produced) --------
    shrink = ix.shrink
    lab = ix.partition.labels
    # E_B: cross-fragment shrink edges (both endpoints boundary by
    # construction); same-fragment boundary-boundary edges are subsumed
    # by that fragment's clique, so every edge has ONE owning slot kind
    cross = lab[shrink.edge_u] != lab[shrink.edge_v]
    ou = ix.shrink_ids[shrink.edge_u[cross]].astype(np.int64)
    ov = ix.shrink_ids[shrink.edge_v[cross]].astype(np.int64)
    ek = np.minimum(ou, ov) * n + np.maximum(ou, ov)
    es = np.arange(ou.size, dtype=np.int64)
    src_parts = [super_id_of[ou].astype(np.int32)]
    dst_parts = [super_id_of[ov].astype(np.int32)]
    w_parts = [shrink.edge_w[cross].astype(np.float32)]
    fi_parts = [np.full(ou.size, -1, dtype=np.int32)]
    pu_parts = [np.full(ou.size, -1, dtype=np.int32)]
    pv_parts = [np.full(ou.size, -1, dtype=np.int32)]
    # per-fragment boundary cliques (paper §V-A Upsilon weights, derived)
    for fi, f in enumerate(ix.fragments):
        bl = f.boundary_local
        ids = super_id_of[f.nodes[bl]]
        ii, jj = np.triu_indices(bl.size, k=1)
        src_parts.append(ids[ii].astype(np.int32))
        dst_parts.append(ids[jj].astype(np.int32))
        w_parts.append(np.full(ii.size, INF, dtype=np.float32))
        fi_parts.append(np.full(ii.size, fi, dtype=np.int32))
        pu_parts.append(bl[ii].astype(np.int32))
        pv_parts.append(bl[jj].astype(np.int32))
    sup_src = np.concatenate(src_parts)
    sup_dst = np.concatenate(dst_parts)
    sup_w = np.concatenate(w_parts)
    sup_fi = np.concatenate(fi_parts)
    sup_pu = np.concatenate(pu_parts)
    sup_pv = np.concatenate(pv_parts)
    order = np.argsort(ek, kind="stable")

    # ---- piece registry + per-node lookups ------------------------------
    piece_gid = -np.ones(n, dtype=np.int32)
    pos_in_piece = np.zeros(n, dtype=np.int32)
    piece_members: List[np.ndarray] = []
    piece_agent: List[int] = []
    piece_agent_pos: List[int] = []
    piece_cap: List[int] = []
    for a in ix.dras.agents:
        for piece in a.pieces:
            cap = next(c for c in PIECE_BUCKETS if piece.size <= c)
            ids = np.unique(np.asarray(piece, dtype=np.int32))
            gid = len(piece_members)
            piece_members.append(ids)
            piece_agent.append(int(a.agent))
            piece_agent_pos.append(int(np.searchsorted(ids, a.agent)))
            piece_cap.append(cap)
            # the agent belongs to many pieces: leave its lookup at -1 so
            # case-1 logic falls through to the exact ds+dt formula
            inner = ids != a.agent
            piece_gid[ids[inner]] = gid
            pos_in_piece[ids[inner]] = np.nonzero(inner)[0]
    # flat layout: bucket-major (all cap-8 blocks, then cap-32, ...),
    # bucket-local order = gid order — matches piece_stage's FW batching
    cap_arr = np.asarray(piece_cap, dtype=np.int64)
    piece_base = np.zeros(len(piece_members), dtype=np.int64)
    off = 0
    for cap in PIECE_BUCKETS:
        for gid in np.nonzero(cap_arr == cap)[0]:
            piece_base[gid] = off
            off += cap * cap

    return BuildPlan(
        n=n, k=k, maxf=maxf, mb=mb, S=S,
        agent_of=ix.dras.agent_of.astype(np.int32),
        frag_of=frag_of, pos_in_frag=pos_in_frag,
        piece_gid=piece_gid, pos_in_piece=pos_in_piece,
        frag_adj=frag_adj, bpos=bpos, bvalid=bvalid, bnd_super=bnd_super,
        sup_src=np.asarray(sup_src, dtype=np.int32),
        sup_dst=np.asarray(sup_dst, dtype=np.int32),
        sup_w=np.asarray(sup_w, dtype=np.float32),
        sup_fi=np.asarray(sup_fi, dtype=np.int32),
        sup_pu=np.asarray(sup_pu, dtype=np.int32),
        sup_pv=np.asarray(sup_pv, dtype=np.int32),
        eb_key=ek[order], eb_slot=es[order],
        piece_members=piece_members,
        piece_agent=np.asarray(piece_agent, dtype=np.int32),
        piece_agent_pos=np.asarray(piece_agent_pos, dtype=np.int32),
        piece_cap=cap_arr.astype(np.int32),
        piece_base=piece_base,
    )


def _brow_from(frag_apsp: jax.Array, bpos: np.ndarray,
               bvalid: np.ndarray) -> jax.Array:
    """Boundary-row table: brow[f, p, b] = dist(node at position p,
    boundary slot b) — serve gathers one row per query endpoint instead
    of a take_along_axis over [q, maxf]."""
    brow = jnp.take_along_axis(frag_apsp,
                               jnp.asarray(bpos)[:, None, :], axis=2)
    return jnp.where(jnp.asarray(bvalid)[:, None, :], brow, INF)


def frag_stage(plan: BuildPlan, *, force=None) -> tuple[jax.Array,
                                                        jax.Array,
                                                        jax.Array]:
    """Stage 1: batched witness FW over every fragment ->
    (apsp, brow, next).  The witness kernel's distance output is
    bit-identical to the distance-only kernel (same recurrence, same
    pivot order), so the path table rides along for free."""
    frag_apsp, frag_next = ops.fw_batch_next(jnp.asarray(plan.frag_adj),
                                             force=force)
    return (frag_apsp, _brow_from(frag_apsp, plan.bpos, plan.bvalid),
            frag_next)


def super_weights(plan: BuildPlan, blocks: np.ndarray,
                  frags: np.ndarray | None = None) -> None:
    """Fill the enforced SUPER slot weights by gathering from fragment
    APSP ``blocks`` (DESIGN.md §9: the Upsilon weights are *derived*
    state, never stored authoritatively).

    ``frags=None``: blocks is the full [k, maxf, maxf] table, fill every
    enforced slot.  Otherwise blocks holds only the listed fragments'
    rows, and only their slots are rewritten.
    """
    if frags is None:
        mask = plan.sup_fi >= 0
        local = plan.sup_fi[mask]
    else:
        mask = np.isin(plan.sup_fi, frags)
        fi_to_row = -np.ones(plan.k, dtype=np.int64)
        fi_to_row[frags] = np.arange(len(frags))
        local = fi_to_row[plan.sup_fi[mask]]
    plan.sup_w[mask] = blocks[local, plan.sup_pu[mask], plan.sup_pv[mask]]


def super_overlay(plan: BuildPlan) -> jax.Array:
    """Dense [S, S] overlay adjacency from the slot list (parallel
    slots min-merged, diag 0)."""
    S = plan.S
    m = np.full((S, S), INF, np.float32)
    np.minimum.at(m, (plan.sup_src, plan.sup_dst), plan.sup_w)
    np.minimum.at(m, (plan.sup_dst, plan.sup_src), plan.sup_w)
    np.fill_diagonal(m, 0.0)
    return jnp.asarray(m)


def overlay_slot_table(plan: BuildPlan) -> np.ndarray:
    """Winning slot id per overlay adjacency pair [S, S] (-1: none).

    Writes slots in descending weight order so the last (= lightest)
    write wins, matching super_overlay's min-merge of parallel slots.
    Computed whenever the overlay is (re)closed and carried on the
    published DeviceIndex as the host-side ``host_ov_slot`` sidecar, so
    path unwinding always reads slot provenance consistent with the
    d_super/super_next epoch it serves — never the live-mutating
    ``plan.sup_w`` (DESIGN.md §10).
    """
    ov = np.full((plan.S, plan.S), -1, np.int32)
    if plan.sup_w.size:
        order = np.argsort(plan.sup_w, kind="stable")[::-1]
        src, dst = plan.sup_src[order], plan.sup_dst[order]
        ov[src, dst] = order
        ov[dst, src] = order
    return ov


def super_stage(plan: BuildPlan, *, force=None) -> tuple[jax.Array,
                                                         jax.Array]:
    """Stage 2: SUPER APSP — dense witness FW closure of the boundary
    overlay -> (d_super, super_next).

    The overlay is small and clique-dense, which is exactly the regime
    where dense (min,+) algebra crushes edge-list relaxation: the FW
    closure solves S=625 in ~60ms where the segment_min Bellman-Ford
    needed a diameter's worth of ~750ms sweeps (~20s) — measured on
    road4000, bit-identical results.  The same closure serves scratch
    builds and incremental refreshes: a warm-started BF was tried for
    the refresh path and measured out (negative-result note in sssp.py;
    the edge-list BF remains the tool for the large sparse sharded
    build, dist_engine.super_apsp_sharded).  Since PR 3 the closure
    carries the first-hop witness matrix (DESIGN.md §10): super_next
    chains through overlay-*adjacent* super nodes, and each adjacency
    hop is resolved back to a concrete slot by PathUnwinder via the
    epoch's overlay_slot_table sidecar.
    """
    S = plan.S
    d_super = jnp.full((S + 1, S + 1), INF, jnp.float32)
    super_next = jnp.full((S + 1, S + 1), -1, jnp.int32)
    if S == 0 or plan.sup_src.size == 0:
        return d_super, super_next
    d_s, n_s = ops.fw_next(super_overlay(plan), force=force)
    return (d_super.at[:S, :S].set(d_s),
            super_next.at[:S, :S].set(n_s))


def _piece_adj(g, members: np.ndarray, cap: int) -> np.ndarray:
    sub, _ids = g.subgraph(members)
    adj = np.full((cap, cap), INF, dtype=np.float32)
    adj[sub.edge_u, sub.edge_v] = sub.edge_w.astype(np.float32)
    adj[sub.edge_v, sub.edge_u] = sub.edge_w.astype(np.float32)
    return adj


def _fw_bucket(adjs: List[np.ndarray], *, force=None,
               pad_pow2: bool = False) -> tuple[np.ndarray, np.ndarray]:
    """Batched witness FW over equally-padded piece matrices ->
    (dist blocks, next blocks).  ``pad_pow2`` (refresh path) rounds the
    batch up with +inf dummies, floored at 8, so the jitted FW program
    compiles for O(log P) distinct batch shapes — and a typical
    localized update batch always hits the already-warm 8-shape
    (EpochedEngine pre-compiles it)."""
    cap = adjs[0].shape[0]
    batch = np.stack(adjs)
    if pad_pow2 and _pow2(len(adjs), floor=8) != len(adjs):
        full = np.full((_pow2(len(adjs), floor=8), cap, cap), INF,
                       np.float32)
        full[:len(adjs)] = batch
        batch = full
    out, nxt = ops.fw_batch_next(jnp.asarray(batch), force=force)
    out = np.asarray(out)[:len(adjs)]
    # Padding blocks are all-+inf: the FW recurrence only ever ADDS
    # (inf+inf = inf, no inf-inf), so no NaN can arise — audited and
    # pinned by the all-INF kernel tests in tests/test_kernels.py.
    # Guard it anyway: mismatches_oracle treats NaN as always-wrong,
    # so a kernel regression here must fail the build loudly, not
    # surface as serving mismatches three layers up.
    if np.isnan(out).any():
        raise FloatingPointError(
            "piece FW produced NaN (inf-padding arithmetic regressed?)")
    return (out, np.asarray(nxt)[:len(adjs)])


def piece_stage(plan: BuildPlan, g, *, force=None) -> tuple[np.ndarray,
                                                            np.ndarray]:
    """Stage 3: per-piece APSP, size-bucketed batched FW, flattened
    end-to-end into the single piece_flat gather table (DESIGN.md §3),
    plus the identically-laid-out first-hop witness table piece_next."""
    total = int(sum(int(c) * int(c) for c in plan.piece_cap))
    flat = np.full(max(total, 1), INF, dtype=np.float32)
    nflat = np.full(max(total, 1), -1, dtype=np.int32)
    for cap in PIECE_BUCKETS:
        gids = np.nonzero(plan.piece_cap == cap)[0]
        if gids.size == 0:
            continue
        adjs = [_piece_adj(g, plan.piece_members[gid], cap)
                for gid in gids]
        blocks, nexts = _fw_bucket(adjs, force=force)
        for gid, block, nxt in zip(gids, blocks, nexts):
            base = plan.piece_base[gid]
            flat[base:base + cap * cap] = block.reshape(-1)
            nflat[base:base + cap * cap] = nxt.reshape(-1)
    return flat, nflat


def hier_super_stage(plan: BuildPlan, *, force=None) -> dict:
    """Stage 2, hierarchical (DESIGN.md §12-13): close the overlay as
    an N-level partition hierarchy instead of one dense FW.

    Per grouping level, bottom first: fill the level's group adjacency
    from its source overlay's current slot weights (level 1 gathers
    ``plan.sup_w``; level l > 1 the previous level's derived ``l2_w``),
    run the existing batched witness FW once at the pow2 tile shape
    [nsf, m2, m2] (``hierarchy.sf_stage``), then gather the NEXT
    overlay's clique weights from those closures (derived state,
    exactly like the level-1 Upsilon weights).  Only the top boundary
    set closes densely (``hierarchy.l2_stage`` -> d2).  Returns the
    DeviceIndex field dict (per-level tuples) plus the host-side
    provenance sidecars (one SlotMap per level).
    """
    levels = plan.hier
    per: dict = {name: [] for name in (
        "sf_of", "pos_in_sf", "sf_members", "sf_closure", "sf_next",
        "l2row", "bnd2_sid")}
    l2_slots = []
    w = plan.sup_w
    for h in levels:
        hierarchy.sf_adj_fill(h, w)
        sf_closure, sf_next, l2row = hierarchy.sf_stage(h, force=force)
        hierarchy.hier_weights(h, np.asarray(sf_closure)[:h.nsf], w)
        Sl = h.sf_of.shape[0]                    # this overlay's size
        sf_of = np.concatenate([h.sf_of, [h.nsf]]).astype(np.int32)
        pos_in_sf = np.concatenate([h.pos_in_sf, [0]]).astype(np.int32)
        members = np.where(h.sf_members < 0, Sl,
                           h.sf_members).astype(np.int32)
        members = np.concatenate(
            [members, np.full((1, h.m2), Sl, np.int32)])
        bnd2_sid = np.concatenate(
            [h.bnd2_sid, np.full((1, h.mb2), h.S2, np.int32)])
        per["sf_of"].append(jnp.asarray(sf_of))
        per["pos_in_sf"].append(jnp.asarray(pos_in_sf))
        per["sf_members"].append(jnp.asarray(members))
        per["sf_closure"].append(sf_closure)
        per["sf_next"].append(sf_next)
        per["l2row"].append(l2row)
        per["bnd2_sid"].append(jnp.asarray(bnd2_sid))
        l2_slots.append(hierarchy.l2_slot_map(h))
        w = h.l2_w
    d2, d2_next = hierarchy.l2_stage(levels[-1], force=force)
    fields = {name: tuple(v) for name, v in per.items()}
    fields["d2"] = d2
    fields["d2_next"] = d2_next
    return {
        "fields": fields,
        "ov_slot": hierarchy.ov_slot_map(plan),
        "l2_slot": l2_slots,
    }


@functools.partial(jax.jit, static_argnames=("sents",))
def _group_chain(l2rows: tuple, sids: tuple, poss: tuple, gs: jax.Array,
                 sents: tuple) -> tuple[jax.Array, jax.Array]:
    """(U, ids) of one bottom-level group: U[p, x] is the exact confined
    distance from its member at position p to the x-th boundary slot of
    its TOP group, whose ids are ``ids``.  ``gs`` [L] is the group at
    every level (groups nest upward), ``sents`` each level's sentinel
    id.  One program per level-width ladder, whichever group it runs
    for: the per-level lifts are (min,+) products (``ops.minplus``)."""
    U = l2rows[0][gs[0]]
    ids = sids[0][gs[0]]
    for li in range(1, len(l2rows)):
        M = l2rows[li][gs[li]][poss[li][ids]]        # [mb, mb']
        M = jnp.where((ids != sents[li - 1])[:, None], M, INF)
        U = ops.minplus(U, M)
        ids = sids[li][gs[li]]
    return U, ids


def _chain_of(levels, fields: dict, g: int) -> tuple[jax.Array,
                                                     jax.Array]:
    """_group_chain for bottom-level group ``g`` of ``fields``'s
    per-level tables."""
    gs = [g]
    for h in levels[1:]:
        gs.append(int(h.sf_of_frag[gs[-1]]))
    return _group_chain(fields["l2row"], fields["bnd2_sid"],
                        fields["pos_in_sf"], jnp.asarray(gs, jnp.int32),
                        tuple(int(h.S2) for h in levels[:-1]))


@functools.partial(jax.jit, static_argnames=("width",))
def _scatter_cols(U: jax.Array, ids: jax.Array, width: int) -> jax.Array:
    """U's columns min-scattered to dense coordinates ``ids`` of a
    [rows, width] row block (+inf elsewhere)."""
    return jnp.full((U.shape[0], width), INF, U.dtype).at[:, ids].min(U)


@jax.jit
def _rows_through(rows: jax.Array, pos: jax.Array,
                  table: jax.Array) -> jax.Array:
    """out[a, :] = min_j rows[a, j] + table[pos[a, j], :]: each row's
    boundary distances carried through a table of rows, chunked over j
    so the gathered block stays [q, 8, width]."""
    q, mb = rows.shape
    c = min(8, mb)                       # mb is padded to a multiple of 8

    def body(i, acc):
        r_c = jax.lax.dynamic_slice_in_dim(rows, i * c, c, axis=1)
        p_c = jax.lax.dynamic_slice_in_dim(pos, i * c, c, axis=1)
        return jnp.minimum(acc, jnp.min(r_c[:, :, None] + table[p_c],
                                        axis=1))

    return jax.lax.fori_loop(0, mb // c, body,
                             jnp.full((q, table.shape[1]), INF, rows.dtype))


@jax.jit
def _top_rows(U: jax.Array, d2: jax.Array, ids: jax.Array) -> jax.Array:
    """A group chain closed over the whole top boundary: [m2, S_top+1]
    = U (x) d2[ids] (sentinel ids land on d2's +inf row)."""
    return ops.minplus(U, d2[ids])


def resident_stage(plan: BuildPlan, fields: dict) -> dict | None:
    """Stage 2b: epoch-resident pre-lifted rows (DESIGN.md §13).

    For each hot level-1 group g (top traffic mass, capped by
    ``plan.resident_mb``), compose the per-level lift chain once:

      U_g[p, c] = min over (a_1, ..., a_{L-1}) of
                  l2row[0][g, p, a_1] + l2row[1][g_2, pos(a_1), a_2]
                  + ... (+ sentinel-masked at every step)

    scattered to dense top coordinates — the exact confined distance
    from every member position p to every TOP boundary node c.  A hot
    cross-top-group query then runs ONE fused minplus_twoside against
    d2 instead of L per-level lifts; exact because a route between
    different top groups must touch the top boundary, and its prefix
    up to the first top contact stays hierarchically confined (no
    same-group legs apply: different top groups imply different groups
    at every level, since groups nest).

    Deterministic in (structure, per-level tables), so a refresh that
    re-runs it lands array-equal with a from-scratch build.  Returns
    the DeviceIndex field dict plus the planner's host sidecars, or
    None when disabled/degenerate.
    """
    levels = plan.hier
    if not levels or plan.resident_mb <= 0:
        return None
    h0 = levels[0]
    stp1 = int(fields["d2"].shape[0])
    if h0.nsf == 0 or stp1 <= 1:
        return None
    # traffic-mass proxy: original graph nodes per level-1 group (the
    # serve mix samples nodes uniformly-by-traffic, so member count is
    # the stationary hot-group weight)
    frag_nodes = np.bincount(plan.frag_of[plan.frag_of >= 0].astype(
        np.int64), minlength=plan.k)
    mass = np.zeros(h0.nsf, dtype=np.int64)
    np.add.at(mass, h0.sf_of_frag.astype(np.int64), frag_nodes)
    per_sf = h0.m2 * stp1 * 4
    cap = int(plan.resident_mb * (1 << 20)) // max(per_sf, 1)
    if cap <= 0:
        return None
    hot = np.sort(np.argsort(-mass, kind="stable")[:min(cap, h0.nsf)])
    rows_out = [_scatter_cols(*_chain_of(levels, fields, g), width=stp1)
                for g in hot.tolist()]
    R = len(rows_out)
    res_rows = jnp.stack(
        rows_out + [jnp.full((h0.m2, stp1), INF, jnp.float32)])
    rmap = np.full(h0.nsf, R, np.int32)
    rmap[hot] = np.arange(R, dtype=np.int32)
    res_of_frag = rmap[h0.sf_of_frag.astype(np.int64)]
    top = _top_group_of_frag(levels)
    return {
        "fields": {"res_rows": res_rows,
                   "res_of_frag": jnp.asarray(res_of_frag),
                   "topgrp_of_frag": jnp.asarray(top)},
        # planner sidecars: fragment -> resident row (-1: cold) and
        # fragment -> TOP group (the exactness gate)
        "res_frag": np.where(res_of_frag < R, res_of_frag,
                             -1).astype(np.int32),
        "topgrp_frag": top,
    }


def _top_group_of_frag(levels) -> np.ndarray:
    """int32 [k]: every fragment's TOP-level group."""
    top = levels[0].sf_of_frag.astype(np.int64)
    for h in levels[1:]:
        top = h.sf_of_frag.astype(np.int64)[top]
    return top.astype(np.int32)


def hub_stage(plan: BuildPlan, fields: dict) -> dict | None:
    """Stage 2c: 2-hop hub labels for the hot serving tier (§15).

    For every agent of a node in ``plan.hub_nodes``, compose its label
    row — the exact overlay distance from the agent to every TOP
    closure coordinate:

      lab[a, y] = min_{j, x} brow[f, p_a, j] + chain_f[j, x] + d2[x, y]

    where ``chain_f`` is the same per-level confined lift ladder the
    resident rows pre-compose (resident_stage), restricted to fragment
    f's boundary slots, and the trailing d2 contraction closes the row
    over the whole top boundary.  Per bottom-level group g holding
    labelled agents, the chain is closed over d2 once (``_top_rows``:
    every member's distance to every top coordinate), and each agent's
    label carries its boundary row through it (``_rows_through``).
    Dense epochs skip the ladder: lab[a] = brow row (min,+) d_super.  No
    Dijkstras anywhere — every leg is a batched (min,+) product over
    tables the build already carries, in programs whose shapes are the
    tables' and the pow2-padded agent count's.

    Exactness (the §15 merge argument): for endpoints in different TOP
    groups (dense: different fragments) the route must touch the top
    boundary; lab is then a pointwise-exact distance-to-hub vector, so
    min_y lab_s[y] + lab_t[y] equals the planner's two-sided combine —
    lower-bounded by the triangle inequality of the closed overlay
    metric, met at the route's first top contact (d2's diagonal is 0).
    Same-top-group pairs must fall through to the planner: their routes
    may never touch the hubs.

    Deterministic in (hub_nodes, brow, per-level tables, d2), so a
    refresh that re-runs it lands array-equal with a from-scratch
    build.  Returns the DeviceIndex field dict plus the planner's host
    sidecars, or None when disabled/degenerate.
    """
    nodes = plan.hub_nodes
    if nodes is None or len(nodes) == 0:
        return None
    nodes = np.asarray(nodes, np.int64)
    agents = np.unique(plan.agent_of[nodes].astype(np.int64))
    agents = agents[plan.frag_of[agents] >= 0]
    if agents.size == 0:
        return None
    brow = fields["brow"]
    levels = plan.hier
    # (fragment, agent) order is the label row order, stable across
    # build and refresh
    frag_a = plan.frag_of[agents]
    order = np.lexsort((agents, frag_a))
    agents, frag_a = agents[order], frag_a[order]
    pos_a = plan.pos_in_frag[agents]
    H = int(agents.size)

    def through(sel, pos, table):
        """_rows_through for the agents ``sel``, padded to pow2 rows."""
        m = _pow2(int(sel.size), floor=8)
        take = np.concatenate([sel, np.full(m - sel.size, sel[0])])
        f, p = frag_a[take], pos_a[take]
        rows = jnp.where(jnp.asarray(plan.bvalid[f]),
                         brow[jnp.asarray(f), jnp.asarray(p)], INF)
        return _rows_through(rows, jnp.asarray(pos[take]),
                             table)[:sel.size]

    topgrp_frag = None
    parts, sels = [], []
    if levels:
        d2 = fields["d2"]
        width = int(d2.shape[0])
        # bottom-level positions of every agent's boundary slots
        pos0 = np.asarray(fields["pos_in_sf"][0])[plan.bnd_super[frag_a]]
        grp_a = levels[0].sf_of_frag[frag_a]
        for g in np.unique(grp_a).tolist():
            sel = np.nonzero(grp_a == g)[0]
            U, ids = _chain_of(levels, fields, g)
            parts.append(through(sel, pos0, _top_rows(U, d2, ids)))
            sels.append(sel)
        topgrp_frag = _top_group_of_frag(levels)
    else:
        d_super = fields["d_super"]
        width = int(d_super.shape[0])
        sel = np.arange(H)
        parts.append(through(sel, plan.bnd_super[frag_a], d_super))
        sels.append(sel)
    inv = np.empty(H, np.int64)
    inv[np.concatenate(sels)] = np.arange(H)
    hub_rows = jnp.concatenate(
        [jnp.concatenate(parts)[jnp.asarray(inv)],
         jnp.full((1, width), INF, jnp.float32)])
    hmap = np.full(plan.n, H, np.int32)          # sentinel row for all
    hmap[agents] = np.arange(H, dtype=np.int32)
    hub_agent = np.full(plan.n, -1, np.int32)    # planner gate sidecar
    hub_agent[agents] = np.arange(H, dtype=np.int32)
    return {
        "fields": {"hub_rows": hub_rows,
                   "hub_of_agent": jnp.asarray(hmap)},
        "hub_agent": hub_agent,
        # fragment -> TOP group, the hierarchical exactness gate — hub
        # serving must not depend on the resident stage having run
        "topgrp_frag": topgrp_frag,
    }


def hub_base_fields(plan: BuildPlan, src, brow) -> dict:
    """The hub_stage input dict from an index/field source: ``src``
    maps a field name to its current array (a dict from the build or
    refresh in flight, falling back to ``dix`` attributes), ``brow``
    is always the freshest fragment boundary rows."""
    base = {"brow": brow}
    if plan.hierarchy_levels >= 2:
        base.update({name: src(name) for name in
                     ("l2row", "bnd2_sid", "pos_in_sf", "d2")})
    else:
        base["d_super"] = src("d_super")
    return base


def resolve_hierarchy_levels(S: int, hierarchy_levels) -> int:
    """Normalize the ``hierarchy_levels`` build knob: "auto" switches
    off the dense overlay once S crosses hierarchy.AUTO_THRESHOLD (the
    planner then deepens on its own until the top closure fits);
    explicit 1..MAX_LEVELS is honored (degrading to 1 on an empty
    overlay; the built depth plan_hierarchy returns is authoritative
    when levels collapse early)."""
    if hierarchy_levels == "auto":
        hierarchy_levels = 2 if S > hierarchy.AUTO_THRESHOLD else 1
    try:
        lv = int(hierarchy_levels)
    except (TypeError, ValueError):
        raise ValueError(
            f"hierarchy_levels must be an int or 'auto': "
            f"{hierarchy_levels!r}")
    if not 1 <= lv <= hierarchy.MAX_LEVELS:
        raise ValueError(
            f"hierarchy_levels must be in 1..{hierarchy.MAX_LEVELS} "
            f"or 'auto': {hierarchy_levels!r}")
    if lv > 1 and S == 0:
        return 1
    return lv


def _node_piece_addressing(plan: BuildPlan) -> tuple[np.ndarray,
                                                     np.ndarray]:
    """Per-node (piece_base, piece_stride) vectors from the registry."""
    base = np.zeros(plan.n, dtype=np.int32)
    stride = np.zeros(plan.n, dtype=np.int32)
    hot = plan.piece_gid >= 0
    gid = plan.piece_gid[hot]
    base[hot] = plan.piece_base[gid]
    stride[hot] = plan.piece_cap[gid]
    return base, stride


#: default resident pre-lift budget (MiB) when ``resident_mb="auto"``
#: on a hierarchical index — sized so every road64k-scale group fits
RESIDENT_MB_AUTO = 64.0


def build_device_index_with_plan(
        ix: DislandIndex, *, force=None,
        hierarchy_levels: int | str = "auto",
        resident_mb: float | str = "auto",
        hub_nodes=None
        ) -> tuple[DeviceIndex, BuildPlan]:
    """Full from-scratch build: compose every stage, keep the plan
    around so refresh_index can run incrementally afterwards.

    ``hierarchy_levels`` picks the overlay closure: 1 = the dense
    [S+1, S+1] FW (unchanged, bit-identical to the pre-hierarchy
    index), N >= 2 = the N-level partition hierarchy (DESIGN.md
    §12-13), "auto" = hierarchical once S crosses
    ``hierarchy.AUTO_THRESHOLD``, deepening until the top closure fits
    under it.  ``resident_mb`` budgets the epoch-resident pre-lifted
    row cache on hierarchical indices ("auto" = RESIDENT_MB_AUTO; 0
    disables).  ``hub_nodes`` pins the hub-label hot-tier node set
    (DESIGN.md §15; None/empty disables the tier).
    """
    bt: dict = {}
    with trace.timed("build.plan", bt, "plan"):
        plan = make_build_plan(ix)
        if hub_nodes is not None and len(hub_nodes):
            plan.hub_nodes = np.asarray(hub_nodes, np.int64)
        lv = resolve_hierarchy_levels(plan.S, hierarchy_levels)
        if lv >= 2:
            plan.hier = hierarchy.plan_hierarchy(
                plan,
                levels="auto" if hierarchy_levels == "auto" else lv)
            # the planner may stop early on degenerate levels (or
            # deepen, under "auto"): the built depth is authoritative
            plan.hierarchy_levels = 1 + len(plan.hier)
            plan.resident_mb = (RESIDENT_MB_AUTO
                                if resident_mb == "auto"
                                else float(resident_mb))
        else:
            plan.hierarchy_levels = 1
    plan.build_timings = bt
    with trace.timed("build.frag_stage", bt, "frag_stage",
                     k=plan.k):
        frag_apsp, brow, frag_next = frag_stage(plan, force=force)
        super_weights(plan, np.asarray(frag_apsp))
    if plan.hierarchy_levels >= 2:
        with trace.timed("build.hier_super_stage", bt, "super_stage",
                         levels=plan.hierarchy_levels):
            hres = hier_super_stage(plan, force=force)
            hier_fields = dict(hres["fields"])
        with trace.timed("build.resident_stage", bt,
                         "resident_stage"):
            rres = resident_stage(plan, hier_fields)
            if rres is not None:
                hier_fields.update(rres["fields"])
        d_super = jnp.full((1, 1), INF, jnp.float32)
        super_next = jnp.full((1, 1), -1, jnp.int32)
    else:
        hres = None
        rres = None
        hier_fields = {}
        with trace.timed("build.super_stage", bt, "super_stage",
                         S=plan.S):
            d_super, super_next = super_stage(plan, force=force)
    with trace.timed("build.hub_stage", bt, "hub_stage"):
        hub = hub_stage(plan, hub_base_fields(
            plan, lambda name: hier_fields.get(name, d_super), brow))
    with trace.timed("build.piece_stage", bt, "piece_stage",
                     pieces=plan.n_pieces):
        piece_flat, piece_next = piece_stage(plan, ix.g, force=force)
    base, stride = _node_piece_addressing(plan)
    dix = DeviceIndex(
        **hier_fields,
        **({} if hub is None else hub["fields"]),
        agent_of=jnp.asarray(plan.agent_of),
        dist_to_agent=jnp.asarray(
            ix.dras.dist_to_agent.astype(np.float32)),
        frag_of=jnp.asarray(plan.frag_of),
        pos_in_frag=jnp.asarray(plan.pos_in_frag),
        piece_gid=jnp.asarray(plan.piece_gid),
        pos_in_piece=jnp.asarray(plan.pos_in_piece),
        piece_base=jnp.asarray(base),
        piece_stride=jnp.asarray(stride),
        frag_apsp=frag_apsp,
        frag_next=frag_next,
        brow=brow,
        bpos=jnp.asarray(plan.bpos),
        bvalid=jnp.asarray(plan.bvalid),
        bnd_super=jnp.asarray(plan.bnd_super),
        d_super=d_super,
        super_next=super_next,
        piece_flat=jnp.asarray(piece_flat),
        piece_next=jnp.asarray(piece_next),
    )
    # host-side sidecars (not pytree fields): slot provenance for the
    # overlay closure this index was built with.  Dense epochs carry
    # the [S, S] overlay_slot_table; hierarchical epochs carry the
    # sparse OvSlotMap (the dense table is exactly the quadratic host
    # object the hierarchy avoids) plus the small level-2 slot table.
    if hres is not None:
        dix.host_ov_slot = hres["ov_slot"]
        dix.host_l2_slot = hres["l2_slot"]
        if rres is not None:
            dix.host_res_frag = rres["res_frag"]
            dix.host_topgrp_frag = rres["topgrp_frag"]
    else:
        dix.host_ov_slot = overlay_slot_table(plan)
    if hub is not None:
        dix.host_hub_agent = hub["hub_agent"]
        if (hub["topgrp_frag"] is not None
                and getattr(dix, "host_topgrp_frag", None) is None):
            # hierarchical epoch without resident rows: the hub gate
            # still needs the fragment -> TOP group map
            dix.host_topgrp_frag = hub["topgrp_frag"]
    return dix, plan


def build_device_index(ix: DislandIndex, *, force=None,
                       hierarchy_levels: int | str = "auto",
                       resident_mb: float | str = "auto",
                       hub_nodes=None) -> DeviceIndex:
    """Assemble padded tensors on host, run device APSP preprocessing."""
    return build_device_index_with_plan(
        ix, force=force, hierarchy_levels=hierarchy_levels,
        resident_mb=resident_mb, hub_nodes=hub_nodes)[0]


def index_fields_equal(a: DeviceIndex, b: DeviceIndex,
                       names) -> dict:
    """Per-field array equality between two indices, tuple-field aware
    (per-level fields compare leaf-by-leaf).  Shared by the refresh
    differential harnesses in serve.py and the tests."""
    out = {}
    for name in names:
        la = jax.tree_util.tree_leaves(getattr(a, name))
        lb = jax.tree_util.tree_leaves(getattr(b, name))
        out[name] = (len(la) == len(lb) and all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(la, lb)))
    return out


def warmup_refresh(plan: BuildPlan, *, force=None) -> None:
    """Pre-compile the refresh-path FW programs (the small pow2
    fragment-batch shapes + one [8, cap, cap] batch per piece bucket in
    use), so no XLA compile lands inside a live apply_updates.  The
    overlay FW program is already warm from the build.  Mirrors
    QueryPlanner.warmup for the serve path (DESIGN.md §9)."""
    shapes = [(min(p, plan.k), plan.maxf, plan.maxf) for p in (4, 8)]
    shapes += [(8, int(cap), int(cap))
               for cap in np.unique(plan.piece_cap)]
    if plan.hier:
        # dirty group batches refresh at these pow2 shapes, per level
        for h in plan.hier:
            shapes += [(min(p, h.nsf), h.m2, h.m2) for p in (4, 8)]
    for shp in set(shapes):
        jax.block_until_ready(
            ops.fw_batch_next(jnp.full(shp, INF, jnp.float32),
                              force=force))


# ---------------------------------------------------------------------------
# incremental refresh (DESIGN.md §9; paper §IV/§V locality)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class UpdateClass:
    """A weight-update batch classified against the index structure.

    The paper's decomposition localizes every weight change: an edge is
    (i) inside one DRA piece, (ii) inside one fragment, and/or (iii) an
    E_B SUPER slot — nothing else.  Same-fragment boundary-boundary
    edges hit (ii) and (iii) simultaneously.
    """

    dirty_frags: np.ndarray      # fragment ids
    frag_fi: np.ndarray          # per same-fragment update
    frag_pu: np.ndarray
    frag_pv: np.ndarray
    frag_w: np.ndarray
    eb_slots: np.ndarray         # per E_B update
    eb_w: np.ndarray
    dirty_gids: np.ndarray       # piece ids
    n_inert: int                 # edges touching no served structure


def classify_updates(plan: BuildPlan, u, v, w) -> UpdateClass:
    """Map (u, v, new_w) updates onto dirty fragments / slots / pieces."""
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    w = np.asarray(w, dtype=np.float64)
    gid_u = plan.piece_gid[u]
    gid_v = plan.piece_gid[v]
    piece_m = (gid_u >= 0) | (gid_v >= 0)
    gid = np.where(gid_u >= 0, gid_u, gid_v)
    # structural invariant (paper Props 3-9): a represented node's only
    # neighbours are its piece co-members and its agent
    other_gid = np.where(gid_u >= 0, gid_v, gid_u)
    other = np.where(gid_u >= 0, v, u)
    safe_gid = np.where(piece_m, gid, 0)
    ok = (~piece_m | (other_gid == gid)
          | (other == plan.piece_agent[safe_gid]))
    if not ok.all():
        bad = np.nonzero(~ok)[0][0]
        raise ValueError(
            f"edge ({int(u[bad])}, {int(v[bad])}) crosses piece "
            "boundaries; index structure does not admit it")
    # same-fragment updates (frag_adj entries)
    fu = plan.frag_of[u]
    fv = plan.frag_of[v]
    frag_m = ~piece_m & (fu >= 0) & (fu == fv)
    # E_B slots (covers cross-fragment edges AND same-fragment edges
    # whose endpoints are both boundary)
    key = np.minimum(u, v) * plan.n + np.maximum(u, v)
    if plan.eb_key.size:
        pos = np.clip(np.searchsorted(plan.eb_key, key), 0,
                      plan.eb_key.size - 1)
        eb_m = ~piece_m & (plan.eb_key[pos] == key)
        slots = plan.eb_slot[pos]
    else:
        eb_m = np.zeros(u.size, dtype=bool)
        slots = np.zeros(u.size, dtype=np.int64)
    inert = int((~piece_m & ~frag_m & ~eb_m).sum())
    return UpdateClass(
        dirty_frags=np.unique(fu[frag_m]).astype(np.int64),
        frag_fi=fu[frag_m],
        frag_pu=plan.pos_in_frag[u[frag_m]],
        frag_pv=plan.pos_in_frag[v[frag_m]],
        frag_w=w[frag_m],
        eb_slots=slots[eb_m],
        eb_w=w[eb_m],
        dirty_gids=np.unique(gid[piece_m]).astype(np.int64),
        n_inert=inert,
    )


@dataclasses.dataclass
class RefreshStats:
    """What one refresh_index call touched, for perflog records."""

    n_updates: int
    n_dirty_frags: int
    n_frags: int
    n_dirty_pieces: int
    n_pieces: int
    n_eb_slots: int
    n_inert: int
    total_increase: float
    decrease_only: bool          # no weight rose (jam-clear batch)
    timings: dict
    # how the top closure was produced: "carry" (no overlay delta),
    # "decrease" (bounded relaxation fast path), "full_fw", "dense"
    top_closure: str = "carry"

    @property
    def dirty_frag_frac(self) -> float:
        return self.n_dirty_frags / max(self.n_frags, 1)

    def as_record(self) -> dict:
        return {
            "n_updates": self.n_updates,
            "dirty_frags": f"{self.n_dirty_frags}/{self.n_frags}",
            "dirty_frag_frac": round(self.dirty_frag_frac, 4),
            "dirty_pieces": f"{self.n_dirty_pieces}/{self.n_pieces}",
            "decrease_only": self.decrease_only,
            "top_closure": self.top_closure,
            "refresh_s": round(self.timings.get("total", 0.0), 4),
            # full per-stage split (classify/frag_fw/super_fw/hub/
            # pieces), so a refresh regression in the record history is
            # attributable to a stage, not just a bigger total
            "stage_timings": {
                k: round(v, 4)
                for k, v in sorted(self.timings.items())
                if k != "total"},
        }


def refresh_frag_stage(plan: BuildPlan, frag_apsp: jax.Array,
                       brow: jax.Array, frag_next: jax.Array,
                       upd: UpdateClass, *,
                       force=None) -> tuple[jax.Array, jax.Array,
                                            jax.Array, np.ndarray]:
    """Re-run witness FW on the dirty fragment subset only.

    The dirty batch is padded to a power of two with +inf dummies so
    refreshes compile O(log k) FW programs total; FW is row-independent
    across the batch, so the dirty rows come out bit-identical to a
    full-batch from-scratch run — distances and first-hop witnesses
    alike, which is what keeps epochs path-consistent (DESIGN.md §10).
    """
    plan.frag_adj[upd.frag_fi, upd.frag_pu, upd.frag_pv] = upd.frag_w
    plan.frag_adj[upd.frag_fi, upd.frag_pv, upd.frag_pu] = upd.frag_w
    dirty = upd.dirty_frags
    if dirty.size == 0:
        return frag_apsp, brow, frag_next, np.empty(
            (0, plan.maxf, plan.maxf), np.float32)
    # every array op below runs at the padded size: repeating the first
    # dirty fragment is idempotent (same rows scattered twice), and the
    # fixed shapes keep refreshes on pre-compiled programs
    # (warmup_refresh) instead of compiling one per dirty count
    d = int(dirty.size)
    p = min(_pow2(d, floor=4), plan.k)
    pad = np.concatenate([dirty, np.full(p - d, dirty[0], np.int64)]) \
        if p > d else dirty
    jpad = jnp.asarray(pad)
    jblocks, jnexts = ops.fw_batch_next(jnp.asarray(plan.frag_adj[pad]),
                                        force=force)
    frag_apsp = frag_apsp.at[jpad].set(jblocks)
    frag_next = frag_next.at[jpad].set(jnexts)
    br = _brow_from(jblocks, plan.bpos[pad], plan.bvalid[pad])
    return (frag_apsp, brow.at[jpad].set(br), frag_next,
            np.asarray(jblocks[:d]))


def refresh_hier_stage(plan: BuildPlan, dix: DeviceIndex,
                       changed_slots: np.ndarray, undo: dict, *,
                       force=None) -> dict:
    """Hierarchical twin of the dense overlay re-close (DESIGN.md
    §12-13): cascade the dirty-slot delta up the level ladder.

    At each level, a changed source slot dirties either one group's
    adjacency block (both endpoints inside it — re-close those groups'
    FW tiles, pow2-padded with repeats, bit-identical to a
    from-scratch hier_super_stage) or a cross slot (a direct
    next-level weight copy) — nothing else, the same block-diagonal
    structure the fragment refresh exploits one level down.  The
    *observed* next-level weight delta (l2_w before vs after) is what
    propagates: the cascade stops at the first level whose boundary
    weights came out unchanged, and every deeper table plus the top
    closure carries over by reference — exactly the
    no-overlay-change carry rule, applied per level.  ``undo`` is
    filled with per-level rollback snapshots of the weight caches
    BEFORE any mutation, so a failure later in the refresh can
    restore them.
    """
    levels = plan.hier
    closures = list(dix.sf_closure)
    nexts = list(dix.sf_next)
    rows_t = list(dix.l2row)
    l2_slots = list(dix.host_l2_slot)
    undo["levels"] = []
    cur = changed_slots
    w_src = plan.sup_w
    d2, d2_next = dix.d2, dix.d2_next
    dirty_top = False
    top_closure = "carry"
    lw_old = np.empty(0, np.float32)
    for li, h in enumerate(levels):
        sl = h.slot_sf[cur]
        sfs = np.unique(sl[sl >= 0]).astype(np.int64)
        lw_old = h.l2_w.copy()
        undo["levels"].append({"hier": h, "sfs": sfs,
                               "sf_adj": h.sf_adj[sfs].copy(),
                               "l2_w": lw_old})
        if sfs.size:
            hierarchy.sf_adj_fill(h, w_src, sfs=sfs)
            d = int(sfs.size)
            p = min(_pow2(d, floor=4), h.nsf)
            pad = np.concatenate([sfs, np.full(p - d, sfs[0],
                                               np.int64)]) \
                if p > d else sfs
            jpad = jnp.asarray(pad)
            blocks, nx = ops.fw_batch_next(jnp.asarray(h.sf_adj[pad]),
                                           force=force)
            closures[li] = closures[li].at[jpad].set(blocks)
            nexts[li] = nexts[li].at[jpad].set(nx)
            r = hierarchy.l2row_from(blocks, h.bnd2_pos[pad],
                                     h.bnd2_valid[pad])
            rows_t[li] = rows_t[li].at[jpad].set(r)
            hierarchy.hier_weights(h, np.asarray(blocks[:d]), w_src,
                                   sfs=sfs)
        else:
            # only cross-group slots changed at this level: no FW,
            # just the O(cross) next-level weight copy
            hierarchy.hier_weights(
                h, np.empty((0, h.m2, h.m2), np.float32), w_src,
                sfs=sfs)
        l2_slots[li] = hierarchy.l2_slot_map(h)
        nxt_changed = np.nonzero(h.l2_w != lw_old)[0].astype(np.int64)
        if nxt_changed.size == 0:
            # the next overlay's weights are untouched: closures AND
            # witnesses above this level are still exact, carry them
            break
        cur = nxt_changed
        w_src = h.l2_w
    else:
        dirty_top = True
    if dirty_top:
        # decrease-only fast path: when every changed top slot weight
        # went DOWN, a bounded (min,+) relaxation seeded from the old
        # closure is exact (hierarchy.l2_decrease_stage); any increase
        # — or a too-large touched set — falls back to the full FW
        h = levels[-1]
        fast = None
        if cur.size and bool(np.all(h.l2_w[cur] <= lw_old[cur])):
            fast = hierarchy.l2_decrease_stage(h, d2, d2_next, cur,
                                               force=force)
        if fast is not None:
            d2, d2_next = fast
            top_closure = "decrease"
        else:
            d2, d2_next = hierarchy.l2_stage(h, force=force)
            top_closure = "full_fw"
    return {
        "fields": {"sf_closure": tuple(closures),
                   "sf_next": tuple(nexts), "l2row": tuple(rows_t),
                   "d2": d2, "d2_next": d2_next},
        "ov_slot": hierarchy.ov_slot_map(plan),
        "l2_slot": l2_slots,
        "top_closure": top_closure,
    }


def refresh_piece_stage(plan: BuildPlan, g_new, dirty_gids: np.ndarray,
                        piece_flat: np.ndarray, piece_next: np.ndarray,
                        dist_to_agent: np.ndarray, *,
                        force=None) -> None:
    """Recompute only the dirty pieces, writing their APSP + witness
    blocks in place into the flat tables and re-deriving dist-to-agent
    for their members from the agent's APSP row (paths from a
    represented node to its agent never leave the piece, Props 3-9)."""
    for cap in PIECE_BUCKETS:
        gids = [g for g in dirty_gids if plan.piece_cap[g] == cap]
        if not gids:
            continue
        adjs = [_piece_adj(g_new, plan.piece_members[gid], cap)
                for gid in gids]
        blocks, nexts = _fw_bucket(adjs, force=force, pad_pow2=True)
        for gid, block, nxt in zip(gids, blocks, nexts):
            base = plan.piece_base[gid]
            piece_flat[base:base + cap * cap] = block.reshape(-1)
            piece_next[base:base + cap * cap] = nxt.reshape(-1)
            members = plan.piece_members[gid]
            inner = members != plan.piece_agent[gid]
            dist_to_agent[members[inner]] = block[
                plan.piece_agent_pos[gid], np.nonzero(inner)[0]]


def refresh_index(dix: DeviceIndex, plan: BuildPlan, g_new, u, v, w, *,
                  w_old=None,
                  force=None) -> tuple[DeviceIndex, RefreshStats]:
    """Incremental index maintenance (DESIGN.md §9; the live-traffic
    path that replaces the full offline pipeline of paper Fig. 7).

    Locality is inherited from the paper's decomposition: a DRA touches
    the rest of G only at its agent (§IV, Props 3-9), so a DRA-internal
    edge dirties exactly one piece; fragments meet only at boundary
    nodes (§V-A), so an intra-fragment edge dirties one fragment's APSP
    plus its boundary-clique Upsilon weights; a cross-fragment edge is
    one E_B overlay slot (§V-A).  Nothing else exists — the same fact
    that makes the query algorithm (§VI-B) two-level makes the update
    problem block-diagonal.

    Given a batch of edge-weight updates (u, v, new_w) against the
    graph the plan currently reflects, re-runs exactly the dirtied
    build stages:

      a. batched FW on the dirty fragments only (refresh_frag_stage),
      b. SUPER slot weights regathered from the new fragment APSP +
         direct E_B writes, then the overlay re-closed by the dense FW
         kernel — skipped entirely when no overlay weight actually
         changed (super_stage; a warm-started BF alternative was
         measured out, see sssp.py),
      c. dirty piece APSP blocks rewritten in place into piece_flat,
         with member dist-to-agent re-derived from the agent row,
      d. a brand-new immutable DeviceIndex assembled from the results —
         the caller publishes it as the next epoch while queries keep
         draining on the old one (dist_engine.EpochedEngine).

    ``g_new`` must be the post-update graph (Graph.with_edge_weights);
    the plan's weight caches are mutated to match, so consecutive
    refreshes compose — and an exception anywhere mid-refresh rolls the
    caches back, so a failed refresh leaves plan and published index
    consistent.  ``w_old`` (the updated edges' previous weights, which
    EpochedEngine passes) is what classifies the batch direction in the
    stats; without it, piece-internal changes are invisible to the
    overlay-delta fallback.  Exactness: every stage recomputes from
    true weights (never patches distances), so the result is
    array-equal to a from-scratch build on g_new — the property the
    differential harness in tests/test_refresh.py enforces per epoch.
    """
    # stage timings flow through the one span API (DESIGN.md §16):
    # trace.timed always fills ``timings`` (the RefreshStats contract)
    # and additionally emits a trace span when the tracer is enabled
    timings: dict = {}
    t_all = time.perf_counter()

    with trace.timed("refresh.classify", timings, "classify",
                     n_updates=len(u)):
        upd = classify_updates(plan, u, v, w)

    frag_w_before = plan.frag_adj[upd.frag_fi, upd.frag_pu,
                                  upd.frag_pv].copy()
    sup_w_before = plan.sup_w.copy()
    hier_undo: dict = {}
    try:
        with trace.timed("refresh.frag_fw", timings, "frag_fw",
                         dirty=int(upd.dirty_frags.size)):
            frag_apsp, brow, frag_next, blocks = refresh_frag_stage(
                plan, dix.frag_apsp, dix.brow, dix.frag_next, upd,
                force=force)

        # ---- SUPER: regather dirty slot weights, re-close overlay ---
        with trace.timed("refresh.super_fw", timings, "super_fw"):
            touched = np.isin(plan.sup_fi, upd.dirty_frags)
            touched_slots = np.concatenate(
                [np.nonzero(touched)[0],
                 upd.eb_slots]).astype(np.int64)
            slot_w_old = sup_w_before[touched_slots]
            if upd.dirty_frags.size:
                super_weights(plan, blocks, frags=upd.dirty_frags)
            plan.sup_w[upd.eb_slots] = upd.eb_w
            slot_w_new = plan.sup_w[touched_slots]
            changed = slot_w_old != slot_w_new
            hier_fields: dict = {}
            l2_slot = getattr(dix, "host_l2_slot", None)
            res_frag = getattr(dix, "host_res_frag", None)
            topgrp_frag = getattr(dix, "host_topgrp_frag", None)
            top_closure = "carry"
            if changed.any():
                if plan.hierarchy_levels >= 2:
                    hres = refresh_hier_stage(plan, dix,
                                              touched_slots[changed],
                                              hier_undo, force=force)
                    hier_fields = dict(hres["fields"])
                    ov_slot = hres["ov_slot"]
                    l2_slot = hres["l2_slot"]
                    top_closure = hres["top_closure"]
                    d_super, super_next = dix.d_super, dix.super_next
                    # re-lift the resident rows against the refreshed
                    # per-level tables (same deterministic stage as
                    # the build, so refresh == rebuild stays
                    # array-equal)
                    rbase = {name: hier_fields.get(name,
                                                   getattr(dix, name))
                             for name in ("l2row", "bnd2_sid",
                                          "pos_in_sf", "d2")}
                    rres = resident_stage(plan, rbase)
                    if rres is not None:
                        hier_fields.update(rres["fields"])
                        res_frag = rres["res_frag"]
                        topgrp_frag = rres["topgrp_frag"]
                else:
                    d_super, super_next = super_stage(plan,
                                                      force=force)
                    ov_slot = overlay_slot_table(plan)
                    top_closure = "dense"
            else:
                # no overlay weight changed: closure AND witnesses are
                # still exact, so the path tables carry over too
                # (hier_fields stays empty — per-level tables and the
                # resident rows carry too)
                d_super, super_next = dix.d_super, dix.super_next
                ov_slot = getattr(dix, "host_ov_slot", None)

        # ---- hub labels (DESIGN.md §15) -----------------------------
        # a label folds a brow leg with the overlay closure, so it is
        # stale iff the closure moved (changed.any()) OR any labeled
        # fragment's boundary rows did (dirty_frags); otherwise every
        # input is unchanged and carrying the rows is bit-identical to
        # recomputing them — the refresh == rebuild invariant the
        # differential harness in tests/test_hublabels.py enforces
        with trace.timed("refresh.hub", timings, "hub"):
            hub_fields: dict = {}
            hub_agent = getattr(dix, "host_hub_agent", None)
            hub_topgrp = None
            if plan.hub_nodes is not None and len(plan.hub_nodes):
                hub_frags = np.unique(plan.frag_of[
                    plan.agent_of[plan.hub_nodes].astype(np.int64)])
                if changed.any() or np.intersect1d(
                        upd.dirty_frags, hub_frags).size:
                    hub = hub_stage(plan, hub_base_fields(
                        plan,
                        lambda name: hier_fields.get(
                            name, getattr(dix, name))
                        if name != "d_super" else d_super, brow))
                    if hub is not None:
                        hub_fields = hub["fields"]
                        hub_agent = hub["hub_agent"]
                        hub_topgrp = hub["topgrp_frag"]

        # ---- pieces + dist-to-agent ---------------------------------
        with trace.timed("refresh.pieces", timings, "pieces",
                         dirty=int(upd.dirty_gids.size)):
            if upd.dirty_gids.size:
                piece_flat = np.asarray(dix.piece_flat).copy()
                piece_next = np.asarray(dix.piece_next).copy()
                dist_to_agent = np.asarray(dix.dist_to_agent).copy()
                refresh_piece_stage(plan, g_new, upd.dirty_gids,
                                    piece_flat, piece_next,
                                    dist_to_agent, force=force)
                piece_flat_j = jnp.asarray(piece_flat)
                piece_next_j = jnp.asarray(piece_next)
                dist_j = jnp.asarray(dist_to_agent)
            else:
                piece_flat_j = dix.piece_flat
                piece_next_j = dix.piece_next
                dist_j = dix.dist_to_agent
    except BaseException:
        # roll the weight caches back: the caller never published a new
        # epoch, so the plan must keep describing the old one
        plan.frag_adj[upd.frag_fi, upd.frag_pu,
                      upd.frag_pv] = frag_w_before
        plan.frag_adj[upd.frag_fi, upd.frag_pv,
                      upd.frag_pu] = frag_w_before
        plan.sup_w[:] = sup_w_before
        for lv in hier_undo.get("levels", []):
            lv["hier"].sf_adj[lv["sfs"]] = lv["sf_adj"]
            lv["hier"].l2_w[:] = lv["l2_w"]
        raise

    # batch direction: against the edges' previous weights when the
    # caller provides them; the overlay delta alone cannot see
    # piece-internal changes
    if w_old is not None:
        delta = np.asarray(w, np.float64) - np.asarray(w_old, np.float64)
        total_increase = float(np.maximum(0.0, delta).sum())
    else:
        fin = np.isfinite(slot_w_old) & np.isfinite(slot_w_new)
        total_increase = float(np.maximum(
            0.0, slot_w_new[fin] - slot_w_old[fin]).sum())

    timings["total"] = time.perf_counter() - t_all
    trace.event("refresh.apply", t_all, t_all + timings["total"],
                n_updates=len(u), top_closure=top_closure,
                dirty_frags=int(upd.dirty_frags.size))
    new_dix = dataclasses.replace(
        dix, frag_apsp=frag_apsp, frag_next=frag_next, brow=brow,
        d_super=d_super, super_next=super_next,
        piece_flat=piece_flat_j, piece_next=piece_next_j,
        dist_to_agent=dist_j, **hier_fields, **hub_fields)
    if ov_slot is not None:
        new_dix.host_ov_slot = ov_slot
    if l2_slot is not None:
        new_dix.host_l2_slot = l2_slot
    if res_frag is not None:
        new_dix.host_res_frag = res_frag
        new_dix.host_topgrp_frag = topgrp_frag
    if hub_agent is not None:
        new_dix.host_hub_agent = hub_agent
        if getattr(new_dix, "host_topgrp_frag", None) is None:
            # hierarchical epoch without resident rows: the hub gate's
            # TOP-group map must survive the epoch swap (replace()
            # never copies host sidecars)
            if hub_topgrp is None:
                hub_topgrp = getattr(dix, "host_topgrp_frag", None)
            if hub_topgrp is not None:
                new_dix.host_topgrp_frag = hub_topgrp
    stats = RefreshStats(
        n_updates=int(np.asarray(u).size),
        n_dirty_frags=int(upd.dirty_frags.size), n_frags=plan.k,
        n_dirty_pieces=int(upd.dirty_gids.size),
        n_pieces=plan.n_pieces,
        n_eb_slots=int(upd.eb_slots.size), n_inert=upd.n_inert,
        total_increase=total_increase,
        decrease_only=total_increase == 0.0, timings=timings,
        top_closure=top_closure)
    return new_dix, stats


# ---------------------------------------------------------------------------
# serving.  Witness conventions (DESIGN.md §10): the *_w variants return
# (dist, wit) with wit int32 per query:
#   same-DRA bucket:  WIT_PIECE (same-piece table won) or WIT_VIA_AGENT
#   cross buckets:    x * (S+1) + y — the winning SUPER boundary pair —
#                     or WIT_LOCAL (intra-fragment path won)
#   any bucket:       WIT_NONE when the distance is +inf
# The host-side PathUnwinder (paths.py) turns (s, t, wit) into a node
# sequence by walking frag_next / piece_next / super_next.
# ---------------------------------------------------------------------------
WIT_NONE = -1       # unreachable; nothing to unwind
WIT_LOCAL = -2      # case 2, intra-fragment path beat the SUPER combine
WIT_VIA_AGENT = 0   # case 1, s -> agent -> t
WIT_PIECE = 1       # case 1, same-piece direct path


def _same_dra_dist(dix: DeviceIndex, s, t, ds, dt):
    """Case 1: same agent.  Same piece -> one flat gather; else via
    agent.  The flat layout replaces the per-bucket Python loop with a
    single padded gather over piece_flat."""
    gid_s = dix.piece_gid[s]
    same_piece = (gid_s >= 0) & (gid_s == dix.piece_gid[t])
    d_via_agent = ds + dt
    idx = (dix.piece_base[s]
           + dix.pos_in_piece[s] * dix.piece_stride[s]
           + dix.pos_in_piece[t])
    d_piece = dix.piece_flat[jnp.where(same_piece, idx, 0)]
    return jnp.where(same_piece, jnp.minimum(d_piece, d_via_agent),
                     d_via_agent)


def _overlay_size(dix: DeviceIndex) -> int:
    """S + 1: the witness packing stride and the sentinel super id + 1.
    Hierarchical indices carry it as the bottom sf_of's length (their
    d_super is a [1, 1] dummy); dense indices as d_super's side."""
    return (dix.sf_of[0].shape[0] if len(dix.sf_of)
            else dix.d_super.shape[0])


def leg_tiles(counts):
    """The grouped legs' tile rule -> (bq, tiles): a group with
    counts[g] live rows takes tiles[g] = ceil(counts[g] / bq) q tiles
    of bq rows.  The device schedule (_leg_schedule) and the planner's
    ``leg_rows`` count both use it, on jnp and numpy counts alike."""
    bq = ops.GROUPED_TILE_ROWS
    return bq, -(-counts // bq)


def _leg_key(grp_s, grp_t, valid, ng: int):
    """Each row's key for the grouped legs at one level: the group both
    its sides lie in, or ``ng`` (no live row) where they lie in
    different groups, a side is sentinel-only (group ``ng``) or
    ``valid`` (None: every row) is False."""
    gs = grp_s[:, 0]
    keyed = (gs == grp_t[:, 0]) & (gs < ng)
    if valid is not None:
        keyed &= valid
    return jnp.where(keyed, gs, ng)


def _leg_schedule(key, ng: int):
    """Grouped-tile schedule of one level's leg, static in shape, from
    each row's key (its group, or ``ng``: no live row) -> (bq, slot,
    tile_group, live).  The live rows of group g fill tiles[g] tiles of
    bq rows (leg_tiles), groups in order and rows in their batch order;
    slot [q] is each row's place in that [T * bq] layout (T * bq, out
    of range, for a keyless row), tile_group [T] each tile's group and
    live the live tile count.  T = ceil(q / bq) + ng bounds the live
    tiles, since each group wastes less than one tile."""
    q = key.shape[0]
    hit = key[:, None] == jnp.arange(ng, dtype=key.dtype)[None, :]
    counts = jnp.sum(hit, axis=0, dtype=jnp.int32)
    bq, tiles = leg_tiles(counts)
    n_tiles = -(-q // bq) + ng
    ends = jnp.cumsum(tiles)
    rank = jnp.sum(jnp.where(hit, jnp.cumsum(hit, axis=0, dtype=jnp.int32),
                             0), axis=1) - 1
    first = (ends - tiles)[jnp.minimum(key, ng - 1)]
    slot = jnp.where(key < ng, first * bq + rank, n_tiles * bq)
    tile_group = jnp.sum(ends[None, :] <= jnp.arange(n_tiles)[:, None],
                         axis=1, dtype=jnp.int32)
    return bq, slot, jnp.minimum(tile_group, ng - 1), ends[-1]


def _hier_leg(dix: DeviceIndex, li: int, row_s, grp_s, pos_s,
              row_t, grp_t, pos_t, *, valid=None, force=None):
    """Same-group leg at grouping level ``li``: min over slot pairs
    (i, j) in the SAME level-li group of
    row_s[i] + sf_closure[li][g, pos_i, pos_j] + row_t[j].

    Accelerator: all valid slots of one side lie in one group (groups
    nest; _lift_compact relies on it too), so the leg is a two-sided
    (min,+) contraction against that group's closure block, and only
    the rows whose two sides share a real group can win.  Each row is
    keyed by that group (_leg_key), or by the sentinel ``ng`` where its
    sides lie in different groups, a side is sentinel-only, or
    ``valid`` is False (the planner's s = t filler rows, which share
    every group); _leg_schedule packs the keyed rows into q tiles of
    one group each.  Each side is scattered straight into that grouped
    layout in its group's m2 coordinates (its sentinel slots, whose
    group differs from slot 0's, are dropped, and so are keyless
    rows), one grouped minplus_twoside contracts every tile against
    its own group's closure block, and each keyed row reads its slot
    back; a keyless row gets +inf, as the gather's mask gives it.
    That streams sum_g ceil(n_g / bq) * bq rows per level, n_g the
    rows keyed g.
    CPU/ref: the gather, chunked over the s-axis so the gathered block
    stays [q, 8, width], over every row (``valid`` unused).  Both are
    exact: every sum is an integer-valued f32 below 2**24."""
    q, mbs = row_s.shape
    mbt = row_t.shape[1]
    if ops.use_pallas(force):
        ng, m2 = dix.sf_closure[li].shape[:2]
        ng -= 1                                  # last group: sentinel
        key = _leg_key(grp_s, grp_t, valid, ng)
        with jax.named_scope(f"hier_leg_l{li}"):
            bq, slot, tile_group, live = _leg_schedule(key, ng)
            n_rows = tile_group.shape[0] * bq

            def grouped(row, grp, pos):
                own = jnp.where(grp == grp[:, :1], pos, m2)  # m2: dropped
                return jnp.full((n_rows, m2), INF, row.dtype).at[
                    slot[:, None], own].min(row, mode="drop")

            out = ops.minplus_twoside_grouped(
                grouped(row_s, grp_s, pos_s), dix.sf_closure[li],
                grouped(row_t, grp_t, pos_t), tile_group, live,
                force=force)
            return jnp.where(key < ng, out[jnp.minimum(slot, n_rows - 1)],
                             INF)
    c = min(8, mbs)                    # widths are padded to mult of 8

    def body(i, acc):
        r_c = jax.lax.dynamic_slice_in_dim(row_s, i * c, c, axis=1)
        g_c = jax.lax.dynamic_slice_in_dim(grp_s, i * c, c, axis=1)
        p_c = jax.lax.dynamic_slice_in_dim(pos_s, i * c, c, axis=1)
        blk = dix.sf_closure[li][g_c[:, :, None], p_c[:, :, None],
                                 pos_t[:, None, :]]      # [q, c, mbt]
        same = g_c[:, :, None] == grp_t[:, None, :]
        cand = jnp.min(jnp.where(same, r_c[:, :, None] + blk, INF),
                       axis=1)
        return jnp.minimum(acc, cand)

    with jax.named_scope(f"hier_leg_l{li}"):
        tmp = jax.lax.fori_loop(0, mbs // c, body,
                                jnp.full((q, mbt), INF, row_s.dtype))
        return jnp.min(tmp + row_t, axis=1)


def _lift_compact(dix: DeviceIndex, li: int, row, grp, pos):
    """Lift a compact boundary row one level: out[q, j] = min_b
    row[q, b] + l2row[li][grp_b, pos_b, j].  All valid slots of one
    side share one group per level (groups nest), so the output stays
    COMPACT — its next-level ids are that group's bnd2_sid row, read
    by the caller — instead of scattering to a dense [q, S_{l+1}+1]
    row at every level.  Chunked so the gathered block stays
    [q, 8, mb']."""
    q, mb = row.shape
    c = min(8, mb)
    mbn = dix.l2row[li].shape[2]

    def body(i, acc):
        r_c = jax.lax.dynamic_slice_in_dim(row, i * c, c, axis=1)
        g_c = jax.lax.dynamic_slice_in_dim(grp, i * c, c, axis=1)
        p_c = jax.lax.dynamic_slice_in_dim(pos, i * c, c, axis=1)
        l2_c = dix.l2row[li][g_c, p_c]           # [q, c, mb']
        return jnp.minimum(acc,
                           jnp.min(r_c[:, :, None] + l2_c, axis=1))

    with jax.named_scope(f"lift_l{li}"):
        return jax.lax.fori_loop(0, mb // c, body,
                                 jnp.full((q, mbn), INF, row.dtype))


def _lift_src_of(dix: DeviceIndex, li: int, row, ids, grp, pos, wc):
    """Witness recovery for one lift: the level-li id whose lifted
    contribution achieved the next-level row at target id ``wc`` (same
    chunked schedule as _lift_compact, carrying a running argmin;
    exact f32 re-comparison)."""
    q, mb = row.shape
    c = min(8, mb)

    def body(i, carry):
        best, besti = carry
        r_c = jax.lax.dynamic_slice_in_dim(row, i * c, c, axis=1)
        g_c = jax.lax.dynamic_slice_in_dim(grp, i * c, c, axis=1)
        p_c = jax.lax.dynamic_slice_in_dim(pos, i * c, c, axis=1)
        l2_c = dix.l2row[li][g_c, p_c]
        sid_c = dix.bnd2_sid[li][g_c]
        m = sid_c == wc[:, None, None]
        contrib = jnp.min(jnp.where(m, r_c[:, :, None] + l2_c, INF),
                          axis=2)                # [q, c]
        cmin = jnp.min(contrib, axis=1)
        loc = jnp.argmin(contrib, axis=1).astype(jnp.int32)
        better = cmin < best
        return (jnp.where(better, cmin, best),
                jnp.where(better, i * c + loc, besti))

    _best, besti = jax.lax.fori_loop(
        0, mb // c, body,
        (jnp.full((q,), INF, row.dtype), jnp.zeros((q,), jnp.int32)))
    return jnp.take_along_axis(ids, besti[:, None], axis=1)[:, 0]


def _scatter_top(dix: DeviceIndex, row, ids):
    """Scatter a compact top-level row into dense d2 coordinates."""
    q = row.shape[0]
    stp1 = dix.d2.shape[0]
    qi = jnp.arange(q, dtype=jnp.int32)[:, None]
    return jnp.full((q, stp1), INF, row.dtype).at[qi, ids].min(row)


def _top_mid_gather(dix: DeviceIndex, row_s, ids_s, row_t, ids_t):
    """Contract compact top rows against d2 WITHOUT scattering:

      mid = min_{x,y} row_s[x] + d2[ids_s[x], ids_t[y]] + row_t[y]

    The scattered row is +inf outside its own top-group boundary
    columns, so gathering d2 at just [ids_s x ids_t] is bit-identical
    to scatter + full minplus_twoside while touching mb_s*mb_t of the
    (S_top+1)^2 closure (~8x less on road64k).  Sentinel slots carry
    id S_top, which indexes d2's +inf row/col — no masking needed.
    Same chunked-gather idiom as the dense CPU witness path, but with
    the largest chunk that divides the (pad_to-8) width — bigger
    gather blocks amortize XLA's per-slice overhead (~25% on the
    road64k top width of 552)."""
    q, mb = row_s.shape
    c = next(cc for cc in (24, 16, 8, mb) if mb % cc == 0)

    def body(i, acc):
        r_c = jax.lax.dynamic_slice_in_dim(row_s, i * c, c, axis=1)
        b_c = jax.lax.dynamic_slice_in_dim(ids_s, i * c, c, axis=1)
        blk = dix.d2[b_c[:, :, None], ids_t[:, None, :]]  # [q, c, mb_t]
        return jnp.minimum(acc,
                           jnp.min(r_c[:, :, None] + blk, axis=1))

    tmp = jax.lax.fori_loop(
        0, mb // c, body,
        jnp.full((q, row_t.shape[1]), INF, row_s.dtype))
    return jnp.min(tmp + row_t, axis=1)


def _combine_mid_h(dix: DeviceIndex, row_s, bs, row_t, bt, *,
                   valid=None, force=None):
    """Hierarchical combine (hierarchy_levels=N, DESIGN.md §12-13):

      mid = min_{x,y} row_s[x] + OD(x, y) + row_t[y]

    where OD decomposes per level: either both sides sit in the same
    level-l group (its resident closure answers exactly — the va
    legs), or the route crosses every level's boundary and the TOP
    closure answers against both rows lifted level by level (the vb
    leg).  On an accelerator every leg is a fused minplus_twoside
    contraction: each va leg scatters the rows whose sides share a
    group (and are ``valid``) into tiles of one group each, in that
    group's closure coordinates, and contracts them all in one grouped
    call (_hier_leg), and the vb leg scatters both rows dense against
    d2, as the dense path does.  On CPU the va legs gather their closure entries, and
    the vb leg stays compact and gathers only each side's own
    top-group boundary columns of d2 (_top_mid_gather — bit-identical,
    the scattered row is +inf everywhere else).  The lift state stays
    compact ([q, width] + ids) until the top; one grouping level
    reproduces the two-level combine bit-for-bit (min re-association
    is exact in f32).
    """
    L = len(dix.sf_of)
    q = row_s.shape[0]
    ids_s, ids_t = bs, bt
    va = jnp.full((q,), INF, row_s.dtype)
    for li in range(L):
        grp_s, pos_s = dix.sf_of[li][ids_s], dix.pos_in_sf[li][ids_s]
        grp_t, pos_t = dix.sf_of[li][ids_t], dix.pos_in_sf[li][ids_t]
        va = jnp.minimum(va, _hier_leg(dix, li, row_s, grp_s, pos_s,
                                       row_t, grp_t, pos_t, valid=valid,
                                       force=force))
        new_s = _lift_compact(dix, li, row_s, grp_s, pos_s)
        new_t = _lift_compact(dix, li, row_t, grp_t, pos_t)
        # slot 0 is valid-first by construction, so its group IS the
        # side's group (sentinel-only rows land on the sentinel group,
        # whose bnd2_sid row is all-sentinel and whose rows are +inf)
        ids_s = dix.bnd2_sid[li][grp_s[:, 0]]
        ids_t = dix.bnd2_sid[li][grp_t[:, 0]]
        row_s, row_t = new_s, new_t
    with jax.named_scope("top_combine"):
        if ops.use_pallas(force):
            vb = ops.minplus_twoside(_scatter_top(dix, row_s, ids_s),
                                     dix.d2,
                                     _scatter_top(dix, row_t, ids_t),
                                     force=force)
        else:
            vb = _top_mid_gather(dix, row_s, ids_s, row_t, ids_t)
    return jnp.minimum(va, vb)


def _hier_leg_w(dix: DeviceIndex, li: int, row_s, ids_s, grp_s, pos_s,
                row_t, ids_t, grp_t, pos_t):
    """_hier_leg carrying its argmin -> (va, xa, ya) with the winning
    pair expressed as level-li overlay ids."""
    q, mbs = row_s.shape
    mbt = row_t.shape[1]
    c = min(8, mbs)

    def body(i, carry):
        acc, accb = carry
        r_c = jax.lax.dynamic_slice_in_dim(row_s, i * c, c, axis=1)
        g_c = jax.lax.dynamic_slice_in_dim(grp_s, i * c, c, axis=1)
        p_c = jax.lax.dynamic_slice_in_dim(pos_s, i * c, c, axis=1)
        blk = dix.sf_closure[li][g_c[:, :, None], p_c[:, :, None],
                                 pos_t[:, None, :]]
        same = g_c[:, :, None] == grp_t[:, None, :]
        cube = jnp.where(same, r_c[:, :, None] + blk, INF)
        cand = jnp.min(cube, axis=1)
        hit = cube == cand[:, None, :]
        loc = jnp.min(jnp.where(
            hit, jax.lax.broadcasted_iota(jnp.int32, cube.shape, 1),
            jnp.int32(mbs)), axis=1)
        better = cand < acc
        return (jnp.where(better, cand, acc),
                jnp.where(better, i * c + loc, accb))

    acc0 = jnp.full((q, mbt), INF, row_s.dtype)
    accb0 = jnp.full((q, mbt), -1, jnp.int32)
    acc, accb = jax.lax.fori_loop(0, mbs // c, body, (acc0, accb0))
    tmp = acc + row_t
    va = jnp.min(tmp, axis=1)
    hit = tmp == va[:, None]
    pos_tw = jnp.min(jnp.where(
        hit, jnp.arange(mbt, dtype=jnp.int32)[None, :], jnp.int32(mbt)),
        axis=1)
    pos_tc = jnp.clip(pos_tw, 0, mbt - 1)
    pos_sw = jnp.take_along_axis(accb, pos_tc[:, None], axis=1)[:, 0]
    xa = jnp.take_along_axis(
        ids_s, jnp.clip(pos_sw, 0, mbs - 1)[:, None], axis=1)[:, 0]
    ya = jnp.take_along_axis(ids_t, pos_tc[:, None], axis=1)[:, 0]
    return va, xa, ya


def _combine_mid_h_w(dix: DeviceIndex, row_s, bs, row_t, bt, *,
                     force=None):
    """Witness variant of _combine_mid_h -> (mid, wx, wy): the winning
    level-1 SUPER pair under the hierarchical overlay metric.  Each
    same-group leg carries its argmin; the top leg gets the winning
    boundary pair (c, d) from the fused argmin kernel and resolves it
    back DOWN the ladder: at each level the winning id either comes
    from that level's same-group leg (if it won) or is un-lifted one
    level by re-finding the row entry whose lift achieved the
    next-level row (an O(q * width) masked argmin — exact because the
    lift is a min of f32 sums re-comparable bit-for-bit).
    """
    L = len(dix.sf_of)
    q = row_s.shape[0]
    ids_s, ids_t = bs, bt
    states = []
    vas, legx, legy = [], [], []
    for li in range(L):
        grp_s, pos_s = dix.sf_of[li][ids_s], dix.pos_in_sf[li][ids_s]
        grp_t, pos_t = dix.sf_of[li][ids_t], dix.pos_in_sf[li][ids_t]
        states.append((row_s, ids_s, grp_s, pos_s,
                       row_t, ids_t, grp_t, pos_t))
        va, xa, ya = _hier_leg_w(dix, li, row_s, ids_s, grp_s, pos_s,
                                 row_t, ids_t, grp_t, pos_t)
        vas.append(va)
        legx.append(xa)
        legy.append(ya)
        row_s = _lift_compact(dix, li, row_s, grp_s, pos_s)
        row_t = _lift_compact(dix, li, row_t, grp_t, pos_t)
        ids_s = dix.bnd2_sid[li][grp_s[:, 0]]
        ids_t = dix.bnd2_sid[li][grp_t[:, 0]]
    vb, wc, wd = ops.minplus_twoside_argmin(
        _scatter_top(dix, row_s, ids_s), dix.d2,
        _scatter_top(dix, row_t, ids_t), force=force)
    mid = vb
    for va in vas:
        mid = jnp.minimum(mid, va)
    # winner selection, lowest level first (same tie preference as the
    # two-level code: a same-group leg beats the lifted leg)
    taken = jnp.zeros((q,), bool)
    wins = []
    for va in vas:
        w = (va == mid) & ~taken
        taken = taken | w
        wins.append(w)
    cur_x, cur_y = wc, wd
    for li in range(L - 1, -1, -1):
        (r_s, i_s, g_s, p_s, r_t, i_t, g_t, p_t) = states[li]
        dx = _lift_src_of(dix, li, r_s, i_s, g_s, p_s, cur_x)
        dy = _lift_src_of(dix, li, r_t, i_t, g_t, p_t, cur_y)
        cur_x = jnp.where(wins[li], legx[li], dx)
        cur_y = jnp.where(wins[li], legy[li], dy)
    fin = jnp.isfinite(mid)
    wx = jnp.where(fin, cur_x, -1)
    wy = jnp.where(fin, cur_y, -1)
    return mid, wx, wy


def _combine_mid(dix: DeviceIndex, row_s, bs, row_t, bt, *, valid=None,
                 force=None):
    """combine = min_{b1,b2} row_s[b1] + D_super[bs[b1], bt[b2]]
    + row_t[b2] without a [q, mb, mb] intermediate.

    Hierarchical indices (non-empty sf_of tuple — a static trace-time
    treedef fact) route to _combine_mid_h.  Dense indices:
    TPU: scatter-min the boundary rows into SUPER coordinates (one
    O(q*mb) scatter each) and run the fused two-sided tropical kernel
    against the resident D_super.  CPU/ref: chunk the b1 axis so the
    gathered block never exceeds [q, 8, mb].  ``valid`` (bool [q], or
    None: every row) marks the rows whose answer is used; the
    hierarchical legs skip the others on an accelerator.
    """
    if len(dix.sf_of):
        return _combine_mid_h(dix, row_s, bs, row_t, bt, valid=valid,
                              force=force)
    with jax.named_scope("top_combine"):
        if ops.use_pallas(force):
            s1 = dix.d_super.shape[0]
            q = row_s.shape[0]
            qi = jnp.arange(q, dtype=jnp.int32)[:, None]
            rs = jnp.full((q, s1), INF,
                          row_s.dtype).at[qi, bs].min(row_s)
            rt = jnp.full((q, s1), INF,
                          row_t.dtype).at[qi, bt].min(row_t)
            return ops.minplus_twoside(rs, dix.d_super, rt, force=force)
        q, mb = row_s.shape
        c = min(8, mb)                   # mb is padded to a multiple of 8

        def body(i, acc):
            r_c = jax.lax.dynamic_slice_in_dim(row_s, i * c, c, axis=1)
            b_c = jax.lax.dynamic_slice_in_dim(bs, i * c, c, axis=1)
            blk = dix.d_super[b_c[:, :, None],
                              bt[:, None, :]]          # [q, c, mb]
            cand = jnp.min(r_c[:, :, None] + blk, axis=1)
            return jnp.minimum(acc, cand)

        tmp = jax.lax.fori_loop(0, mb // c, body,
                                jnp.full((q, mb), INF, row_s.dtype))
        return jnp.min(tmp + row_t, axis=1)


def _combine_mid_w(dix: DeviceIndex, row_s, bs, row_t, bt, *,
                   force=None):
    """Witness variant of _combine_mid -> (mid, wx, wy) where (wx, wy)
    is the winning SUPER boundary pair in super ids (-1 when mid is
    +inf).  Same two layouts as the distance path: fused argmin kernel
    against the scattered rows on TPU, b1-chunked gather on CPU;
    hierarchical indices route to _combine_mid_h_w."""
    if len(dix.sf_of):
        return _combine_mid_h_w(dix, row_s, bs, row_t, bt, force=force)
    if ops.use_pallas(force):
        s1 = dix.d_super.shape[0]
        q = row_s.shape[0]
        qi = jnp.arange(q, dtype=jnp.int32)[:, None]
        rs = jnp.full((q, s1), INF, row_s.dtype).at[qi, bs].min(row_s)
        rt = jnp.full((q, s1), INF, row_t.dtype).at[qi, bt].min(row_t)
        return ops.minplus_twoside_argmin(rs, dix.d_super, rt,
                                          force=force)
    q, mb = row_s.shape
    c = min(8, mb)                       # mb is padded to a multiple of 8

    def body(i, carry):
        acc, accb = carry
        r_c = jax.lax.dynamic_slice_in_dim(row_s, i * c, c, axis=1)
        b_c = jax.lax.dynamic_slice_in_dim(bs, i * c, c, axis=1)
        blk = dix.d_super[b_c[:, :, None], bt[:, None, :]]  # [q, c, mb]
        cube = r_c[:, :, None] + blk
        cand = jnp.min(cube, axis=1)
        hit = cube == cand[:, None, :]
        loc = jnp.min(jnp.where(
            hit, jax.lax.broadcasted_iota(jnp.int32, cube.shape, 1),
            jnp.int32(mb)), axis=1)
        better = cand < acc
        return (jnp.where(better, cand, acc),
                jnp.where(better, i * c + loc, accb))

    acc0 = jnp.full((q, mb), INF, row_s.dtype)
    accb0 = jnp.full((q, mb), -1, jnp.int32)
    acc, accb = jax.lax.fori_loop(0, mb // c, body, (acc0, accb0))
    tmp = acc + row_t                    # [q, mb]
    mid = jnp.min(tmp, axis=1)
    hit = tmp == mid[:, None]
    pos_t = jnp.min(jnp.where(
        hit, jnp.arange(mb, dtype=jnp.int32)[None, :], jnp.int32(mb)),
        axis=1)
    pos_t_c = jnp.clip(pos_t, 0, mb - 1)
    pos_s = jnp.take_along_axis(accb, pos_t_c[:, None], axis=1)[:, 0]
    fin = jnp.isfinite(mid)
    wx = jnp.where(fin, jnp.take_along_axis(
        bs, jnp.clip(pos_s, 0, mb - 1)[:, None], axis=1)[:, 0], -1)
    wy = jnp.where(fin, jnp.take_along_axis(
        bt, pos_t_c[:, None], axis=1)[:, 0], -1)
    return mid, wx, wy


def serve_same_dra(dix: DeviceIndex, s: jax.Array,
                   t: jax.Array) -> jax.Array:
    """Planner bucket 1: both endpoints in the same DRA."""
    ds, dt = dix.dist_to_agent[s], dix.dist_to_agent[t]
    out = _same_dra_dist(dix, s, t, ds, dt)
    return jnp.where(s == t, 0.0, out)


def serve_same_dra_w(dix: DeviceIndex, s: jax.Array, t: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """serve_same_dra in return_witness mode -> (dist, wit) with wit in
    {WIT_PIECE, WIT_VIA_AGENT, WIT_NONE}."""
    ds, dt = dix.dist_to_agent[s], dix.dist_to_agent[t]
    gid_s = dix.piece_gid[s]
    same_piece = (gid_s >= 0) & (gid_s == dix.piece_gid[t])
    d_via_agent = ds + dt
    idx = (dix.piece_base[s]
           + dix.pos_in_piece[s] * dix.piece_stride[s]
           + dix.pos_in_piece[t])
    d_piece = dix.piece_flat[jnp.where(same_piece, idx, 0)]
    out = jnp.where(same_piece, jnp.minimum(d_piece, d_via_agent),
                    d_via_agent)
    wit = jnp.where(same_piece & (d_piece <= d_via_agent),
                    WIT_PIECE, WIT_VIA_AGENT)
    out = jnp.where(s == t, 0.0, out)
    wit = jnp.where(jnp.isfinite(out), wit, WIT_NONE)
    return out, wit.astype(jnp.int32)


def serve_cross(dix: DeviceIndex, s: jax.Array, t: jax.Array, *,
                with_local: bool, force=None) -> jax.Array:
    """Planner buckets 2/3: endpoints in different DRAs.  with_local
    folds in the intra-fragment distance (same-fragment bucket only,
    so the cross-fragment program skips that gather entirely).  A row
    with s == t (the planner's filler; a real such pair is same-DRA)
    runs no same-group leg on an accelerator: its answer is dropped by
    the planner and set to 0 by serve_step."""
    us, ut = dix.agent_of[s], dix.agent_of[t]
    ds, dt = dix.dist_to_agent[s], dix.dist_to_agent[t]
    fs, ft = dix.frag_of[us], dix.frag_of[ut]
    ps, pt = dix.pos_in_frag[us], dix.pos_in_frag[ut]
    row_s = dix.brow[fs, ps]                     # [q, mb]
    row_t = dix.brow[ft, pt]
    mid = _combine_mid(dix, row_s, dix.bnd_super[fs], row_t,
                       dix.bnd_super[ft],
                       valid=(s != t) & (fs >= 0) & (ft >= 0), force=force)
    if with_local:
        with jax.named_scope("local_fold"):
            mid = jnp.minimum(mid, jnp.where(
                fs == ft, dix.frag_apsp[fs, ps, pt], INF))
    d = ds + mid + dt
    return jnp.where((fs >= 0) & (ft >= 0), d, INF)


def serve_cross_w(dix: DeviceIndex, s: jax.Array, t: jax.Array, *,
                  with_local: bool, force=None
                  ) -> tuple[jax.Array, jax.Array]:
    """serve_cross in return_witness mode -> (dist, wit): wit is the
    packed winning SUPER pair x * (S+1) + y, WIT_LOCAL when the
    intra-fragment path won (same-fragment bucket only), WIT_NONE when
    unreachable."""
    us, ut = dix.agent_of[s], dix.agent_of[t]
    ds, dt = dix.dist_to_agent[s], dix.dist_to_agent[t]
    fs, ft = dix.frag_of[us], dix.frag_of[ut]
    ps, pt = dix.pos_in_frag[us], dix.pos_in_frag[ut]
    row_s = dix.brow[fs, ps]                     # [q, mb]
    row_t = dix.brow[ft, pt]
    mid, wx, wy = _combine_mid_w(dix, row_s, dix.bnd_super[fs], row_t,
                                 dix.bnd_super[ft], force=force)
    s1 = _overlay_size(dix)
    wit = wx * s1 + wy
    if with_local:
        local = jnp.where(fs == ft, dix.frag_apsp[fs, ps, pt], INF)
        wit = jnp.where(local <= mid, WIT_LOCAL, wit)
        mid = jnp.minimum(mid, local)
    d = ds + mid + dt
    d = jnp.where((fs >= 0) & (ft >= 0), d, INF)
    wit = jnp.where(jnp.isfinite(d), wit, WIT_NONE)
    return d, wit.astype(jnp.int32)


def _lift_res(dix: DeviceIndex, row, pos, ridx, cols=None):
    """Resident lift: rs[q, c] = min_b row[q, b] +
    res_rows[ridx, pos_b, c] — the whole per-level lift ladder
    collapsed into one chunked gather against the pre-composed rows.

    With ``cols`` (int32 [q, w]) the output is restricted to those
    d2 columns per query instead of the full S_top+1 width — the CPU
    path passes each endpoint's own top-group boundary ids, cutting
    the gather traffic to match _top_mid_gather's contraction."""
    q, mb = row.shape
    c = min(8, mb)
    stp1 = dix.res_rows.shape[2]

    def body_full(i, acc):
        r_c = jax.lax.dynamic_slice_in_dim(row, i * c, c, axis=1)
        p_c = jax.lax.dynamic_slice_in_dim(pos, i * c, c, axis=1)
        blk = dix.res_rows[ridx[:, None], p_c]   # [q, c, S_top+1]
        return jnp.minimum(acc,
                           jnp.min(r_c[:, :, None] + blk, axis=1))

    def body_cols(i, acc):
        r_c = jax.lax.dynamic_slice_in_dim(row, i * c, c, axis=1)
        p_c = jax.lax.dynamic_slice_in_dim(pos, i * c, c, axis=1)
        blk = dix.res_rows[ridx[:, None, None], p_c[:, :, None],
                           cols[:, None, :]]     # [q, c, w]
        return jnp.minimum(acc,
                           jnp.min(r_c[:, :, None] + blk, axis=1))

    width = stp1 if cols is None else cols.shape[1]
    body = body_full if cols is None else body_cols
    with jax.named_scope("lift_res"):
        return jax.lax.fori_loop(0, mb // c, body,
                                 jnp.full((q, width), INF, row.dtype))


def serve_cross_res(dix: DeviceIndex, s: jax.Array, t: jax.Array, *,
                    force=None) -> jax.Array:
    """Planner bucket 4 (DESIGN.md §13): the resident fast path for hot
    cross-top-group queries.  Both endpoints' fragments must be in
    RESIDENT level-1 groups and in DIFFERENT top-level groups (the
    planner guarantees both) — then the route must touch the top
    boundary, every confined prefix is pre-composed in res_rows, and
    the whole combine is one contraction against d2: a fused
    minplus_twoside on an accelerator, or a gather restricted to each
    endpoint's own top-group boundary columns on CPU (a route's first
    top-boundary contact lies in its endpoint's own top group — the
    confined prefix up to it is exactly what res_rows pre-compose —
    so the restriction is exact).  The same value as the full lift up
    to f32 re-association (the resident rows pre-add the per-level
    legs); exact in the reals, validated against the oracle like
    every other bucket."""
    us, ut = dix.agent_of[s], dix.agent_of[t]
    ds, dt = dix.dist_to_agent[s], dix.dist_to_agent[t]
    fs, ft = dix.frag_of[us], dix.frag_of[ut]
    ps, pt = dix.pos_in_frag[us], dix.pos_in_frag[ut]
    row_s = dix.brow[fs, ps]                     # [q, mb]
    row_t = dix.brow[ft, pt]
    bs, bt = dix.bnd_super[fs], dix.bnd_super[ft]
    pos_s = dix.pos_in_sf[0][bs]
    pos_t = dix.pos_in_sf[0][bt]
    if ops.use_pallas(force):
        rs = _lift_res(dix, row_s, pos_s, dix.res_of_frag[fs])
        rt = _lift_res(dix, row_t, pos_t, dix.res_of_frag[ft])
        mid = ops.minplus_twoside(rs, dix.d2, rt, force=force)
    else:
        ids_s = dix.bnd2_sid[-1][dix.topgrp_of_frag[fs]]
        ids_t = dix.bnd2_sid[-1][dix.topgrp_of_frag[ft]]
        rs = _lift_res(dix, row_s, pos_s, dix.res_of_frag[fs],
                       cols=ids_s)
        rt = _lift_res(dix, row_t, pos_t, dix.res_of_frag[ft],
                       cols=ids_t)
        mid = _top_mid_gather(dix, rs, ids_s, rt, ids_t)
    d = ds + mid + dt
    return jnp.where((fs >= 0) & (ft >= 0), d, INF)


def serve_hub(dix: DeviceIndex, s: jax.Array, t: jax.Array, *,
              force=None) -> jax.Array:
    """Hot-tier hub-label serve (DESIGN.md §15): both endpoints'
    agents must be labeled and in different TOP groups (dense epochs:
    different fragments) — the planner's hub_mask guarantees both —
    then the whole query is two label-row gathers and one O(W)
    (min,+) merge; no per-level lifting, no d2 contraction, no planner
    dispatch.  A mis-gated pair gathers the all-INF sentinel row and
    returns +inf rather than a wrong distance.  Bit-equal to the
    planner cross path: every sum is an integer-valued f32 (graph
    weights are integers), so the merge's re-association is exact."""
    us, ut = dix.agent_of[s], dix.agent_of[t]
    ds, dt = dix.dist_to_agent[s], dix.dist_to_agent[t]
    fs, ft = dix.frag_of[us], dix.frag_of[ut]
    ls = dix.hub_rows[dix.hub_of_agent[us]]      # [q, W]
    lt = dix.hub_rows[dix.hub_of_agent[ut]]
    mid = ops.label_merge(ls, lt, force=force)
    d = ds + mid + dt
    return jnp.where((fs >= 0) & (ft >= 0), d, INF)


def serve_step(dix: DeviceIndex, s: jax.Array, t: jax.Array, *,
               force=None) -> jax.Array:
    """Batched exact distance queries: s, t int32 [q] -> f32 [q].

    The monolithic program (every case in one jit); the query planner
    in dist_engine.py runs the per-case programs instead.
    """
    us, ut = dix.agent_of[s], dix.agent_of[t]
    d_cross = serve_cross(dix, s, t, with_local=True, force=force)
    d_same = serve_same_dra(dix, s, t)
    out = jnp.where(us == ut, d_same, d_cross)
    return jnp.where(s == t, 0.0, out)


def serve_step_w(dix: DeviceIndex, s: jax.Array, t: jax.Array, *,
                 force=None) -> tuple[jax.Array, jax.Array]:
    """serve_step in return_witness mode -> (dist, wit).

    The witness namespace is per-case (same-DRA flags vs packed SUPER
    pairs); the host unwinder re-derives the case from agent_of, so no
    case bits are spent in the witness itself.
    """
    us, ut = dix.agent_of[s], dix.agent_of[t]
    d_cross, w_cross = serve_cross_w(dix, s, t, with_local=True,
                                     force=force)
    d_same, w_same = serve_same_dra_w(dix, s, t)
    same = us == ut
    out = jnp.where(same, d_same, d_cross)
    wit = jnp.where(same, w_same, w_cross)
    return jnp.where(s == t, 0.0, out), wit


def _overlay_row_h(dix: DeviceIndex, rs: jax.Array, *,
                   force=None) -> jax.Array:
    """Exact overlay distances from a scattered source row rs [S+1] to
    EVERY overlay node, through the hierarchy: ascend the ladder
    (within-group (min,+) against the resident closures + boundary
    lift per level), one small vector (x) matrix product against the
    top closure, then descend (lift back through each level's rows,
    min-merged with that level's within-group leg)."""
    L = len(dix.sf_of)
    r = rs
    withins = []
    for li in range(L):
        members = dix.sf_members[li]             # [ng+1, m2] (S_l pad)
        rm = r[members]                          # [ng+1, m2]
        withins.append(jnp.min(rm[:, :, None] + dix.sf_closure[li],
                               axis=1))
        lift = jnp.min(rm[:, :, None] + dix.l2row[li], axis=1)
        np1 = (dix.sf_of[li + 1].shape[0] if li + 1 < L
               else dix.d2.shape[0])
        r = jnp.full((np1,), INF, rs.dtype).at[
            dix.bnd2_sid[li]].min(lift)
    z = ops.minplus(r[None, :], dix.d2, force=force)[0]  # [S_top+1]
    for li in range(L - 1, -1, -1):
        back = z[dix.bnd2_sid[li]]               # [ng+1, mb2]
        via = jnp.min(dix.l2row[li] + back[:, None, :], axis=2)
        out = jnp.minimum(withins[li], via)      # [ng+1, m2]
        sz = dix.sf_of[li].shape[0]
        z = jnp.full((sz,), INF, rs.dtype).at[
            dix.sf_members[li]].min(out)
    return z


def serve_one_to_all(dix: DeviceIndex, s: int | jax.Array, *,
                     force=None) -> jax.Array:
    """Exact distances from one source to EVERY node: [n].

    The bulk/retrieval pattern: scatter the source boundary row into
    SUPER coordinates, one vector-matrix (min,+) product against the
    SUPER matrix (Pallas kernel on TPU), then a per-node gather
    combine.  Used by the retrieval-style benchmarks.
    """
    s = jnp.asarray(s, jnp.int32).reshape(())
    n = dix.agent_of.shape[0]
    us = dix.agent_of[s]
    ds = dix.dist_to_agent[s]
    fs = dix.frag_of[us]
    ps = dix.pos_in_frag[us]
    row_s = dix.brow[fs, ps]                             # [mb]
    bs = dix.bnd_super[fs]                               # [mb]
    s1 = _overlay_size(dix)
    rs = jnp.full((s1,), INF, row_s.dtype).at[bs].min(row_s)
    # u_s -> every super node (vector (x) matrix min-plus; the
    # hierarchical overlay runs it per level)
    if len(dix.sf_of):
        x = _overlay_row_h(dix, rs, force=force)                # [S+1]
    else:
        x = ops.minplus(rs[None, :], dix.d_super, force=force)[0]
    # per-target combine (sentinel slots hit the +inf row of d_super)
    tt = jnp.arange(n, dtype=jnp.int32)
    ut = dix.agent_of[tt]
    dt = dix.dist_to_agent[tt]
    ft = dix.frag_of[ut]
    ptv = dix.pos_in_frag[ut]
    row_t = dix.brow[ft, ptv]                            # [n, mb]
    mid = jnp.min(x[dix.bnd_super[ft]] + row_t, axis=1)  # [n]
    local = jnp.where(ft == fs, dix.frag_apsp[ft, ps, ptv], INF)
    d_cross = ds + jnp.minimum(mid, local) + dt
    d_cross = jnp.where((fs >= 0) & (ft >= 0), d_cross, INF)
    d_same = _same_dra_dist(dix, jnp.broadcast_to(s, tt.shape), tt,
                            jnp.broadcast_to(ds, dt.shape), dt)
    out = jnp.where(us == ut, d_same, d_cross)
    return jnp.where(tt == s, 0.0, out)
