"""GNN architectures: graphcast, dimenet, graphsage, gat.

One unified representation drives all four shapes (DESIGN.md §4):
every batch is a (possibly block-diagonal) flat graph

    node_feat [N, df], edge_src [E], edge_dst [E], loss targets + mask

  * molecule          -> 128 small graphs as one disjoint union
  * full_graph_sm/lg  -> the graph itself
  * minibatch_lg      -> the sampled k-hop subgraph, loss on seed nodes

Message passing is gather -> compute -> segment_sum (JAX has no sparse
SpMM; the scatter/segment formulation IS the system, per the assignment
note).  dimenet adds triplet gathers (edge->edge angular messages);
gat adds segment-softmax edge attention.

Sharding: node and edge arrays are sharded over the *flattened* mesh
(every device owns a slice of edges); weights are replicated.  The
segment_sum over sharded edges lowers to partial sums + reduce-scatter
under SPMD.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import shard_map

from .common import Shardings


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    name: str
    arch: str                  # graphcast | dimenet | graphsage | gat
    n_layers: int
    d_hidden: int
    d_feat: int
    n_classes: int = 64
    n_heads: int = 8           # gat
    aggregator: str = "sum"
    d_edge: int = 4            # graphcast edge features
    n_radial: int = 6          # dimenet bases
    n_spherical: int = 7
    n_bilinear: int = 8
    n_out: int = 1
    dtype: Any = jnp.float32
    # sharded (shard_map) message passing: node/edge arrays stay sharded;
    # per-layer all_gather(h) + psum_scatter(agg) replaces the SPMD
    # full-replication gathers that blow HBM on ogb_products-scale cells
    sharded: bool = False

    def flat_axes(self, sh: Shardings):
        if sh.mesh is None:
            return None
        return tuple(sh.mesh.axis_names)


# ---------------------------------------------------------------------------
def _mlp_init(key, dims, dtype):
    ws = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        key, k1 = jax.random.split(key)
        ws[f"w{i}"] = (jax.random.normal(k1, (a, b), jnp.float32)
                       * (a ** -0.5)).astype(dtype)
        ws[f"b{i}"] = jnp.zeros((b,), dtype)
    return ws


def _mlp(ws, x, act=jax.nn.relu, final_act=False):
    n = len([k for k in ws if k.startswith("w")])
    for i in range(n):
        x = x @ ws[f"w{i}"] + ws[f"b{i}"]
        if i < n - 1 or final_act:
            x = act(x)
    return x


def _segment_sum(values, ids, n, sh: Shardings, flat):
    out = jax.ops.segment_sum(values, ids, num_segments=n)
    return sh.constrain(out, flat, None) if flat else out


def _segment_mean(values, ids, n, sh, flat):
    s = _segment_sum(values, ids, n, sh, flat)
    cnt = jax.ops.segment_sum(jnp.ones((values.shape[0], 1),
                                       values.dtype), ids, num_segments=n)
    return s / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# graphcast: encoder - interaction-network processor - decoder
# ---------------------------------------------------------------------------
def init_graphcast(cfg: GNNConfig, key) -> Dict:
    d = cfg.d_hidden
    keys = jax.random.split(key, 6)
    layers = []
    for i in range(cfg.n_layers):
        k1, k2 = jax.random.split(jax.random.fold_in(keys[0], i))
        layers.append({
            "edge_mlp": _mlp_init(k1, (3 * d, d, d), cfg.dtype),
            "node_mlp": _mlp_init(k2, (2 * d, d, d), cfg.dtype),
        })
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *layers)
    return {
        "enc_node": _mlp_init(keys[1], (cfg.d_feat, d, d), cfg.dtype),
        "enc_edge": _mlp_init(keys[2], (cfg.d_edge, d, d), cfg.dtype),
        "layers": stacked,
        "dec": _mlp_init(keys[3], (d, d, cfg.n_out), cfg.dtype),
    }


def forward_graphcast(cfg: GNNConfig, sh: Shardings, params: Dict,
                      batch: Dict) -> jax.Array:
    flat = cfg.flat_axes(sh)
    x, src, dst = batch["node_feat"], batch["edge_src"], batch["edge_dst"]
    n = x.shape[0]
    h = _mlp(params["enc_node"], x.astype(cfg.dtype))
    e = _mlp(params["enc_edge"], batch["edge_feat"].astype(cfg.dtype))
    h = sh.constrain(h, flat, None)
    e = sh.constrain(e, flat, None)

    def layer(carry, lw):
        h, e = carry
        msg_in = jnp.concatenate([e, h[src], h[dst]], axis=-1)
        e2 = e + _mlp(lw["edge_mlp"], msg_in)
        agg = _segment_sum(e2, dst, n, sh, flat)
        h2 = h + _mlp(lw["node_mlp"],
                      jnp.concatenate([h, agg], axis=-1))
        return (sh.constrain(h2, flat, None),
                sh.constrain(e2, flat, None)), None

    (h, e), _ = jax.lax.scan(jax.checkpoint(layer), (h, e),
                             params["layers"])
    pred = _mlp(params["dec"], h)                     # [N, n_out]
    mask = batch["loss_mask"].astype(jnp.float32)
    err = (pred.astype(jnp.float32)
           - batch["target"].astype(jnp.float32)) ** 2
    return jnp.sum(err.mean(-1) * mask) / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# dimenet: directional message passing with radial/spherical bases
# ---------------------------------------------------------------------------
def init_dimenet(cfg: GNNConfig, key) -> Dict:
    d = cfg.d_hidden
    ks = jax.random.split(key, 8)
    nsr = cfg.n_spherical * cfg.n_radial
    layers = []
    for i in range(cfg.n_layers):
        kk = jax.random.fold_in(ks[0], i)
        k1, k2, k3, k4 = jax.random.split(kk, 4)
        layers.append({
            "msg_mlp": _mlp_init(k1, (d, d, d), cfg.dtype),
            "proj_kj": _mlp_init(k2, (d, d), cfg.dtype),
            "sbf_w": (jax.random.normal(k3, (nsr, cfg.n_bilinear),
                                        jnp.float32) * nsr ** -0.5
                      ).astype(cfg.dtype),
            "bilinear": (jax.random.normal(k4, (cfg.n_bilinear, d, d),
                                           jnp.float32) * d ** -0.5
                         ).astype(cfg.dtype),
        })
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *layers)
    return {
        "embed": _mlp_init(ks[1], (cfg.d_feat + cfg.n_radial, d, d),
                           cfg.dtype),
        "rbf_w": (jax.random.normal(ks[2], (cfg.n_radial, d), jnp.float32)
                  * cfg.n_radial ** -0.5).astype(cfg.dtype),
        "layers": stacked,
        "out": _mlp_init(ks[3], (d, d, cfg.n_out), cfg.dtype),
    }


def _rbf(dist, n_radial):
    """Bessel-style radial basis: sin(n pi d / c) / d."""
    d = jnp.maximum(dist, 1e-3)[:, None]
    n = jnp.arange(1, n_radial + 1, dtype=jnp.float32)
    c = 5.0
    return jnp.sin(n * jnp.pi * d / c) / d


def _sbf(angle, n_spherical, n_radial):
    """cos(l * angle) x radial grid — simplified spherical basis."""
    l = jnp.arange(n_spherical, dtype=jnp.float32)
    a = jnp.cos(angle[:, None] * l)               # [T, n_sph]
    n = jnp.arange(1, n_radial + 1, dtype=jnp.float32)
    r = jnp.sin(n * jnp.pi * 0.5)                 # fixed radial weight
    return (a[:, :, None] * r[None, None, :]).reshape(angle.shape[0], -1)


def forward_dimenet(cfg: GNNConfig, sh: Shardings, params: Dict,
                    batch: Dict) -> jax.Array:
    flat = cfg.flat_axes(sh)
    x, src, dst = batch["node_feat"], batch["edge_src"], batch["edge_dst"]
    dist = batch["edge_dist"]
    t_kj, t_ji, angle = (batch["tri_edge_kj"], batch["tri_edge_ji"],
                         batch["tri_angle"])
    n, e_cnt = x.shape[0], src.shape[0]
    rbf = _rbf(dist, cfg.n_radial).astype(cfg.dtype)       # [E, nr]
    sbf = _sbf(angle, cfg.n_spherical,
               cfg.n_radial).astype(cfg.dtype)             # [T, ns*nr]
    m = _mlp(params["embed"],
             jnp.concatenate([x.astype(cfg.dtype)[src], rbf], -1))
    m = sh.constrain(m, flat, None)
    rbf_g = rbf @ params["rbf_w"]                          # [E, d]

    def layer(m, lw):
        mk = _mlp(lw["proj_kj"], m)[t_kj]                  # [T, d]
        w = sbf @ lw["sbf_w"]                              # [T, nb]
        tri = jnp.einsum("tb,bdf,td->tf", w, lw["bilinear"], mk)
        agg = jax.ops.segment_sum(tri, t_ji, num_segments=e_cnt)
        m2 = m + _mlp(lw["msg_mlp"], m * rbf_g + agg)
        return sh.constrain(m2, flat, None), None

    m, _ = jax.lax.scan(jax.checkpoint(layer), m, params["layers"])
    node_e = _segment_sum(m, dst, n, sh, flat)
    pred = _mlp(params["out"], node_e)                     # [N, n_out]
    # graph-level energy: sum nodes per graph
    gid = batch["graph_id"]
    n_graphs = batch["target_g"].shape[0]
    energy = jax.ops.segment_sum(pred[:, 0], gid, num_segments=n_graphs)
    err = (energy.astype(jnp.float32)
           - batch["target_g"].astype(jnp.float32)) ** 2
    return jnp.mean(err)


# ---------------------------------------------------------------------------
# graphsage: concat(self, mean-neighbour) -> linear
# ---------------------------------------------------------------------------
def init_graphsage(cfg: GNNConfig, key) -> Dict:
    d = cfg.d_hidden
    ks = jax.random.split(key, cfg.n_layers + 1)
    layers = []
    d_in = cfg.d_feat
    for i in range(cfg.n_layers):
        layers.append(_mlp_init(ks[i], (2 * d_in, d), cfg.dtype))
        d_in = d
    return {
        "layers": layers,   # ragged dims: keep as list
        "cls": _mlp_init(ks[-1], (d, cfg.n_classes), cfg.dtype),
    }


def forward_graphsage(cfg: GNNConfig, sh: Shardings, params: Dict,
                      batch: Dict) -> jax.Array:
    flat = cfg.flat_axes(sh)
    h = batch["node_feat"].astype(cfg.dtype)
    src, dst = batch["edge_src"], batch["edge_dst"]
    n = h.shape[0]
    for lw in params["layers"]:
        agg = _segment_mean(h[src], dst, n, sh, flat)
        h = jax.nn.relu(_mlp(lw, jnp.concatenate([h, agg], -1)))
        h = sh.constrain(h, flat, None)
        h = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True),
                            1e-6)
    logits = _mlp(params["cls"], h)
    return _masked_ce(logits, batch["labels"], batch["loss_mask"])


# ---------------------------------------------------------------------------
# gat: segment-softmax edge attention
# ---------------------------------------------------------------------------
def init_gat(cfg: GNNConfig, key) -> Dict:
    h_, d = cfg.n_heads, cfg.d_hidden
    ks = jax.random.split(key, cfg.n_layers + 1)
    layers = []
    d_in = cfg.d_feat
    for i in range(cfg.n_layers):
        k1, k2, k3 = jax.random.split(ks[i], 3)
        layers.append({
            "w": (jax.random.normal(k1, (d_in, h_, d), jnp.float32)
                  * d_in ** -0.5).astype(cfg.dtype),
            "a_src": (jax.random.normal(k2, (h_, d), jnp.float32)
                      * d ** -0.5).astype(cfg.dtype),
            "a_dst": (jax.random.normal(k3, (h_, d), jnp.float32)
                      * d ** -0.5).astype(cfg.dtype),
        })
        d_in = h_ * d
    return {"layers": layers,
            "cls": _mlp_init(ks[-1], (d_in, cfg.n_classes), cfg.dtype)}


def forward_gat(cfg: GNNConfig, sh: Shardings, params: Dict,
                batch: Dict) -> jax.Array:
    flat = cfg.flat_axes(sh)
    h = batch["node_feat"].astype(cfg.dtype)
    src, dst = batch["edge_src"], batch["edge_dst"]
    n = h.shape[0]
    for li, lw in enumerate(params["layers"]):
        z = jnp.einsum("nd,dhf->nhf", h, lw["w"])          # [N, H, F]
        logit_s = jnp.einsum("nhf,hf->nh", z, lw["a_src"])
        logit_d = jnp.einsum("nhf,hf->nh", z, lw["a_dst"])
        e_logit = jax.nn.leaky_relu(logit_s[src] + logit_d[dst],
                                    negative_slope=0.2)    # [E, H]
        # segment softmax over incoming edges of dst
        e_max = jax.ops.segment_max(e_logit, dst, num_segments=n)
        e_exp = jnp.exp(e_logit - e_max[dst])
        e_den = jax.ops.segment_sum(e_exp, dst, num_segments=n)
        alpha = e_exp / jnp.maximum(e_den[dst], 1e-9)      # [E, H]
        msg = z[src] * alpha[..., None]
        h2 = jax.ops.segment_sum(msg, dst, num_segments=n)  # [N, H, F]
        h = jax.nn.elu(h2.reshape(n, -1))
        h = sh.constrain(h, flat, None)
    logits = _mlp(params["cls"], h)
    return _masked_ce(logits, batch["labels"], batch["loss_mask"])


# ---------------------------------------------------------------------------
def _masked_ce(logits, labels, mask):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    ce = lse - gold
    m = mask.astype(jnp.float32)
    return jnp.sum(ce * m) / jnp.maximum(m.sum(), 1.0)


# ---------------------------------------------------------------------------
# shard_map message passing (production path for full-batch-large cells)
# ---------------------------------------------------------------------------
def forward_graphcast_sharded(cfg: GNNConfig, sh: Shardings, params: Dict,
                              batch: Dict) -> jax.Array:
    """Graphcast with owner-computes edge partitioning.

    Input contract (the BGP locality layout, DESIGN.md §5): each shard
    owns N/P nodes and their *incoming* edges; ``edge_dst`` is
    shard-local, ``edge_src`` is global.  Per layer the only collective
    is one tiled all_gather of the bf16 node state for the src halo;
    aggregation is a local segment_sum (no cross-shard scatter, whose
    bf16->f32-promoted transpose buffers dominated the 45 GB/device
    SPMD baseline; EXPERIMENTS.md §Perf G1).
    """
    axes = cfg.flat_axes(sh)
    mesh = sh.mesh
    from jax.sharding import PartitionSpec as P
    import functools as ft

    @ft.partial(shard_map, mesh=mesh,
                in_specs=(P(), {k: P(axes) if batch[k].ndim == 1
                                else P(axes, None) for k in batch}),
                out_specs=P())
    def run(params, b):
        x, src, dst = b["node_feat"], b["edge_src"], b["edge_dst"]
        n_local = x.shape[0]
        h = _mlp(params["enc_node"], x.astype(cfg.dtype))     # [N/P, d]
        e = _mlp(params["enc_edge"], b["edge_feat"].astype(cfg.dtype))

        e_local = batch["edge_src"].shape[0] // (
            mesh.size if mesh is not None else 1)
        n_chunks = 4 if e_local % 4 == 0 else 1

        def layer(carry, lw):
            h, e = carry
            h_full = jax.lax.all_gather(h, axes, axis=0, tiled=True)
            nl, d = h.shape
            # edge work in checkpointed chunks: only one chunk's message
            # tensors are live at a time (bounds the [E/P, 3d] buffers)
            src_c = src.reshape(n_chunks, -1)
            dst_c = dst.reshape(n_chunks, -1)
            e_c = e.reshape(n_chunks, -1, d)

            def chunk(agg, xs):
                s_, d_, e_ = xs
                msg = jnp.concatenate([e_, h_full[s_], h[d_]], -1)
                e2_ = e_ + _mlp(lw["edge_mlp"], msg)
                agg = agg + jax.ops.segment_sum(e2_, d_,
                                                num_segments=nl)
                return agg, e2_

            # (h * 0) keeps the carry varying over the manual mesh axes
            # (shard_map vma rule); a fresh zeros() would be unvarying
            agg, e2 = jax.lax.scan(jax.checkpoint(chunk),
                                   (h * 0).astype(e.dtype),
                                   (src_c, dst_c, e_c))
            e2 = e2.reshape(-1, d)
            h2 = h + _mlp(lw["node_mlp"],
                          jnp.concatenate([h, agg], axis=-1))
            return (h2, e2), None

        # block-wise activation checkpointing: the carry holds the big
        # [E/P, d] edge state, so per-layer stashing costs n_layers x
        # 1 GB on ogb_products — checkpoint every `blk` layers instead
        L = cfg.n_layers
        blk = 4 if L % 4 == 0 else 1
        stacked = jax.tree_util.tree_map(
            lambda w: w.reshape(L // blk, blk, *w.shape[1:]),
            params["layers"])

        def block(carry, lws):
            # inner layers are ALSO checkpointed: the block recompute
            # must not stash 4 layers of h_full/msg intermediates
            return jax.lax.scan(jax.checkpoint(layer), carry, lws)

        (h, e), _ = jax.lax.scan(jax.checkpoint(block), (h, e), stacked)
        pred = _mlp(params["dec"], h)
        mask = b["loss_mask"].astype(jnp.float32)
        err = (pred.astype(jnp.float32)
               - b["target"].astype(jnp.float32)) ** 2
        sse = jnp.sum(err.mean(-1) * mask)
        cnt = jnp.sum(mask)
        sse, cnt = jax.lax.psum((sse, cnt), axes)
        return sse / jnp.maximum(cnt, 1.0)

    return run(params, batch)


def forward_dimenet_sharded(cfg: GNNConfig, sh: Shardings, params: Dict,
                            batch: Dict) -> jax.Array:
    """DimeNet with partition-local triplets + owner-computes edges.

    Triplet indices reference edges *within the local shard* (angular
    neighbourhoods are partition-local under the BGP locality-aware
    edge ordering — DESIGN.md §Arch-applicability) and ``edge_dst`` is
    shard-local, so the directional message stack and the edge->node
    reduction are collective-free; only the src halo (one all_gather of
    the raw features) and the final energy psum cross shards.
    """
    axes = cfg.flat_axes(sh)
    mesh = sh.mesh
    from jax.sharding import PartitionSpec as P
    import functools as ft

    n_graphs = batch["target_g"].shape[0]

    @ft.partial(shard_map, mesh=mesh,
                in_specs=(P(), {k: (P(None) if k == "target_g"
                                    else P(axes) if batch[k].ndim == 1
                                    else P(axes, None)) for k in batch}),
                out_specs=P())
    def run(params, b):
        x, src, dst = b["node_feat"], b["edge_src"], b["edge_dst"]
        e_local = src.shape[0]
        rbf = _rbf(b["edge_dist"], cfg.n_radial).astype(cfg.dtype)
        sbf = _sbf(b["tri_angle"], cfg.n_spherical,
                   cfg.n_radial).astype(cfg.dtype)
        t_kj, t_ji = b["tri_edge_kj"], b["tri_edge_ji"]   # LOCAL ids
        x_full = jax.lax.all_gather(x.astype(cfg.dtype), axes, axis=0,
                                    tiled=True)
        m = _mlp(params["embed"],
                 jnp.concatenate([x_full[src], rbf], -1))  # [E/P, d]
        rbf_g = rbf @ params["rbf_w"]

        def layer(m, lw):
            mk = _mlp(lw["proj_kj"], m)[t_kj]             # local gather
            w = sbf @ lw["sbf_w"]
            tri = jnp.einsum("tb,bdf,td->tf", w, lw["bilinear"], mk)
            agg = jax.ops.segment_sum(tri, t_ji,
                                      num_segments=e_local)
            return m + _mlp(lw["msg_mlp"], m * rbf_g + agg), None

        m, _ = jax.lax.scan(jax.checkpoint(layer), m, params["layers"])
        node_e = jax.ops.segment_sum(m, dst,
                                     num_segments=x.shape[0])  # local dst
        pred = _mlp(params["out"], node_e)
        gid = b["graph_id"]
        energy = jax.lax.psum(
            jax.ops.segment_sum(pred[:, 0], gid, num_segments=n_graphs),
            axes)
        err = (energy.astype(jnp.float32)
               - b["target_g"].astype(jnp.float32)) ** 2
        return jnp.mean(err)

    return run(params, batch)


INIT = {"graphcast": init_graphcast, "dimenet": init_dimenet,
        "graphsage": init_graphsage, "gat": init_gat}
FORWARD = {"graphcast": forward_graphcast, "dimenet": forward_dimenet,
           "graphsage": forward_graphsage, "gat": forward_gat}
FORWARD_SHARDED = {"graphcast": forward_graphcast_sharded,
                   "dimenet": forward_dimenet_sharded}


def init_params(cfg: GNNConfig, key) -> Dict:
    return INIT[cfg.arch](cfg, key)


def forward_loss(cfg: GNNConfig, sh: Shardings, params: Dict,
                 batch: Dict) -> jax.Array:
    if (cfg.sharded and sh.mesh is not None
            and cfg.arch in FORWARD_SHARDED):
        return FORWARD_SHARDED[cfg.arch](cfg, sh, params, batch)
    return FORWARD[cfg.arch](cfg, sh, params, batch)
