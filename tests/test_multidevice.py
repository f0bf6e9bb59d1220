"""Multi-device semantics, run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest
process keeps the default single device, per the launch design)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(body: str) -> None:
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_host_mesh
    """ % os.path.join(ROOT, "src")) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, timeout=420)
    assert res.returncode == 0, res.stdout + res.stderr


def test_sharded_serve_matches_host_engine():
    _run("""
        from repro.core.graph import road_like
        from repro.core.supergraph import build_index
        from repro.core.device_engine import build_device_index
        from repro.core.dist_engine import serve_sharded
        from repro.core.engine import DislandEngine
        mesh = make_host_mesh((4, 2))
        g = road_like(900, seed=31)
        ix = build_index(g)
        dix = build_device_index(ix)
        eng = DislandEngine(ix)
        rng = np.random.default_rng(0)
        s = jnp.asarray(rng.integers(0, g.n, 32), jnp.int32)
        t = jnp.asarray(rng.integers(0, g.n, 32), jnp.int32)
        got = np.asarray(serve_sharded(mesh, dix, s, t))
        for i in range(32):
            want = eng.query(int(s[i]), int(t[i]))
            if np.isinf(want):
                assert np.isinf(got[i])
            else:
                assert abs(got[i] - want) < 1e-3
        print("ok")
    """)


def test_chip_smoke_four_chips_matches_planner():
    """chip_smoke.py --four-chips' phase: serve_sharded over every
    device equals the one-device planner, and Dijkstra, bit for bit."""
    _run("""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(%r, "chip_smoke.py"))
        chip_smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(chip_smoke)
        chip_smoke.four_chips(["--nodes", "2000", "--batch-size", "128",
                               "--validate", "16", "--json", ""])
        print("ok")
    """ % ROOT)


def test_compressed_psum_approximates_mean():
    _run("""
        import functools
        from repro.optim import compressed_psum
        mesh = make_host_mesh((8,), ("d",))
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(8, 64)).astype(np.float32))
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=P("d"), out_specs=P("d"))
        def f(v):
            return compressed_psum(v[0], "d")[None]
        got = np.asarray(f(x))
        want = np.asarray(x).mean(0)
        scale = np.abs(x).max() / 127
        assert np.abs(got - want[None]).max() <= scale + 1e-5
        print("ok")
    """)


def test_gnn_sharded_matches_dense():
    """Owner-computes graphcast path == dense path on a localized batch."""
    _run("""
        import dataclasses
        from repro.models import gnn
        from repro.models.common import Shardings
        P_ = 8
        mesh = make_host_mesh((4, 2))
        rng = np.random.default_rng(3)
        n, d = 64, 8          # 8 nodes per shard
        npp = n // P_
        # edges grouped by dst owner, dst LOCAL, src global
        src_g, dst_l, dst_g = [], [], []
        for shard in range(P_):
            for _ in range(12):
                dst = shard * npp + rng.integers(0, npp)
                src = rng.integers(0, n)
                src_g.append(src); dst_g.append(dst)
                dst_l.append(dst - shard * npp)
        cfg = dataclasses.replace(
            gnn.GNNConfig(name="gc", arch="graphcast", n_layers=2,
                          d_hidden=8, d_feat=d, n_out=2),
            sharded=True)
        params = gnn.init_params(cfg, jax.random.PRNGKey(0))
        base = dict(
            node_feat=jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
            edge_feat=jnp.asarray(rng.normal(size=(len(src_g), 4)).astype(np.float32)),
            target=jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32)),
            loss_mask=jnp.ones(n, jnp.float32))
        b_shard = dict(base, edge_src=jnp.asarray(src_g, jnp.int32),
                       edge_dst=jnp.asarray(dst_l, jnp.int32))
        b_dense = dict(base, edge_src=jnp.asarray(src_g, jnp.int32),
                       edge_dst=jnp.asarray(dst_g, jnp.int32))
        sh = Shardings(mesh=mesh)
        got = float(gnn.forward_loss(cfg, sh, params, b_shard))
        cfg_d = dataclasses.replace(cfg, sharded=False)
        want = float(gnn.forward_loss(cfg_d, Shardings(None), params,
                                      b_dense))
        assert abs(got - want) < 1e-4 * max(abs(want), 1), (got, want)
        print("ok", got, want)
    """)


def test_dimenet_sharded_matches_dense_local_triplets():
    _run("""
        import dataclasses
        from repro.models import gnn
        from repro.models.common import Shardings
        P_ = 8
        mesh = make_host_mesh((4, 2))
        rng = np.random.default_rng(5)
        n, d = 64, 6
        npp = n // P_
        e_per = 8
        src_g, dst_l, dst_g = [], [], []
        for shard in range(P_):
            for _ in range(e_per):
                dst = shard * npp + rng.integers(0, npp)
                src = rng.integers(0, n)
                src_g.append(src); dst_g.append(dst)
                dst_l.append(dst - shard * npp)
        E = len(src_g)
        # partition-local triplets: both edges within the same shard
        t_kj_l, t_ji_l, t_kj_g, t_ji_g, ang = [], [], [], [], []
        for shard in range(P_):
            for _ in range(2 * e_per):
                a_ = rng.integers(0, e_per)
                b_ = rng.integers(0, e_per)
                t_kj_l.append(a_); t_ji_l.append(b_)
                t_kj_g.append(shard * e_per + a_)
                t_ji_g.append(shard * e_per + b_)
                ang.append(rng.uniform(0, np.pi))
        cfg = dataclasses.replace(
            gnn.GNNConfig(name="dn", arch="dimenet", n_layers=2,
                          d_hidden=8, d_feat=d), sharded=True)
        params = gnn.init_params(cfg, jax.random.PRNGKey(1))
        base = dict(
            node_feat=jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
            edge_dist=jnp.asarray(rng.uniform(0.5, 3, E).astype(np.float32)),
            tri_angle=jnp.asarray(np.array(ang, np.float32)),
            graph_id=jnp.zeros(n, jnp.int32),
            target_g=jnp.asarray(rng.normal(size=(1,)).astype(np.float32)))
        b_shard = dict(base, edge_src=jnp.asarray(src_g, jnp.int32),
                       edge_dst=jnp.asarray(dst_l, jnp.int32),
                       tri_edge_kj=jnp.asarray(t_kj_l, jnp.int32),
                       tri_edge_ji=jnp.asarray(t_ji_l, jnp.int32))
        b_dense = dict(base, edge_src=jnp.asarray(src_g, jnp.int32),
                       edge_dst=jnp.asarray(dst_g, jnp.int32),
                       tri_edge_kj=jnp.asarray(t_kj_g, jnp.int32),
                       tri_edge_ji=jnp.asarray(t_ji_g, jnp.int32))
        sh = Shardings(mesh=mesh)
        got = float(gnn.forward_loss(cfg, sh, params, b_shard))
        cfg_d = dataclasses.replace(cfg, sharded=False)
        want = float(gnn.forward_loss(cfg_d, Shardings(None), params,
                                      b_dense))
        assert abs(got - want) < 1e-4 * max(abs(want), 1), (got, want)
        print("ok", got, want)
    """)


def test_lm_sharded_loss_matches_single_device():
    """Full train-cell sharding (FSDP+TP+SP) must not change the loss."""
    _run("""
        import dataclasses
        from repro.models import transformer
        from repro.models.common import Shardings
        mesh = make_host_mesh((2, 4))
        cfg = transformer.LMConfig(
            name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
            d_ff=64, vocab=64, dtype=jnp.float32, attn_chunk=8,
            gather_fsdp_in_body=True, seq_shard_activations=True)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
        base = float(transformer.forward_loss(cfg, Shardings(None),
                                              params, toks))
        sh = Shardings(mesh=mesh)
        with mesh:
            sharded = float(jax.jit(
                lambda p, t: transformer.forward_loss(cfg, sh, p, t)
            )(params, toks))
        assert abs(base - sharded) < 1e-4 * max(abs(base), 1)
        print("ok", base, sharded)
    """)
