"""Multi-device semantics, run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest
process keeps the default single device)."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(body: str) -> None:
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp, numpy as np
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
