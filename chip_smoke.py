"""Smoke run of the exact road-distance server on a TPU.

Drives the serving path once, in this one process, through the entry
points a user calls, on the road64k preset (about 62k nodes, a
3-level overlay hierarchy, hub labels for the Zipf head, a 2% live
update): the ``EpochedEngine`` build, ``QueryPlanner`` offline batches,
one ``query_path`` batch, one ``apply_updates`` refresh round and a
short ``ServingRuntime`` live window with the label tier on.  Every
phase checks its answers against float64 Dijkstra on the host, on the
graph of the epoch that served them, and raises on any mismatch.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # serve_sharded on a 4-chip mesh,
                                        # compared with the one-chip planner

Progress lines come first; the last line is one JSON object naming the
device.  Without a TPU it exits non-zero and prints no result.  It
writes no tracked file (the perf-record helper gets ``--json ""``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: the serve CLI flags of the road64k deployment the smoke drives; the
#: live window may take its time to drain (it checks answers, not
#: latency)
ROAD64K = ["--graph", "road64k", "--expect-hierarchy", "3",
           "--hub-budget", "2048", "--batch-size", "1024",
           "--validate", "64", "--update-frac", "0.02",
           "--mix", "zipf", "--rate", "500", "--live-seconds", "4",
           "--live-wait-timeout", "300", "--json", ""]
#: offline batches after the planner warmup
BATCHES = 3
#: paths served, unwound and checked: unwinding is host work that
#: takes seconds per road64k path (measured on a v5e host)
PATHS = 16


def _log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _exact_mismatches(g, s, t, got, n_check: int) -> int:
    """Served distances vs float64 Dijkstra, compared with ``==``
    (integer weights make every f32 (min,+) sum exact)."""
    from repro.core import dijkstra

    return sum(float(got[i]) != dijkstra.pair(g, int(s[i]), int(t[i]))
               for i in range(min(n_check, len(s))))


def _args(serve_argv, **overrides):
    from repro.launch import serve

    args = serve.build_parser().parse_args(serve_argv)
    serve.resolve_graph(args)
    for k, v in overrides.items():
        setattr(args, k, v)
    return args


def _build(args):
    from repro.launch import serve

    t0 = time.perf_counter()
    engine, _ = serve._build_engine(args)
    ov = serve._overlay_record(engine)
    _log(f"set-up: build {time.perf_counter() - t0:.3f}s, "
         f"hierarchy_levels={ov['hierarchy_levels']}, "
         f"overlay_bytes={ov['overlay_bytes']}, "
         f"overlay_dense_bytes={ov['overlay_dense_bytes']}")
    return engine


def _check_native_kernels(engine, batch: int) -> None:
    """No hidden fallback: kernels dispatch to Pallas (never the jnp
    reference), and a compiled serve program holds a Mosaic kernel."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core.device_engine import serve_cross
    from repro.kernels import ops

    if not ops.use_pallas():
        raise RuntimeError("kernel dispatch chose the jnp reference")
    z = jnp.zeros(batch, jnp.int32)
    hlo = jax.jit(functools.partial(serve_cross, with_local=False)).lower(
        engine.dix, z, z).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise RuntimeError("compiled cross-query program has no "
                           "tpu_custom_call (no Pallas kernel)")
    _log("kernels: Pallas dispatch, tpu_custom_call in the compiled "
         "cross-query program")


def _offline(engine, args, rng) -> None:
    n, b = engine.g.n, args.batch_size
    t0 = time.perf_counter()
    engine.warmup(b)
    _log(f"offline: planner warmup {time.perf_counter() - t0:.3f}s")
    for i in range(BATCHES):
        s = rng.integers(0, n, b).astype("int32")
        t = rng.integers(0, n, b).astype("int32")
        t0 = time.perf_counter()
        got = engine.query(s, t)     # host arrays: waits for the device
        dt = time.perf_counter() - t0
        _log(f"offline: batch {i} of {b} in {dt * 1e3:.3f}ms, buckets "
             f"{engine.planner.last_counts}")
    bad = _exact_mismatches(engine.g, s, t, got, args.validate)
    _log(f"offline: {bad} mismatches of {args.validate} vs Dijkstra (==)")
    if bad:
        raise RuntimeError(f"offline: {bad} oracle mismatches")


def _paths(engine, rng) -> None:
    from repro.launch import serve

    n = engine.g.n
    s = rng.integers(0, n, PATHS).astype("int32")
    t = rng.integers(0, n, PATHS).astype("int32")
    t0 = time.perf_counter()
    dist, paths = engine.query_path(s, t)
    _log(f"paths: batch of {PATHS} served and unwound in "
         f"{time.perf_counter() - t0:.3f}s")
    bad = serve._validate_paths(engine.g, s, t, dist, paths, PATHS)
    if bad:
        raise RuntimeError(f"paths: {bad} oracle mismatches")


def _refresh(engine, args, rng) -> None:
    from repro.core.graph import traffic_updates

    u, v, w = traffic_updates(engine.g, args.update_frac,
                              seed=args.seed + 10)
    t0 = time.perf_counter()
    stats = engine.apply_updates(u, v, w)
    rec = stats.as_record()
    _log(f"refresh: {len(u)} updates -> epoch {engine.epoch} in "
         f"{time.perf_counter() - t0:.3f}s (dirty frags "
         f"{rec['dirty_frags']}, pieces {rec['dirty_pieces']})")
    n, b = engine.g.n, args.batch_size
    s = rng.integers(0, n, b).astype("int32")
    t = rng.integers(0, n, b).astype("int32")
    got = engine.query(s, t)
    bad = _exact_mismatches(engine.g, s, t, got, args.validate)
    _log(f"refresh: epoch {engine.epoch}: {bad} mismatches of "
         f"{args.validate} vs Dijkstra on the updated graph (==)")
    if bad:
        raise RuntimeError(f"refresh: {bad} oracle mismatches")


def _live(engine, args) -> None:
    from repro.launch import serve

    args.live = True
    t0 = time.perf_counter()
    rec = serve._live_loop(engine, args)[0]
    _log(f"live: {rec['n_requests']} requests in "
         f"{time.perf_counter() - t0:.3f}s; tiers "
         f"{rec['cache_hits']} cache / {rec['label_hits']} label / "
         f"{rec['planner_dispatches']} planner; "
         f"{rec['oracle_bad']} mismatches of {rec['oracle_checked']}")
    if rec["oracle_bad"] or not rec["oracle_checked"]:
        raise RuntimeError("live: responses failed validation")
    if not rec["label_hits"]:
        raise RuntimeError("live: the label tier served nothing")


def one_chip(serve_argv) -> None:
    """The one-chip phases, in order, on one engine."""
    import numpy as np

    args = _args(serve_argv, update_batches=1)
    engine = _build(args)
    _check_native_kernels(engine, args.batch_size)
    rng = np.random.default_rng(args.seed + 1)
    for name, phase in (("offline", lambda: _offline(engine, args, rng)),
                        ("paths", lambda: _paths(engine, rng)),
                        ("refresh", lambda: _refresh(engine, args, rng)),
                        ("live", lambda: _live(engine, args))):
        t0 = time.perf_counter()
        phase()
        _log(f"phase {name}: {time.perf_counter() - t0:.3f}s")


def four_chips(serve_argv) -> None:
    """``serve_sharded`` over a mesh of every device, against the
    one-chip planner's answers on the same batch (``==``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dist_engine import serve_sharded
    from repro.launch.mesh import make_host_mesh

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(jax.devices())}")
    args = _args(serve_argv, hub_budget=0)
    engine = _build(args)
    rng = np.random.default_rng(args.seed + 1)
    n, b = engine.g.n, args.batch_size
    s = rng.integers(0, n, b).astype("int32")
    t = rng.integers(0, n, b).astype("int32")
    t0 = time.perf_counter()
    want = engine.query(s, t)
    _log(f"four chips: one-chip planner batch of {b} in "
         f"{time.perf_counter() - t0:.3f}s")
    mesh = make_host_mesh()
    t0 = time.perf_counter()
    got = np.asarray(jax.block_until_ready(serve_sharded(
        mesh, engine.dix, jnp.asarray(s), jnp.asarray(t))))
    _log(f"four chips: serve_sharded over {dict(mesh.shape)} in "
         f"{time.perf_counter() - t0:.3f}s (compile included)")
    diff = int(np.sum(got != want))
    _log(f"four chips: {diff} of {b} differ from the one-chip planner")
    if diff:
        raise RuntimeError(f"four chips: {diff} answers differ")
    bad = _exact_mismatches(engine.g, s, t, got, args.validate)
    _log(f"four chips: {bad} mismatches of {args.validate} vs "
         f"Dijkstra (==)")
    if bad:
        raise RuntimeError(f"four chips: {bad} oracle mismatches")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only serve_sharded on a 4-chip mesh and "
                         "its one-chip comparison")
    opts = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found "
                         f"platform {dev.platform!r}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import serve

    serve.use_compile_cache()
    count = len(jax.devices())
    _log(f"device: {dev.platform} {dev.device_kind} x{count}")
    t0 = time.perf_counter()
    (four_chips if opts.four_chips else one_chip)(ROAD64K)
    _log(f"total {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()
