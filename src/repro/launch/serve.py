"""DISLAND serving driver (the paper's end-to-end application).

Builds the full index over a synthetic road graph, uploads the device
tensors, then serves batched shortest-distance queries — by default
through the case-bucketing QueryPlanner (each jitted sub-program does
only its bucket's work), or monolithically (--mode fused) or sharded
over a device mesh (--mode sharded) — and validates a sample against
host Dijkstra.  Each run appends a perf record to BENCH_serve.json so
the µs/query trajectory is tracked across PRs.

``--update-batches`` turns on the live-traffic loop (planner mode):
between serving batches, a localized weight-update batch is absorbed by
the incremental refresh path and published as a new index epoch
(DESIGN.md §9); refresh latency, the from-scratch rebuild baseline, and
an exact-match check against that rebuild are all recorded.

``--live`` replaces the offline batch loop with the online serving
runtime (DESIGN.md §11): an open-loop Poisson arrival stream with a
Zipf/geo/uniform query mix flows through the deadline-aware
micro-batcher and the epoch-tagged result cache, optionally while a
background thread absorbs ``--live-update-batches`` traffic rounds
concurrently; p50/p95/p99 latency, achieved qps, cache hit rate, and
the batch-occupancy histogram are recorded, and a response sample is
validated against the host oracle *of the epoch that served it*.

    PYTHONPATH=src python -m repro.launch.serve --nodes 4000 \
        --batches 5 --batch-size 1024 --validate 64 \
        --update-batches 3 --update-frac 0.02
    PYTHONPATH=src python -m repro.launch.serve --nodes 4000 --live \
        --rate 2000 --live-seconds 5 --mix zipf \
        --live-update-batches 3 --validate 64
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dijkstra
from ..core.device_engine import (build_device_index, index_fields_equal,
                                  serve_step)
from ..core.dist_engine import EpochedEngine, serve_sharded
from ..core.graph import road_like, traffic_updates
from ..core.paths import path_weight
from ..core.supergraph import (build_index, index_arrays_equal,
                               reweight_index)
from ..obs import trace
from ..perflog import append_records, latest
from .mesh import make_host_mesh

REFRESHED_FIELDS = ("frag_apsp", "frag_next", "brow", "d_super",
                    "super_next", "piece_flat", "piece_next",
                    "dist_to_agent",
                    # hierarchical overlay tables — per-level tuples,
                    # empty (or 1-sized dummies) at hierarchy_levels=1,
                    # so the parity check is free on dense epochs
                    "sf_closure", "sf_next", "l2row", "d2", "d2_next",
                    # resident pre-lifted rows (dummies when cold)
                    "res_rows", "res_of_frag",
                    # hub-label hot-tier tables (dummies when no hub
                    # set is pinned) — refresh must reproduce the
                    # scratch rebuild bit-for-bit (DESIGN.md §15)
                    "hub_rows", "hub_of_agent")


# ---------------------------------------------------------------------------
# shared helpers (engine setup / validation / record emission)
# ---------------------------------------------------------------------------
def _label(args) -> str:
    """Graph label for perf records; tolerant of hand-built arg
    namespaces (tests drive the loops without the CLI preamble)."""
    return getattr(args, "graph_label", None) or f"road{args.nodes}"


def _overlay_record(engine: EpochedEngine) -> dict:
    """Overlay-closure shape + memory fields for perf records: the
    measurement behind the exp10 sub-quadratic claim (DESIGN.md §12)."""
    plan = engine.plan
    if plan.hierarchy_levels >= 2:
        from ..core.hierarchy import hier_overlay_stats

        rec = hier_overlay_stats(plan.hier, plan.S)
        rec["resident_groups"] = max(
            0, int(engine.dix.res_rows.shape[0]) - 1)
        return rec
    dense = 2 * (plan.S + 1) * (plan.S + 1) * 4
    return {"hierarchy_levels": 1, "S": plan.S,
            "overlay_bytes": dense, "overlay_dense_bytes": dense}


def _hub_selection(g, args) -> np.ndarray | None:
    """Traffic-head hub set for the label hot tier (DESIGN.md §15):
    the endpoints of the top-ranked rows of the Zipf pool the live
    workload draws from (same seed => bit-identical pool), first seen
    in rank order, capped at ``--hub-budget`` nodes.  Returns None when
    the budget is 0 (tier off)."""
    budget = int(getattr(args, "hub_budget", 0) or 0)
    if not budget:
        return None
    from ..data.queries import zipf_pool

    pairs = zipf_pool(g, seed=args.seed + 4)
    flat = pairs.ravel()        # rank-interleaved (s1, t1, s2, t2, ...)
    _, first = np.unique(flat, return_index=True)
    return flat[np.sort(first)][:budget]


def _host_build_record(args, timings: dict) -> list:
    """``section: "host_build"`` perf record from the host index stage
    timings (DESIGN.md §17) — the measurement behind the staged-
    pipeline speedup claim and the bench-gate ``host_build`` section."""
    stages = {k: round(float(v), 4) for k, v in timings.items()
              if isinstance(v, (int, float)) and not isinstance(v, bool)}
    return [{
        "section": "host_build",
        "graph": _label(args),
        "backend": jax.default_backend(),
        "build_workers": int(getattr(args, "build_workers", 1) or 1),
        "wall_s": round(sum(stages.values()), 4),
        **{f"stage_{k}_s": v for k, v in stages.items()},
    }]


def _build_engine(args) -> tuple[EpochedEngine, float]:
    """Graph + host index + EpochedEngine with timing prints — the one
    setup path shared by the planner serving loops (offline batches,
    --paths, --update-batches, --live).  All stage wall-times flow
    through the span API (DESIGN.md §16): the console prints, the
    returned ``build_s``, and the build trace all read one
    measurement.

    The host index is built *inside* ``EpochedEngine`` via the staged
    streaming handoff (DESIGN.md §17): with ``--build-workers N`` the
    per-fragment covers run process-parallel and overlap the device
    build, so the ``device_engine`` span covers the whole index
    pipeline end to end."""
    workers = int(getattr(args, "build_workers", 1) or 1)
    bt: dict = {}
    with trace.timed("build.graph", bt, "graph", nodes=args.nodes):
        g = road_like(args.nodes, seed=args.seed)
    print(f"graph: n={g.n} m={g.m} ({bt['graph']:.1f}s)")
    # refresh-path warmup compiles the delta-FW programs — minutes of
    # wasted work at road64k scale when the run applies no updates
    warm = bool(args.update_batches
                or (args.live and args.live_update_batches))
    hub_nodes = _hub_selection(g, args)
    with trace.timed("build.device_engine", bt, "device_engine",
                     warm_refresh=warm, build_workers=workers):
        engine = EpochedEngine(g, paths=args.paths,
                               hierarchy_levels=args.hierarchy_levels,
                               resident_mb=args.resident_mb,
                               warm_refresh=warm, hub_nodes=hub_nodes,
                               build_workers=workers)
    build_s = bt["device_engine"]
    print(f"index: {engine.ix.timings} (workers={workers})")
    if getattr(args, "check_build_parity", False):
        with trace.timed("build.parity_check", bt, "parity"):
            eq = index_arrays_equal(engine.ix, build_index(g))
        bad = [k for k, v in eq.items() if not v]
        if bad:
            raise SystemExit(
                f"build parity FAILED: --build-workers {workers} "
                f"diverges from the serial build on {bad}")
        print(f"build parity: workers={workers} == serial on all "
              f"index tables ({bt['parity']:.1f}s)")
    _emit(args, _host_build_record(args, engine.ix.timings),
          "host_build",
          prev_filter={"section": "host_build", "graph": _label(args),
                       "build_workers": workers},
          prev_key="wall_s")
    dix = engine.dix
    ov = _overlay_record(engine)
    print(f"device index: frag_apsp={dix.frag_apsp.shape} "
          f"d_super={dix.d_super.shape} ({build_s:.1f}s)")
    if hub_nodes is not None:
        h, w = np.asarray(dix.hub_rows).shape
        print(f"hub labels: {h - 1} agents x {w} hubs from "
              f"{len(hub_nodes)}-node budget")
    if ov["hierarchy_levels"] >= 2:
        print(f"overlay hierarchy: {ov['hierarchy_levels']} levels, "
              f"S2 ladder {ov['levels_S2']} from S={ov['S']} "
              f"(nsf={ov['nsf']} m2={ov['m2']}); "
              f"{ov['resident_groups']} resident groups; "
              f"{ov['overlay_bytes'] / 1e6:.1f}MB vs dense "
              f"{ov['overlay_dense_bytes'] / 1e6:.1f}MB")
    if args.expect_hierarchy and \
            ov["hierarchy_levels"] != args.expect_hierarchy:
        raise SystemExit(
            f"expected hierarchy_levels={args.expect_hierarchy}, "
            f"built {ov['hierarchy_levels']} (S={ov['S']})")
    if args.max_s2_ratio and ov["hierarchy_levels"] >= 2:
        ratio = ov["S2"] / max(1, ov["S"])
        if ratio > args.max_s2_ratio:
            raise SystemExit(
                f"level-2 boundary too large: S2={ov['S2']} / "
                f"S={ov['S']} = {ratio:.3f} > --max-s2-ratio "
                f"{args.max_s2_ratio}")
        print(f"S2/S ratio {ratio:.3f} <= {args.max_s2_ratio} (ok)")
    return engine, build_s


def _validate_sample(g, s, t, got, n_check: int, *,
                     label: str = "validation") -> int:
    """Distance sample vs host Dijkstra on ``g``; returns (and prints)
    the mismatch count.  Callers assert it is zero."""
    bad = 0
    n_check = min(n_check, len(s))
    for i in range(n_check):
        want = dijkstra.pair(g, int(s[i]), int(t[i]))
        bad += dijkstra.mismatches_oracle(want, float(got[i]))
    print(f"{label}: {bad} mismatches of {n_check}")
    return bad


def _validate_paths(g, s, t, dist, paths, n_check: int) -> int:
    """Path sample vs host Dijkstra on ``g``: every path must be edge-
    valid with weight == served distance == Dijkstra, exactly (None
    where unreachable).  Returns (and prints) the mismatch count."""
    bad = 0
    n_check = min(n_check, len(s))
    for i in range(n_check):
        want = dijkstra.pair(g, int(s[i]), int(t[i]))
        if np.isinf(want):
            bad += paths[i] is not None
            continue
        w = path_weight(g, paths[i])          # raises on a broken hop
        if not (w == float(dist[i]) == want):
            bad += 1
    print(f"path validation: {bad} mismatches of {n_check} "
          "(edge-valid, weight == serve == Dijkstra, exact)")
    return bad


def _emit(args, records: list, label: str, *, prev_filter=None,
          prev_key: str | None = None) -> None:
    """Append perf records to --json (when enabled), printing the most
    recent committed record for the same config first so the cross-PR
    delta is visible in the run log."""
    if not args.json or not records:
        return
    if prev_filter:
        prev = latest(args.json, **prev_filter)
        if prev and prev_key:
            print(f"previous {label} record: {prev[prev_key]}")
    append_records(args.json, records)
    print(f"{len(records)} {label} record(s) appended to {args.json}")


# ---------------------------------------------------------------------------
# serving loops
# ---------------------------------------------------------------------------
def _update_loop(engine: EpochedEngine, args, build_s: float) -> list:
    """Absorb --update-batches rounds of localized traffic, serving and
    validating on each new epoch; returns perf records."""
    records = []
    rng = np.random.default_rng(args.seed + 2)
    for r in range(args.update_batches):
        u, v, w = traffic_updates(engine.g, args.update_frac,
                                  seed=args.seed + 10 + r)
        # one measurement per stage (span API): record fields, prints,
        # and the trace all read the same numbers
        tm: dict = {}
        with trace.timed("refresh.apply_updates", tm, "refresh",
                         round=r, n_updates=len(u)):
            stats = engine.apply_updates(u, v, w)
        refresh_s = tm["refresh"]
        s = rng.integers(0, engine.g.n, args.batch_size)
        t = rng.integers(0, engine.g.n, args.batch_size)
        with trace.timed("serve.epoch_batch", tm, "serve",
                         epoch=engine.epoch):
            out = engine.query(s, t)
        serve_s = tm["serve"]
        bad = _validate_sample(engine.g, s, t, out, args.validate,
                               label=f"epoch {engine.epoch} validation")
        # Two from-scratch baselines on the updated graph, re-measured
        # each round so refresh and baseline share contention
        # conditions:
        #  * full pipeline (build_index + device build) — what a weight
        #    change costs WITHOUT the delta path, since the hybrid
        #    covers are weight-dependent (DESIGN.md §9);
        #  * reweight + device rebuild (same structure) — itself only
        #    possible because overlay weights are derived; also the
        #    array-parity exactness reference (checked on round 0).
        with trace.timed("refresh.scratch_pipeline", tm, "pipeline"):
            build_device_index(
                build_index(engine.g),
                hierarchy_levels=engine.plan.hierarchy_levels)
        pipeline_s = tm["pipeline"]
        # same hub set as the live plan: the parity check covers the
        # hub tables too (REFRESHED_FIELDS), so the scratch oracle
        # must label the identical node set
        with trace.timed("refresh.scratch_reweight", tm, "reweight"):
            sdix = build_device_index(
                reweight_index(engine.ix, engine.g),
                hierarchy_levels=engine.plan.hierarchy_levels,
                hub_nodes=engine.plan.hub_nodes)
        reweight_s = tm["reweight"]
        scratch_match = all(index_fields_equal(
            engine.dix, sdix, REFRESHED_FIELDS).values())
        rec = {
            "section": "refresh",
            "graph": _label(args),
            "backend": jax.default_backend(),
            "epoch": engine.epoch,
            "update_frac": args.update_frac,
            "refresh_s": round(refresh_s, 4),
            "scratch_pipeline_s": round(pipeline_s, 4),
            "scratch_reweight_s": round(reweight_s, 4),
            "refresh_over_scratch": round(refresh_s / pipeline_s, 4),
            "refresh_over_reweight": round(refresh_s / reweight_s, 4),
            "initial_build_s": round(build_s, 4),
            "post_refresh_mismatches": bad,
            "scratch_match": scratch_match,
            "serve_batch_ms": round(serve_s * 1e3, 3),
            **stats.as_record(),
        }
        records.append(rec)
        print(f"epoch {engine.epoch}: refresh {refresh_s*1e3:.0f}ms "
              f"({stats.as_record()['dirty_frags']} frags, "
              f"{stats.as_record()['dirty_pieces']} pieces, "
              f"decrease_only={stats.decrease_only}) -> "
              f"{refresh_s / pipeline_s:.1%} of full pipeline "
              f"({pipeline_s:.2f}s), "
              f"{refresh_s / reweight_s:.1%} of reweight rebuild "
              f"({reweight_s:.2f}s), match={scratch_match}")
        assert bad == 0
    return records


def _paths_loop(engine: EpochedEngine, args) -> list:
    """Serve the path-unwinding workload (planner witness programs +
    host-side unwind) and validate a sample; returns perf records."""
    rng = np.random.default_rng(args.seed + 3)
    batch_s = []
    total = 0
    last = None
    for _ in range(args.batches):
        s = rng.integers(0, engine.g.n, args.batch_size).astype(np.int32)
        t = rng.integers(0, engine.g.n, args.batch_size).astype(np.int32)
        t0 = time.perf_counter()
        dist, paths = engine.query_path(s, t)
        batch_s.append(time.perf_counter() - t0)
        total += args.batch_size
        last = (s, t, dist, paths)
    median_s = float(np.median(batch_s))
    per_p = median_s / args.batch_size
    pps = args.batch_size / median_s
    hops = [len(p) - 1 for p in last[3] if p is not None]
    print(f"paths: {total} unwound; median batch "
          f"{median_s * 1e3:.2f}ms -> {per_p * 1e6:.2f}us/path "
          f"({pps:,.0f} paths/s, mean {np.mean(hops):.1f} hops)")
    s, t, dist, paths = last
    assert _validate_paths(engine.g, s, t, dist, paths,
                           args.validate) == 0
    return [{
        "section": "serve_paths",
        "graph": _label(args),
        "backend": jax.default_backend(),
        "batch_size": args.batch_size,
        "median_batch_ms": round(median_s * 1e3, 3),
        "us_per_path": round(per_p * 1e6, 3),
        "paths_per_s": round(pps, 1),
        "mean_hops": round(float(np.mean(hops)), 1) if hops else 0.0,
    }]


def _start_obs(args, runtime) -> dict:
    """Wire the live runtime's registry to the exporters the CLI asked
    for (--metrics-out periodic snapshots + Prometheus text sidecar,
    --metrics-port HTTP endpoint).  Returns the handles to stop."""
    handles: dict = {}
    if getattr(args, "metrics_out", ""):
        from ..obs import MetricsExporter

        handles["exporter"] = MetricsExporter(
            runtime.registry, args.metrics_out,
            interval_s=getattr(args, "metrics_every", 2.0),
            extra=lambda: {
                "slow_queries": runtime.slow_log.records()}).start()
    port = getattr(args, "metrics_port", 0)
    if port:
        from ..obs import MetricsServer

        srv = MetricsServer(runtime.registry, port).start()
        handles["server"] = srv
        print(f"metrics: http://127.0.0.1:{srv.port}/metrics")
    return handles


def _stop_obs(args, handles: dict) -> None:
    exporter = handles.get("exporter")
    if exporter is not None:
        exporter.stop()
        print(f"metrics: {exporter.writes} snapshot(s) -> "
              f"{args.metrics_out} (+ .prom exposition)")
    server = handles.get("server")
    if server is not None:
        server.stop()


def _write_trace(args) -> None:
    """Drain the default tracer into a Chrome-trace file
    (--trace-out; load it in chrome://tracing or Perfetto)."""
    if not getattr(args, "trace_out", ""):
        return
    from ..obs.export import write_chrome_trace

    tr = trace.get_tracer()
    events = tr.events()
    write_chrome_trace(args.trace_out, events)
    dropped = f" ({tr.dropped} dropped)" if tr.dropped else ""
    print(f"trace: {len(events)} event(s) -> {args.trace_out}"
          f"{dropped}")


def _live_loop(engine: EpochedEngine, args) -> list:
    """Online serving runtime under open-loop load (DESIGN.md §11),
    optionally with concurrent background refresh (pipelined through
    the prioritized staged path by default, DESIGN.md §14); returns a
    ``section: "serve_live"`` perf record, plus a ``serve_refresh``
    record when refresh rounds ran."""
    from ..serving import (ServingRuntime, run_load_with_refresh,
                           validate_against_epochs, workload_pairs)

    runtime = ServingRuntime(engine, max_batch=args.live_batch,
                             deadline_s=args.deadline_ms * 1e-3,
                             cache_size=args.cache_size)
    tm: dict = {}
    with trace.timed("serve.warmup", tm, "warmup"):
        runtime.warmup()
    print(f"live: warmed {runtime.max_batch}-cap buckets in "
          f"{tm['warmup']:.1f}s; deadline "
          f"{args.deadline_ms}ms, cache "
          f"{args.cache_size or 'off'}, mix {args.mix}")
    n = max(1, int(round(args.rate * args.live_seconds)))
    pairs = workload_pairs(engine.g, args.mix, n, seed=args.seed + 4,
                           zipf_a=args.zipf_a)
    obs_handles = _start_obs(args, runtime)
    try:
        report, graphs, driver = run_load_with_refresh(
            runtime, pairs, rate_qps=args.rate, seed=args.seed + 5,
            refresh_rounds=args.live_update_batches,
            refresh_frac=args.update_frac,
            refresh_interval_s=args.live_update_every,
            refresh_seed=args.seed,
            refresh_pipelined=args.live_pipelined,
            wait_timeout_s=args.live_wait_timeout,
            join_timeout_s=args.live_join_timeout)
        runtime.close()
    finally:
        _stop_obs(args, obs_handles)
    epochs = sorted({r.epoch for r in report.requests})
    stats = runtime.stats()
    # per-tier resolution split (DESIGN.md §15): every response came
    # from exactly one of cache / label merge / planner dispatch
    label_rate = stats["label_hits"] / max(
        1, stats["label_hits"] + stats["planner_dispatches"])
    print(f"live: {report.n_requests} requests at "
          f"{report.offered_qps:.0f} qps offered / "
          f"{report.achieved_qps:.0f} achieved; latency p50 "
          f"{report.p50_ms}ms p95 {report.p95_ms}ms p99 "
          f"{report.p99_ms}ms "
          f"({report.latency_source}, n={report.latency_n}); "
          f"tiers: {stats['cache_hits']} cache / "
          f"{stats['label_hits']} label / "
          f"{stats['planner_dispatches']} planner "
          f"({stats.get('cache_hit_rate', 0.0):.1%} cache hit rate, "
          f"{stats.get('cache_stale', 0)} stale rejected; label tier "
          f"took {label_rate:.1%} of misses at "
          f"{stats['label_us_per_query']:.0f}us/q vs planner "
          f"{stats['planner_us_per_query']:.0f}us/q); "
          f"{stats['flushes']} flushes, mean occupancy "
          f"{stats['mean_occupancy']:.1%} "
          f"(full={stats['flush_full']} "
          f"deadline={stats['flush_deadline']}); epochs served "
          f"{epochs}")
    slow = runtime.slow_log.records()
    if slow:
        w0 = slow[0]
        print(f"slow queries: worst {w0['latency_ms']:.0f}ms "
              f"(tier {w0['tier']}, epoch {w0['epoch']}, waited "
              f"{w0['batch_wait_ms']:.0f}ms in a "
              f"{w0['batch_size']}-request batch); {len(slow)} logged "
              f"of {runtime.slow_log.offered}")
    if args.live_update_batches:
        print(f"live staleness: max serving gap "
              f"{report.max_serving_gap_ms:.0f}ms, "
              f"{report.stale_responses} responses from mid-pipeline "
              f"epochs, max lag {report.max_staleness_batches} "
              "batch(es)")
    evicted = driver.evicted_epochs if driver is not None else ()
    checked, bad = validate_against_epochs(
        report.requests, graphs, sample=args.validate, seed=args.seed,
        evicted=evicted)
    print(f"live validation: {bad} mismatches of {checked} vs the "
          "host oracle of each response's serving epoch")
    assert bad == 0
    if args.max_serving_gap and \
            report.max_serving_gap_ms > args.max_serving_gap * 1e3:
        raise SystemExit(
            f"serving stalled: max gap {report.max_serving_gap_ms:.0f}"
            f"ms > --max-serving-gap {args.max_serving_gap}s — the "
            "foreground paused longer than the allowed bound")
    hot_tier = getattr(args, "hot_tier", 0.0) or 0.0
    if hot_tier and label_rate < hot_tier:
        raise SystemExit(
            f"hot tier underused: label tier served {label_rate:.1%} "
            f"of cache misses < --hot-tier {hot_tier:.1%} — the hub "
            "selection no longer covers the workload head")
    rec = {
        "section": "serve_live",
        "graph": _label(args),
        "backend": jax.default_backend(),
        "mix": args.mix,
        "rate_qps": args.rate,
        "deadline_ms": args.deadline_ms,
        "max_batch": runtime.max_batch,
        "cache": "on" if args.cache_size else "off",
        "refresh": "on" if args.live_update_batches else "off",
        "hub_budget": int(getattr(args, "hub_budget", 0) or 0),
        "label_hit_rate": round(label_rate, 4),
        "epochs_served": len(epochs),
        "oracle_checked": checked,
        "oracle_bad": bad,
        **report.as_record(),
    }
    records = [rec]
    if driver is not None:
        rec.update(driver.as_record())
        records.append({
            "section": "serve_refresh",
            "graph": _label(args),
            "backend": jax.default_backend(),
            "mix": args.mix,
            "rate_qps": args.rate,
            "update_frac": args.update_frac,
            "pipelined": args.live_pipelined,
            "max_serving_gap_ms": report.max_serving_gap_ms,
            "stale_responses": report.stale_responses,
            "max_staleness_batches": report.max_staleness_batches,
            "epochs_served": len(epochs),
            **driver.as_record(),
        })
    return records


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
#: the checkout root (src/repro/launch/serve.py -> three levels up)
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of the cache key,
    so it must not move between runs."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI's flags; cross-flag checks live in ``main``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=4000)
    ap.add_argument("--graph", default=None,
                    help="named road preset (data/roads.py, e.g. "
                         "road64k); overrides --nodes and labels the "
                         "perf records")
    ap.add_argument("--hierarchy-levels", default=None,
                    help="overlay closure: 1 (dense), N>=2 (N-level "
                         "tiled hierarchy) or auto (deepen until the "
                         "top closure is small); default: the "
                         "preset's setting, else auto")
    ap.add_argument("--expect-hierarchy", type=int, default=0,
                    help="fail unless the built index uses exactly "
                         "this many overlay levels (CI smoke sanity; "
                         "catches an auto build silently falling back "
                         "to a shallower hierarchy)")
    ap.add_argument("--max-s2-ratio", type=float, default=0.0,
                    help="fail if the level-2 boundary exceeds this "
                         "fraction of S (partitioner-quality gate; "
                         "0 disables)")
    ap.add_argument("--resident-mb", default="auto",
                    help="budget (MB) for the epoch-resident "
                         "pre-lifted row cache on hierarchical "
                         "indices; 0 disables, auto uses the "
                         "built-in default")
    ap.add_argument("--build-workers", type=int, default=1,
                    help="process-parallel per-fragment cover workers "
                         "for the host build (DESIGN.md §17); the "
                         "parallel build is array-equal to --build-"
                         "workers 1 by contract")
    ap.add_argument("--check-build-parity", action="store_true",
                    help="rebuild the host index serially and fail "
                         "unless the --build-workers build is array-"
                         "equal on every index table (CI smoke)")
    ap.add_argument("--batches", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--validate", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", choices=("planner", "fused", "sharded"),
                    default="planner")
    ap.add_argument("--sharded", action="store_true",
                    help="alias for --mode sharded")
    ap.add_argument("--paths", action="store_true",
                    help="also serve exact paths (witness mode + host "
                         "unwind, planner only) and report paths/sec")
    ap.add_argument("--update-batches", type=int, default=0,
                    help="live-traffic rounds after serving (planner)")
    ap.add_argument("--update-frac", type=float, default=0.02,
                    help="fraction of edges perturbed per round")
    ap.add_argument("--json", default="BENCH_serve.json",
                    help="perf-record file ('' disables)")
    live = ap.add_argument_group("live serving (--live)")
    live.add_argument("--live", action="store_true",
                      help="replace the offline batch loop with the "
                           "online serving runtime: open-loop arrivals "
                           "through micro-batching + result cache "
                           "(planner only)")
    live.add_argument("--rate", type=float, default=1500.0,
                      help="offered arrival rate, queries/sec")
    live.add_argument("--live-seconds", type=float, default=4.0,
                      help="load duration (requests = rate * seconds)")
    live.add_argument("--mix", choices=("uniform", "zipf", "geo"),
                      default="zipf", help="query mix")
    live.add_argument("--zipf-a", type=float, default=1.2,
                      help="Zipf exponent for --mix zipf")
    live.add_argument("--deadline-ms", type=float, default=2.0,
                      help="micro-batch flush deadline")
    live.add_argument("--live-batch", type=int, default=256,
                      help="micro-batch size cap (snapped to a planner "
                           "bucket size)")
    live.add_argument("--cache-size", type=int, default=65536,
                      help="result-cache capacity (0 disables)")
    live.add_argument("--hub-budget", type=int, default=0,
                      help="pin hub labels (DESIGN.md §15) for up to "
                           "this many traffic-head nodes (the Zipf "
                           "pool's top-ranked endpoints); 0 disables "
                           "the label hot tier")
    live.add_argument("--hot-tier", type=float, default=0.0,
                      help="fail unless the label tier served at "
                           "least this fraction of cache misses "
                           "(CI smoke gate; requires --hub-budget)")
    live.add_argument("--live-update-batches", type=int, default=0,
                      help="concurrent background refresh rounds "
                           "during the load run")
    live.add_argument("--live-pipelined",
                      action=argparse.BooleanOptionalAction,
                      default=True,
                      help="stage each refresh round through the "
                           "prioritized pipeline (one epoch per work "
                           "item, traffic-weighted order, staleness "
                           "tags); --no-live-pipelined restores the "
                           "monolithic one-epoch-per-round path")
    live.add_argument("--max-serving-gap", type=float, default=0.0,
                      help="fail if no response completes for longer "
                           "than this many seconds during the live "
                           "run (0 disables; the CI road64k smoke "
                           "sets a bound well under the refresh "
                           "wall time, so a stop-the-world re-close "
                           "fails it)")
    live.add_argument("--live-wait-timeout", type=float, default=60.0,
                      help="seconds to wait for every response after "
                           "the load phase (raise at road64k scale: "
                           "flushes contend with concurrent refresh "
                           "FW on CPU)")
    live.add_argument("--live-join-timeout", type=float, default=900.0,
                      help="seconds to wait for background refresh "
                           "rounds to finish after the load phase (a "
                           "road64k hierarchical re-close is minutes "
                           "on CPU)")
    live.add_argument("--live-update-every", type=float, default=0.25,
                      help="seconds between background refresh rounds")
    obs = ap.add_argument_group("observability (DESIGN.md §16)")
    obs.add_argument("--metrics-out", default="",
                     help="write periodic metrics snapshots (JSON + "
                          "Prometheus .prom sidecar) to this path "
                          "during --live ('' disables)")
    obs.add_argument("--metrics-every", type=float, default=2.0,
                     help="seconds between metrics snapshots")
    obs.add_argument("--metrics-port", type=int, default=0,
                     help="serve live Prometheus text at "
                          "127.0.0.1:PORT/metrics during --live "
                          "(0 disables)")
    obs.add_argument("--trace-out", default="",
                     help="enable tracing spans and write the Chrome-"
                          "trace JSON here at exit (build, refresh, "
                          "and per-request serve spans; load in "
                          "chrome://tracing)")
    return ap


def resolve_graph(args) -> None:
    """Apply ``--graph``'s preset (node count, label, hierarchy) and
    normalize the numeric knobs that arrive as strings."""
    preset = None
    if args.graph:
        from ..data.roads import road_preset

        preset = road_preset(args.graph)
        args.nodes = preset.nodes
    args.graph_label = preset.name if preset else f"road{args.nodes}"
    if args.hierarchy_levels is None:
        args.hierarchy_levels = preset.hierarchy if preset else "auto"
    elif args.hierarchy_levels != "auto":
        args.hierarchy_levels = int(args.hierarchy_levels)
    if args.resident_mb != "auto":
        args.resident_mb = float(args.resident_mb)


def main(argv=None) -> None:
    ap = build_parser()
    args = ap.parse_args(argv)
    resolve_graph(args)
    mode = "sharded" if args.sharded else args.mode
    if args.expect_hierarchy and mode != "planner":
        # the guard lives in _build_engine (planner setup); accepting
        # it elsewhere would silently skip the check it exists for
        ap.error("--expect-hierarchy requires --mode planner")
    if args.update_batches and mode != "planner":
        ap.error("--update-batches requires --mode planner")
    if args.check_build_parity and mode != "planner":
        ap.error("--check-build-parity requires --mode planner "
                 "(the parity check lives in the planner setup path)")
    if args.paths and mode != "planner":
        ap.error("--paths requires --mode planner")
    if args.live and mode != "planner":
        ap.error("--live requires --mode planner")
    if args.live and args.paths:
        ap.error("--paths is not supported with --live (the live "
                 "runtime serves distances only)")
    if args.hub_budget and not args.live:
        ap.error("--hub-budget requires --live (the label hot tier "
                 "is a serving-runtime tier)")
    if args.hot_tier and not args.hub_budget:
        ap.error("--hot-tier requires --hub-budget (no labels, no "
                 "label hits to gate on)")
    if (args.metrics_out or args.metrics_port) and not args.live:
        ap.error("--metrics-out/--metrics-port require --live (the "
                 "metrics registry lives on the serving runtime)")
    use_compile_cache()
    if args.trace_out:
        # enable before the build so the build/refresh stage spans
        # land in the same trace as the serve lifecycle events
        trace.get_tracer().enable()

    if args.live:
        engine, _build_s = _build_engine(args)
        _emit(args, _live_loop(engine, args), "live",
              prev_filter={"section": "serve_live",
                           "graph": _label(args),
                           "mix": args.mix, "rate_qps": args.rate,
                           "cache": "on" if args.cache_size else "off",
                           "refresh": "on" if args.live_update_batches
                           else "off"},
              prev_key="p99_ms")
        if args.update_batches:
            _emit(args, _update_loop(engine, args, _build_s), "refresh")
        _write_trace(args)
        return

    engine = None
    if mode == "planner":
        engine, build_s = _build_engine(args)
        dix = engine.dix
    else:
        bt: dict = {}
        with trace.timed("build.graph", bt, "graph",
                         nodes=args.nodes):
            g = road_like(args.nodes, seed=args.seed)
        print(f"graph: n={g.n} m={g.m} ({bt['graph']:.1f}s)")
        with trace.timed("build.host_index", bt, "host_index",
                         build_workers=args.build_workers):
            ix = build_index(g, build_workers=args.build_workers)
        print(f"index: {ix.timings} ({bt['host_index']:.1f}s)")
        _emit(args, _host_build_record(args, ix.timings), "host_build",
              prev_filter={"section": "host_build",
                           "graph": _label(args),
                           "build_workers": args.build_workers},
              prev_key="wall_s")
        with trace.timed("build.device_index", bt, "device_index"):
            dix = build_device_index(
                ix, hierarchy_levels=args.hierarchy_levels)
        build_s = bt["device_index"]
        print(f"device index: frag_apsp={dix.frag_apsp.shape} "
              f"d_super={dix.d_super.shape} ({build_s:.1f}s)")
    g = engine.g if engine is not None else g

    rng = np.random.default_rng(args.seed + 1)
    batch_s = []
    planner = None
    if mode == "sharded":
        mesh = make_host_mesh()
        fn = lambda s, t: serve_sharded(mesh, dix, s, t)  # noqa: E731
    elif mode == "planner":
        planner = engine.planner
        fn = planner
    else:
        jfn = jax.jit(lambda s, t: serve_step(dix, s, t))
        fn = jfn
    # warm-up before timing: the planner pre-compiles every sub-program
    # at every padded bucket size a batch can produce; the other modes
    # compile their one program on a throwaway batch
    if planner is not None:
        planner.warmup(args.batch_size)
    else:
        s = jnp.asarray(rng.integers(0, g.n, args.batch_size), jnp.int32)
        t = jnp.asarray(rng.integers(0, g.n, args.batch_size), jnp.int32)
        jax.block_until_ready(jnp.asarray(fn(s, t)))
    total_q = 0
    last = None
    for i in range(args.batches):
        s = jnp.asarray(rng.integers(0, g.n, args.batch_size), jnp.int32)
        t = jnp.asarray(rng.integers(0, g.n, args.batch_size), jnp.int32)
        t0 = time.perf_counter()
        out = jax.block_until_ready(jnp.asarray(fn(s, t)))
        batch_s.append(time.perf_counter() - t0)
        total_q += args.batch_size
        last = (np.asarray(s), np.asarray(t), np.asarray(out))
    median_s = float(np.median(batch_s))
    per_q = median_s / args.batch_size
    qps = args.batch_size / median_s
    print(f"served {total_q} queries; median batch {median_s*1e3:.2f}ms "
          f"-> {per_q*1e6:.2f}us/query ({qps:,.0f} qps)")
    if planner is not None:
        print(f"planner buckets (last batch): {planner.last_counts}")
    _emit(args, [{
        "section": "serve",
        "graph": _label(args),
        "mode": mode,
        "backend": jax.default_backend(),
        "batch_size": args.batch_size,
        "median_batch_ms": round(median_s * 1e3, 3),
        "us_per_query": round(per_q * 1e6, 3),
        "qps": round(qps, 1),
        **({} if engine is None else _overlay_record(engine)),
    }], mode, prev_filter={"section": "serve",
                           "graph": _label(args), "mode": mode},
        prev_key="us_per_query")
    if args.validate:
        s, t, got = last
        bad = _validate_sample(g, s, t, got, args.validate)
        assert bad == 0
    if args.paths:
        _emit(args, _paths_loop(engine, args), "paths",
              prev_filter={"section": "serve_paths",
                           "graph": _label(args)},
              prev_key="us_per_path")
    if args.update_batches:
        _emit(args, _update_loop(engine, args, build_s), "refresh")
    _write_trace(args)


if __name__ == "__main__":
    main()
