"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's deployment from its configuration (graph,
host and device index, hub labels) and warms up the shapes its traffic
uses; then the driver measures for ``--seconds``, the answers are
compared with the reference, and the last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device``, with ``--trace 1`` a ``breakdown``, and last the
numbers compared with their limits (also the last lines of standard
error).  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import cells, graphgen, reference, trace_reduce, traffic  # noqa: E402

#: limits of the numbers that decide ``correct`` (exact comparison)
LIMITS = {"mismatches": 0, "unanswered": 0}


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at one fixed directory:
    ``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``.
    Every program is kept, however fast it compiled, so that a second
    run of a cell compiles nothing."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_devices(chips: int, root: Path) -> list:
    """The cell's devices; raises SystemExit without a TPU, with fewer
    chips than asked, or on a device the peak table does not know."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found platform "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    with open(root / "bench" / "peaks.json") as f:
        peaks = json.load(f)
    if devs[0].device_kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind "
                         f"{devs[0].device_kind!r} in bench/peaks.json")
    return devs[:chips]


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's seed and window, the
    deployment's data and the engine under test."""
    cell: cells.Cell
    seed: int
    seconds: float
    edges: graphgen.Edges
    pool: np.ndarray
    engine: object
    tracing: bool

    def annotate(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


class WindowWatch:
    """Counts, inside its ``with`` block, what should not happen inside
    the window: JAX tracing and compiling, and the collector's full
    collections with their pauses.  Reported on standard error."""

    def __init__(self):
        import jax

        self.on = False
        self.compiles: dict = {}
        self.full_gc: list = []
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._jax)

    def __enter__(self):
        gc.callbacks.append(self._gc)
        self.on = True
        return self

    def __exit__(self, *exc):
        self.on = False
        gc.callbacks.remove(self._gc)
        return False

    def _jax(self, name, secs, **kw):
        if self.on and name.startswith("/jax/core/compile/"):
            key = name.rsplit("/", 1)[-1]
            self.compiles[key] = self.compiles.get(key, 0) + 1

    def _gc(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.on:
            self.full_gc.append(time.perf_counter() - self._gc_t0)

    def report(self) -> str:
        return (f"in the window: compile events {self.compiles or 'none'}; "
                f"{len(self.full_gc)} full collections, longest "
                f"{max(self.full_gc, default=0.0) * 1e3:.3f} ms")


#: compiles and persistent-cache lookups since the process started,
#: counted once ``count_compiles`` has run
COMPILES = {"backend_compiles": 0, "compile_s": 0.0, "cache_hits": 0,
            "cache_misses": 0}


def count_compiles() -> None:
    """Count JAX's backend compiles and compile-cache hits and misses
    into ``COMPILES``: set-up reports them, so that a run which found
    its programs in the cache shows it."""
    import jax

    def duration(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            COMPILES["backend_compiles"] += 1
            COMPILES["compile_s"] += secs

    def event(name, **kw):
        key = name.rsplit("/", 1)[-1]
        if name.startswith("/jax/compilation_cache/") and key in COMPILES:
            COMPILES[key] += 1

    jax.monitoring.register_event_duration_secs_listener(duration)
    jax.monitoring.register_event_listener(event)


@dataclasses.dataclass
class View:
    """What a per-layer metric reader gets."""
    trace: dict | None
    counters: dict | None
    answered: int


def build(cell: cells.Cell):
    """The deployment: graph, popular pairs, hub nodes, engine."""
    from repro.core.dist_engine import EpochedEngine
    from repro.core.graph import Graph

    cfg = cell.config
    t0 = time.perf_counter()
    edges = graphgen.road_like(int(cfg["nodes"]), int(cfg["graph_seed"]))
    want = cfg["expect"]
    if (edges.n, edges.m) != (want["nodes"], want["edges"]):
        raise SystemExit(f"bench: graph has {edges.n} nodes and {edges.m} "
                         f"edges, the configuration expects {want}")
    pool = traffic.popular_pool(cfg, edges.n)
    hubs = traffic.hub_nodes(pool, int(cfg["hub_budget"]))
    g = Graph.from_edges(edges.n, edges.u, edges.v, edges.w)
    t1 = time.perf_counter()
    engine = EpochedEngine(g, warm_refresh=False, paths=False,
                           hierarchy_levels=int(cfg["hierarchy_levels"]),
                           resident_mb=cfg["resident_mb"], hub_nodes=hubs)
    levels = engine.plan.hierarchy_levels
    if levels != int(cfg["hierarchy_levels"]):
        raise SystemExit(f"bench: built {levels} hierarchy levels, the "
                         f"configuration states {cfg['hierarchy_levels']}")
    log(f"graph {edges.n} nodes {edges.m} edges in {t1 - t0:.6f}s; "
        f"engine in {time.perf_counter() - t1:.6f}s; {levels} levels; "
        f"stages {engine.plan.build_timings}")
    return edges, pool, engine


def check(ctx: Context, res: dict) -> tuple[dict, np.ndarray, np.ndarray]:
    """Compare the sampled answers with the reference.  Returns the
    numbers compared, and the checked pairs with the reference's
    distances."""
    idx = ctx.cell.driver.sample(ctx, res)
    pairs, got = res["pairs"][idx], res["got"][idx]
    uniq, inv = np.unique(pairs, axis=0, return_inverse=True)
    t0 = time.perf_counter()
    want = reference.Reference(ctx.edges).distances(uniq[:, 0],
                                                    uniq[:, 1])
    want = want[inv.ravel()]
    given = ~np.isnan(got)
    bad = reference.mismatches(got[given], want[given])
    log(f"checked {idx.size} answers ({uniq.shape[0]} distinct pairs) "
        f"against the reference in {time.perf_counter() - t0:.6f}s")
    return ({"mismatches": bad, "unanswered": res["failed"]},
            pairs, want)


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool, *,
             devices=None, t_start: float = T_START, built=None) -> dict:
    """One run of ``cell``; returns the result line as a dict.
    ``built`` hands in a deployment that ``build`` made earlier."""
    import jax

    devices = devices or jax.devices()[:cell.chips]
    edges, pool, engine = built or build(cell)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, edges=edges,
                  pool=pool, engine=engine, tracing=trace)
    state = cell.driver.prepare(ctx)
    # no full collection left due from set-up's garbage to fall in the
    # window
    gc.collect()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.6f}s; since the start "
        + ", ".join(f"{k} {v:.6g}" for k, v in COMPILES.items()))
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            trace_reduce.start(log_dir)
        try:
            with WindowWatch() as watch:
                res = cell.driver.run_window(ctx, state, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        red = trace_reduce.reduce_trace(log_dir) if trace else None
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    for line in res["log"] + [watch.report()]:
        log(line)
    stats = devices[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    log(f"device memory peak {peak} bytes of "
        f"{stats.get('bytes_limit', 'unknown')}")
    del state, engine
    ctx.engine = None
    gc.collect()
    compared = check(ctx, res)[0]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": all(compared[k] <= LIMITS[k] for k in LIMITS),
           "attempted": res["attempted"], "failed": res["failed"]}
    if trace:
        view = View(trace=red, counters=res["counters"],
                    answered=res["answered"])
        metrics = {}
        for m, reader in cell.per_layer:
            v = reader.read(view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = trace_reduce.breakdown(red)
        log(f"device busy {red['busy_s']:.6f}s of a "
            f"{red['window_s']:.6f}s window; programs "
            + json.dumps(red["modules"]))
    else:
        e2e = dict(res["e2e"], setup_s=setup_s)
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
            elif out["correct"]:
                # a run that answered nothing is not correct, and has
                # no latency to report
                raise RuntimeError(f"the driver reported no {m['name']}")
        out["metrics"] = metrics
        out["device"] = device
    out["compared"] = {k: {"value": compared[k], "limit": LIMITS[k]}
                       for k in LIMITS}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload, ROOT)
    devices = find_devices(cell.chips, ROOT)
    use_compile_cache(ROOT)
    count_compiles()
    sys.path.insert(0, str(ROOT / "src"))
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices=devices)
    for k, c in out["compared"].items():
        log(f"compared: {k} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
