"""Observability layer tests (DESIGN.md §16): metrics registry
thread-safety, streaming-histogram percentile exactness against a
sorted-list reference, span nesting/ordering invariants, trace-export
golden structure from a deterministic scripted serve, the perflog
atomic-append contract under concurrency, and the measured cost of the
disabled tracing path.

The percentile contract under test: ``Histogram.percentile(q)`` must
land within one geometric bucket (``growth`` relative error, 5% by
default) of the exact nearest-rank answer, clamped into the exact
tracked [min, max] — and the phase-scoped ``since()`` window must obey
the same bound using only bucket-count subtraction.
"""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.obs import (Counter, Histogram, MetricsExporter,
                       MetricsRegistry, MetricsServer, SlowQueryLog,
                       Tracer, load_chrome_trace, write_chrome_trace,
                       write_snapshot)
from repro.obs import trace as trace_mod
from repro.perflog import append_records, read_records


# ---------------------------------------------------------------------------
# metrics primitives + registry
# ---------------------------------------------------------------------------
def test_registry_get_or_create_shares_instances():
    reg = MetricsRegistry()
    c1 = reg.counter("serve.cache.hits")
    c2 = reg.counter("serve.cache.hits")
    assert c1 is c2
    c1.inc(3)
    assert c2.value == 3
    assert reg.names() == ["serve.cache.hits"]
    assert reg.get("serve.cache.hits") is c1
    assert reg.get("nope") is None


def test_registry_type_conflict_raises():
    """Two call sites silently aliasing one name to different
    primitives is always a bug — it must raise, not return either."""
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.histogram("x")


def test_registry_concurrent_increments_exact():
    """The thread-safety contract: N threads hammering shared
    counters/labels/histograms lose no update — totals are exact."""
    reg = MetricsRegistry()
    n_threads, n_iter = 8, 2000
    barrier = threading.Barrier(n_threads)

    def work(tid):
        barrier.wait()
        c = reg.counter("c")            # get-or-create races too
        lab = reg.labeled("lab")
        h = reg.histogram("h")
        g = reg.gauge("g")
        for i in range(n_iter):
            c.inc()
            lab.inc(tid % 3)
            h.observe(1e-3 * (1 + (i % 7)))
            g.set(i)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_iter
    assert reg.counter("c").value == total
    assert reg.labeled("lab").total == total
    assert sum(reg.labeled("lab").snapshot().values()) == total
    assert reg.histogram("h").count == total
    snap = reg.histogram("h").freeze()
    assert sum(snap.counts.values()) == total


def _exact_nearest_rank(xs, q):
    xs = np.sort(np.asarray(xs, float))
    rank = max(1, int(np.ceil(q / 100.0 * len(xs))))
    return float(xs[rank - 1])


@pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
def test_histogram_percentiles_match_sorted_list(dist):
    """p50/p95/p99 from the streaming histogram vs the exact sorted
    list: within one bucket (5% relative) of the nearest-rank answer,
    and always inside the exact observed [min, max]."""
    rng = np.random.default_rng(hash(dist) % 2**31)
    if dist == "lognormal":
        xs = rng.lognormal(-6.0, 1.0, size=5000)      # ~ms latencies
    elif dist == "uniform":
        xs = rng.uniform(1e-4, 5e-2, size=5000)
    else:
        xs = np.concatenate([rng.normal(2e-3, 2e-4, 2500),
                             rng.normal(4e-2, 3e-3, 2500)]).clip(1e-6)
    h = Histogram("lat")
    for x in xs:
        h.observe(float(x))
    snap = h.freeze()
    assert snap.count == len(xs)
    assert snap.min == pytest.approx(float(xs.min()))
    assert snap.max == pytest.approx(float(xs.max()))
    for q in (1, 25, 50, 90, 95, 99, 99.9, 100):
        got = snap.percentile(q)
        want = _exact_nearest_rank(xs, q)
        assert want / h.growth <= got <= want * h.growth, (q, got, want)
        assert snap.min <= got <= snap.max
    assert snap.mean == pytest.approx(float(xs.mean()), rel=1e-9)


def test_histogram_edge_cases():
    h = Histogram("h")
    assert h.freeze().percentile(99) == 0.0        # empty: defined 0
    h.observe(5e-3)
    s = h.freeze()
    # single observation: every percentile IS that observation (the
    # min==max clamp defeats bucket-midpoint error entirely)
    for q in (0, 50, 100):
        assert s.percentile(q) == pytest.approx(5e-3)
    # outlier beyond the top bucket: mass is clamped, max stays exact
    h2 = Histogram("h2", max_buckets=64)
    h2.observe(1e9)
    assert h2.freeze().max == 1e9
    with pytest.raises(ValueError):
        Histogram("bad", growth=1.0)


def test_histogram_since_window_is_phase_scoped():
    """since(prev) must report ONLY the observations after the freeze
    point — the mechanism run_load uses to scope a shared runtime
    histogram to one load phase."""
    rng = np.random.default_rng(7)
    a = rng.uniform(1e-3, 2e-3, 300)               # phase A: fast
    b = rng.uniform(5e-2, 9e-2, 400)               # phase B: slow
    h = Histogram("lat")
    for x in a:
        h.observe(float(x))
    h0 = h.freeze()
    for x in b:
        h.observe(float(x))
    win = h.since(h0)
    assert win.count == len(b)
    assert win.sum == pytest.approx(float(b.sum()), rel=1e-6)
    for q in (50, 95, 99):
        got = win.percentile(q)
        want = _exact_nearest_rank(b, q)
        # window min/max fall back to bucket bounds, so allow one
        # bucket of slack on each side of the exact-reference bound
        assert want / h.growth**2 <= got <= want * h.growth**2
        assert got > float(a.max())                # phase A invisible
    # empty window
    h1 = h.freeze()
    assert h.since(h1).count == 0
    assert h.since(h1).percentile(99) == 0.0


def test_registry_snapshot_and_prometheus_render():
    reg = MetricsRegistry()
    reg.counter("serve.cache.hits").inc(5)
    reg.gauge("serve.epoch").set(3)
    reg.labeled("serve.batch.flushes").inc("deadline", 2)
    reg.array_counter("serve.frag_traffic", 4).add(
        np.array([0, 2, 0, 7], np.int64))
    reg.histogram("serve.request.latency_s").observe(1e-3)
    snap = reg.snapshot()
    assert snap["serve.cache.hits"] == 5
    assert snap["serve.batch.flushes"] == {"deadline": 2}
    assert snap["serve.frag_traffic"]["total"] == 9
    assert snap["serve.frag_traffic"]["nonzero"] == 2
    assert snap["serve.request.latency_s"]["count"] == 1
    json.dumps(snap)                               # JSON-safe
    prom = reg.prometheus()
    assert "# TYPE serve_cache_hits counter" in prom
    assert "serve_epoch 3" in prom
    assert 'serve_batch_flushes{label="deadline"} 2' in prom
    assert 'serve_request_latency_s{quantile="0.99"}' in prom
    assert "serve_request_latency_s_count 1" in prom


# ---------------------------------------------------------------------------
# tracing spans
# ---------------------------------------------------------------------------
def test_disabled_span_is_shared_noop_singleton():
    """The disabled fast path allocates nothing: every span() call
    returns the same no-op object, events are dropped before building
    anything, and timed() still fills the timings dict."""
    tr = Tracer(enabled=False)
    s1, s2 = tr.span("a", k=1), tr.span("b")
    assert s1 is s2                                # shared singleton
    with s1:
        pass
    tr.event("e", 0.0, 1.0, tag=1)
    assert tr.events() == []
    out = {}
    with tr.timed("t", out, "stage"):
        time.sleep(0.002)
    assert out["stage"] >= 0.002                   # timed ALWAYS times
    assert tr.events() == []                       # ... but no event


def test_span_nesting_and_ordering_invariants():
    """Nested spans: children emit before parents (exit order), carry
    their depth, and parent intervals contain child intervals."""
    tr = Tracer(enabled=True)
    with tr.span("outer", stage="build"):
        assert tr.depth == 1
        with tr.span("inner"):
            assert tr.depth == 2
            time.sleep(0.001)
        with tr.span("inner2"):
            pass
    assert tr.depth == 0
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "inner2", "outer"]
    outer = evs[2]
    assert outer["ph"] == "X" and outer["args"]["stage"] == "build"
    assert "depth" not in outer["args"]            # top level
    for child in evs[:2]:
        assert child["args"]["depth"] == 1
        assert child["ts"] >= outer["ts"]
        assert child["ts"] + child["dur"] \
            <= outer["ts"] + outer["dur"] + 1e-3
    assert evs[0]["ts"] + evs[0]["dur"] <= evs[1]["ts"] + 1e-3


def test_span_depth_is_per_thread():
    tr = Tracer(enabled=True)
    seen = {}
    # all four threads stay alive inside their spans together, so no
    # thread id is reused by a later thread
    together = threading.Barrier(4, timeout=10)

    def work(tid):
        with tr.span(f"t{tid}"):
            time.sleep(0.005)
            seen[tid] = tr.depth
            together.wait()
    threads = [threading.Thread(target=work, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(d == 1 for d in seen.values())      # no cross-thread
    tids = {e["tid"] for e in tr.events()}
    assert len(tids) == 4                          # separate rows


def test_tracer_buffer_bounded_with_drop_count():
    tr = Tracer(enabled=True, max_events=10)
    for i in range(25):
        tr.event(f"e{i}", 0.0, 1.0)
    evs = tr.events()
    assert len(evs) == 10 and tr.dropped == 15
    assert evs[-1]["name"] == "e24"                # oldest dropped
    assert tr.drain() and tr.events() == []


def test_disabled_path_is_cheap():
    """The overhead argument's foundation: a disabled span() call is
    orders of magnitude under a request's budget.  Bound it loosely
    (2µs/call average over 200k calls — CI machines are noisy; the
    real number is tens of ns)."""
    tr = Tracer(enabled=False)
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("hot", epoch=1, tier="cache"):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 2e-6, f"{per_call * 1e9:.0f}ns per disabled span"


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def test_chrome_trace_roundtrip_and_truncation(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("a", k=1):
        with tr.span("b"):
            pass
    path = str(tmp_path / "trace.json")
    write_chrome_trace(path, tr.events())
    back = load_chrome_trace(path)
    assert back == tr.events()
    # a crash mid-run leaves a trailing-comma, no-] file — the Chrome
    # trace array format tolerates that, and so must the loader
    lines = open(path).read().splitlines()
    (tmp_path / "trunc.json").write_text("\n".join(lines[:-1]))
    assert load_chrome_trace(str(tmp_path / "trunc.json")) \
        == tr.events()[:-1]
    (tmp_path / "empty.json").write_text("[\n")
    assert load_chrome_trace(str(tmp_path / "empty.json")) == []


def test_metrics_snapshot_and_exporter(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc(2)
    path = str(tmp_path / "metrics.json")
    snap = write_snapshot(path, reg, extra={"run": "test"})
    on_disk = json.loads(open(path).read())
    assert on_disk["metrics"]["c"] == 2 and on_disk["run"] == "test"
    assert snap["metrics"] == on_disk["metrics"]
    prom = open(str(tmp_path / "metrics.prom")).read()
    assert "# TYPE c counter" in prom
    # the periodic exporter writes a final snapshot on stop, so even a
    # run shorter than one interval leaves a complete file
    exp = MetricsExporter(reg, path, interval_s=60.0,
                          extra=lambda: {"slow_queries": []}).start()
    reg.counter("c").inc(1)
    exp.stop()
    assert exp.writes >= 1
    assert json.loads(open(path).read())["metrics"]["c"] == 3


def test_metrics_http_server():
    reg = MetricsRegistry()
    reg.counter("serve.cache.hits").inc(7)
    srv = MetricsServer(reg, port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        prom = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "serve_cache_hits 7" in prom
        js = json.loads(urllib.request.urlopen(base + "/").read())
        assert js["metrics"]["serve.cache.hits"] == 7
    finally:
        srv.stop()


def test_slow_query_log_keeps_worst_n():
    log = SlowQueryLog(n=3)
    for i, lat in enumerate([0.01, 0.5, 0.02, 0.3, 0.001, 0.4]):
        log.offer(lat, {"s": i, "t": i + 1, "tier": "planner"})
    recs = log.records()
    assert log.offered == 6 and len(recs) == 3
    assert [r["latency_ms"] for r in recs] == [500.0, 400.0, 300.0]
    assert recs[0]["s"] == 1 and recs[0]["tier"] == "planner"
    json.dumps(recs)


# ---------------------------------------------------------------------------
# scripted serve -> trace export (golden structure)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine():
    from repro.core.dist_engine import EpochedEngine
    from repro.core.graph import road_like

    g = road_like(380, seed=11)
    eng = EpochedEngine(g)
    eng.warmup(64)
    return eng


def test_scripted_serve_trace_export(engine, tmp_path):
    """Deterministic single-thread serve (auto=False) with the default
    tracer enabled: the exported Chrome trace must contain the request
    lifecycle — flush spans sized/bucketed, per-request events tagged
    with tier/epoch/staleness, tier-resolution spans — and load back
    structurally identical."""
    from repro.core.graph import traffic_updates
    from repro.serving import ServingRuntime

    e0 = engine.epoch
    tr = trace_mod.get_tracer()
    tr.clear()
    tr.enable()
    try:
        rt = ServingRuntime(engine, max_batch=64, cache_size=64,
                            auto=False)
        rng = np.random.default_rng(3)
        pairs = rng.integers(0, engine.g.n, (12, 2))
        for a, b in pairs:
            rt.submit(int(a), int(b))
        assert rt.flush() == 12
        # epoch moves; resubmit a prefix (cache goes stale) + fresh
        u, v, w = traffic_updates(engine.g, frac=0.02, seed=5)
        engine.apply_updates(u, v, w)
        for a, b in pairs[:6]:
            rt.submit(int(a), int(b))
        rt.flush()
        rt.close()
        events = tr.drain()
    finally:
        tr.enable(False)
        tr.clear()

    by_name: dict = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    flushes = by_name.get("serve.flush", [])
    assert len(flushes) == 2
    assert flushes[0]["args"]["size"] == 12
    assert flushes[0]["args"]["bucket"] >= 12      # pow2 pad
    reqs = by_name.get("serve.request", [])
    assert len(reqs) == 18
    for e in reqs:
        assert e["ph"] == "X" and e["dur"] >= 0
        assert e["args"]["tier"] in ("cache", "label", "planner")
        assert e["args"]["epoch"] in (e0, e0 + 1)
        assert e["args"]["staleness"] >= 0
    # epoch tags advance across the refresh
    assert {e["args"]["epoch"] for e in reqs} == {e0, e0 + 1}
    assert by_name.get("serve.cache_lookup")
    assert by_name.get("serve.tier.planner")
    # tier-resolution spans nest inside their flush span: in time, and
    # as spans (parent ids) one level below it
    f0 = flushes[0]
    t0 = by_name["serve.tier.planner"][0]
    assert f0["ts"] <= t0["ts"] + 1e-3
    assert t0["ts"] + t0["dur"] <= f0["ts"] + f0["dur"] + 1e-3
    flush_ids = {f["args"]["id"] for f in flushes}
    tiers = by_name["serve.tier.planner"] + by_name.get("serve.tier.label",
                                                        [])
    for t in tiers:
        assert t["args"]["parent"] in flush_ids
        assert t["args"]["depth"] == f0["args"].get("depth", 0) + 1
    # ... and the planner's own spans one level below the tiers'
    assert by_name.get("planner.query")
    assert {q["args"]["parent"] for q in by_name["planner.query"]} \
        <= {t["args"]["id"] for t in tiers}

    # golden write -> load roundtrip (chrome://tracing-compatible)
    path = str(tmp_path / "serve_trace.json")
    write_chrome_trace(path, events)
    assert load_chrome_trace(path) == events


def test_runtime_metrics_registry_view(engine):
    """The runtime's registry view of one scripted serve: named
    metrics agree with the legacy stats() dict they replaced."""
    from repro.serving import ServingRuntime

    rt = ServingRuntime(engine, max_batch=64, cache_size=64,
                        auto=False)
    rng = np.random.default_rng(4)
    pairs = rng.integers(0, engine.g.n, (10, 2))
    for a, b in pairs:
        rt.submit(int(a), int(b))
    rt.flush()
    for a, b in pairs:                             # all cache hits
        rt.submit(int(a), int(b))
    rt.flush()
    rt.close()
    st = rt.stats()
    reg = rt.registry
    assert reg.counter("serve.cache.hits").value == st["cache_hits"]
    assert reg.counter("serve.tier.planner.dispatches").value \
        == st["planner_dispatches"]
    hist = rt.latency_histogram()
    assert hist.count == 20                        # every request
    assert hist.summary(scale=1e3)["p99"] > 0
    assert reg.labeled("serve.batch.flushes").get("manual") == 2


def test_tracing_overhead_loose_ab(engine):
    """A-B at test scale: the same scripted serve with tracing +
    exporters enabled must stay within 40% of the disabled wall time
    (min of 3 repeats each — CI machines are noisy; the real budget,
    <2% live qps at road4000, is measured by scripts/obs_overhead.py
    and recorded in BENCH_serve.json)."""
    from repro.serving import ServingRuntime

    rng = np.random.default_rng(9)
    pairs = rng.integers(0, engine.g.n, (64, 2))

    def one_run(traced, tmpdir=None):
        tr = trace_mod.get_tracer()
        if traced:
            tr.clear()
            tr.enable()
        rt = ServingRuntime(engine, max_batch=64, cache_size=0,
                            auto=False)
        t0 = time.perf_counter()
        for a, b in pairs:
            rt.submit(int(a), int(b))
            rt.flush()
        wall = time.perf_counter() - t0
        rt.close()
        if traced:
            tr.enable(False)
            tr.clear()
        return wall

    one_run(False), one_run(True)                  # warm both paths
    off = min(one_run(False) for _ in range(3))
    on = min(one_run(True) for _ in range(3))
    assert on <= off * 1.40, f"tracing overhead {on / off:.2f}x"


# ---------------------------------------------------------------------------
# perflog atomic append
# ---------------------------------------------------------------------------
def test_perflog_concurrent_appends_lose_nothing(tmp_path):
    """N threads x M appends through the flock'd read-modify-write:
    every record lands exactly once and the file is valid JSON at the
    end — the regression test for the lost-update/truncation bug the
    temp-file + lock rewrite fixed."""
    path = str(tmp_path / "bench.json")
    append_records(path, [{"seed": True}])
    n_threads, n_appends = 6, 20
    barrier = threading.Barrier(n_threads)

    def work(tid):
        barrier.wait()
        for i in range(n_appends):
            append_records(path, [{"tid": tid, "i": i}])

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = read_records(path)
    assert len(recs) == 1 + n_threads * n_appends
    got = {(r["tid"], r["i"]) for r in recs if "tid" in r}
    assert got == {(t, i) for t in range(n_threads)
                   for i in range(n_appends)}
    json.load(open(path))                          # well-formed


def test_perflog_append_survives_corrupt_history(tmp_path):
    path = str(tmp_path / "bench.json")
    with open(path, "w") as f:
        f.write('[{"half": ')                      # torn write
    append_records(path, [{"ok": 1}])
    assert read_records(path) == [{"ok": 1}]
