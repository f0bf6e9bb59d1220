"""Open loop of single requests through ``ServingRuntime.submit``.

Arrivals are a Poisson process at the mix's fixed ``rate_qps`` over the
window, drawn from the seed in set-up; each request is submitted at its
scheduled time (or at once, when the generator runs late) and carries
that time, so latency runs from the scheduled arrival to the response.
After the window closes the requests are drained, waiting at most
``mix["drain_s"]`` past the close: one that never comes is failed.  The
result cache is empty when the window starts, as after every epoch
publish.  The check compares a sample drawn from the seed: some
answers drawn uniformly, and some from each tier that answered (result
cache, hub labels, planner), so that a rare tier is covered.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import traffic, window


def prepare(ctx) -> dict:
    """Set-up: the runtime at the configuration's knobs, every flush
    shape compiled, and the window's requests drawn."""
    from repro.serving import ServingRuntime

    cfg, mix = ctx.cell.config, ctx.cell.mix
    runtime = ServingRuntime(ctx.engine, max_batch=int(cfg["max_batch"]),
                             deadline_s=float(cfg["deadline_ms"]) * 1e-3,
                             cache_size=int(cfg["cache_size"]))
    runtime.warmup()
    arrivals = window.uniform_arrivals(
        float(mix["rate_qps"]), ctx.seconds,
        traffic.rng_for(ctx.seed, traffic.ARRIVALS))
    pairs = traffic.draw_pairs(ctx.cell, ctx.edges.n, arrivals.size,
                               traffic.rng_for(ctx.seed, traffic.PAIRS),
                               ctx.pool)
    return {"runtime": runtime, "arrivals": arrivals, "pairs": pairs}


def _counters(registry) -> dict:
    out = {}
    for name, v in registry.snapshot().items():
        if isinstance(v, (int, float)):
            out[name] = float(v)
        elif isinstance(v, dict) and all(isinstance(x, (int, float))
                                         for x in v.values()):
            for k, x in v.items():
                out[f"{name}.{k}"] = float(x)
    return out


class _Book:
    """Compact records of the window's requests.  A request is kept
    only while it is in flight: holding every finished ``Request`` for
    the window would grow the collector's oldest generation and make
    the harness, not the server, pause for full collections."""

    def __init__(self, n: int):
        self.got = np.full(n, np.nan)
        self.t_done = np.full(n, np.nan)
        self.t_submit = np.full(n, np.nan)
        self.tier = np.full(n, "", dtype=object)
        self.tiers: dict = {}
        self.flight: collections.deque = collections.deque()

    def record(self, i: int, r) -> None:
        self.t_submit[i] = r.t_submit
        ok = r.done and r.error is None
        tier = r.tier if ok else "failed"
        self.tiers[tier] = self.tiers.get(tier, 0) + 1
        self.tier[i] = tier
        if ok:
            self.got[i] = r.dist
            self.t_done[i] = r.t_done

    def harvest(self) -> None:
        """Record the finished requests at the head of the flight."""
        while self.flight and self.flight[0][1].done:
            self.record(*self.flight.popleft())


def run_window(ctx, state, seconds: float) -> dict:
    runtime, arr, pairs = state["runtime"], state["arrivals"], state["pairs"]
    book = _Book(arr.size)
    before = _counters(runtime.registry)
    with ctx.annotate("bench.window"):
        t0 = time.perf_counter()
        with ctx.annotate("bench.live.submit_loop"):
            for i in range(arr.size):
                book.harvest()
                delay = t0 + arr[i] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                try:
                    book.flight.append((i, runtime.submit(
                        int(pairs[i, 0]), int(pairs[i, 1]),
                        t_sched=t0 + arr[i])))
                except RuntimeError:        # refused: the server closed
                    book.tiers["refused"] = book.tiers.get("refused", 0) + 1
        close = t0 + seconds
        with ctx.annotate("bench.live.drain"):
            while book.flight:
                i, r = book.flight.popleft()
                r.wait(max(0.0, close + float(ctx.cell.mix["drain_s"])
                           - time.perf_counter()))
                book.record(i, r)
    after = _counters(runtime.registry)
    runtime.close()
    ok = ~np.isnan(book.got)
    t_sched = t0 + arr
    lat = window.latency_ms(t_sched[ok], book.t_done[ok])
    sub = ~np.isnan(book.t_submit)
    late = window.lateness_p99_ms(t_sched[sub], book.t_submit[sub]) \
        if sub.any() else float("nan")
    deltas = {k: after[k] - before.get(k, 0.0) for k in after}
    deltas["requests"] = float(arr.size)
    deltas["max_batch"] = float(runtime.max_batch)
    drain = float(np.nanmax(book.t_done, initial=close)) - close
    e2e = {}
    if lat.size:
        e2e = {"p50_ms": window.exact_percentile(lat, 50),
               "p99_ms": window.exact_percentile(lat, 99)}
    return {
        "e2e": e2e, "pairs": pairs, "got": book.got, "latency_ms": lat,
        "drain_past_close_s": drain, "lateness_p99_ms": late,
        "attempted": int(arr.size), "failed": int(np.sum(~ok)),
        "answered": int(ok.sum()), "buckets": None, "tier": book.tier,
        "counters": deltas,
        "log": [f"{arr.size} requests at {arr.size / seconds:.1f} qps "
                f"offered; generator lateness p99 {late:.6f} ms; drain "
                f"past the close {drain:.6f} s; tiers "
                f"{book.tiers}; flushes "
                f"{deltas.get('serve.batch.flushes.full', 0):.0f} full / "
                f"{deltas.get('serve.batch.flushes.deadline', 0):.0f} "
                f"deadline"],
    }


def sample(ctx, res: dict) -> np.ndarray:
    """Indices of the answers the reference checks."""
    chk = ctx.cell.mix["check"]
    rng = traffic.rng_for(ctx.seed, traffic.SAMPLE)
    total = res["got"].size
    idx = [rng.choice(total, size=min(int(chk["uniform"]), total),
                      replace=False)]
    for tier in sorted(set(res["tier"]) - {""}):
        members = np.nonzero(res["tier"] == tier)[0]
        idx.append(rng.choice(members, size=min(int(chk["per_tier"]),
                                                members.size),
                              replace=False))
    return np.unique(np.concatenate(idx))
