"""Window and percentile arithmetic: exact percentiles over every
request, and a stall inside the window shows in the rate."""
import time
import types

import bench_tiny
import numpy as np
import pytest

from bench import cells, window

OFFLINE = cells.load_module(bench_tiny.REPO / "bench/drivers/offline_batch.py")


def test_exact_percentile_is_the_nearest_rank_over_all_values():
    v = np.arange(1, 1001, dtype=float)[::-1]      # order does not matter
    assert window.exact_percentile(v, 50) == 500
    assert window.exact_percentile(v, 99) == 990
    assert window.exact_percentile(v, 100) == 1000
    # one slow request in a hundred is the 99th percentile's business
    lat = np.full(100, 2.0)
    lat[-1] = 500.0
    assert window.exact_percentile(lat, 99) == 2.0
    lat[-2] = 400.0
    assert window.exact_percentile(lat, 99) == 400.0
    with pytest.raises(ValueError):
        window.exact_percentile([], 50)


def test_latency_runs_from_the_scheduled_arrival():
    sched = np.array([0.0, 0.1, 0.2, 0.3])
    # a 0.5 s stall at 0.05 s holds every request queued behind it
    done = np.maximum(sched, 0.55) + 0.001
    lat = window.latency_ms(sched, done)
    assert lat[0] == pytest.approx(551.0) and lat[3] == pytest.approx(251.0)
    assert window.exact_percentile(lat, 99) == pytest.approx(551.0)
    assert window.lateness_p99_ms(sched, sched + 0.002) == pytest.approx(2.0)


def test_closed_loop_rate_counts_all_work_over_all_time():
    assert window.closed_loop_rate(10.0, [11.0, 12.0, 14.0],
                                   [100, 100, 100]) == pytest.approx(75.0)
    with pytest.raises(ValueError):
        window.closed_loop_rate(0.0, [], [])


class _Engine:
    """Answers every batch with zeros after ``batch_s``; one batch
    stalls for ``stall_s``."""

    def __init__(self, batch_s, stall_s=0.0, stall_at=2):
        self.batch_s, self.stall_s, self.stall_at = batch_s, stall_s, stall_at
        self.calls = 0
        self.planner = types.SimpleNamespace(
            plan=lambda s, t: {"cross_frag": np.arange(s.size)})

    def query(self, s, t):
        self.calls += 1
        time.sleep(self.batch_s + (self.stall_s if self.calls == self.stall_at
                                   else 0.0))
        return np.zeros(s.size, np.float32)


def _offline_rate(engine, seconds=0.6):
    cell = types.SimpleNamespace(mix={"batch": 32})
    ctx = types.SimpleNamespace(cell=cell, engine=engine, seed=1, pool=None,
                                edges=types.SimpleNamespace(n=100),
                                annotate=lambda name: _null())
    job = [np.stack([np.arange(32), np.arange(32) + 1], axis=1)] * 2
    res = OFFLINE.run_window(
        ctx, {"job": job, "rng": np.random.default_rng(1)}, seconds)
    return res["e2e"]["queries_per_s"], res


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def test_a_stall_inside_the_window_shows_in_queries_per_s():
    steady, res = _offline_rate(_Engine(0.02))
    stalled, res_s = _offline_rate(_Engine(0.02, stall_s=0.3))
    assert res["failed"] == 0 and res["attempted"] % 32 == 0
    # the stall takes about half the window's time, and the rate with it
    assert stalled < 0.75 * steady


OPEN_LOOP = cells.load_module(bench_tiny.REPO / "bench/drivers/open_loop.py")


@pytest.mark.parametrize("seed", [5, 2**33 + 1])
def test_the_live_sample_covers_every_tier_that_answered(seed):
    tier = np.array(["planner"] * 5000 + ["label"] * 3 + ["cache"] * 40
                    + [""] * 7, dtype=object)
    cell = types.SimpleNamespace(mix={"check": {"uniform": 100,
                                                "per_tier": 24}})
    ctx = types.SimpleNamespace(cell=cell, seed=seed)
    res = {"got": np.zeros(tier.size), "tier": tier}
    idx = OPEN_LOOP.sample(ctx, res)
    assert np.array_equal(idx, OPEN_LOOP.sample(ctx, res))
    assert np.all(np.diff(idx) > 0)
    picked = list(tier[idx])
    # the rare tier whole, the others at least their share
    assert picked.count("label") == 3 and picked.count("cache") >= 24
    assert picked.count("planner") >= 24 and len(idx) <= 100 + 3 * 24
