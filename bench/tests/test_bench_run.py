"""Whole runs of a tiny cell on the CPU, past the harness's look for a
chip: a sound run is correct, the control is not, and each fault the
cells can have, planted under the timed path, makes ``correct`` false."""
import json
import os
import subprocess
import sys
import time

import bench_tiny
import pytest

from bench import calibrate, cells, run, window

CELLS = [bench_tiny.OFFLINE, bench_tiny.LIVE]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench"))
    built = run.build(cells.resolve(bench_tiny.OFFLINE, root))
    return root, built


def _run(tiny, name, seconds=1.0, seed=2**31 + 11):
    root, built = tiny
    return run.run_cell(cells.resolve(name, root), seed, seconds, False,
                        built=built, t_start=time.perf_counter())


def test_run_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(bench_tiny.REPO / "bench/run.py"),
         "--workload", "road64k.offline-uniform", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(tiny, name):
    out = _run(tiny, name)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = {"setup_s"} | ({"queries_per_s"} if name == bench_tiny.OFFLINE
                          else {"p50_ms", "p99_ms"})
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "compared"
    assert out["compared"]["mismatches"] == {"value": 0, "limit": 0}
    json.dumps(out)


def _answer_altered(orig):
    """One answer of every planner call changed where it is produced
    (the tiny live cell's sample holds every answer)."""
    def query(self, s, t, **kw):
        out = orig(self, s, t, **kw).copy()
        out[0] += 1.0
        return out
    return query


def _bucket_altered(case):
    """Every answer of one planner bucket's program changed: the
    offline cell samples each bucket that served, the rare ones included."""
    def fault(orig):
        def _dispatch(self, fns, s, t, outs, dix=None):
            fns = dict(fns)
            fn = fns[case]
            fns[case] = lambda *a: fn(*a) + 1.0
            return orig(self, fns, s, t, outs, dix)
        return _dispatch
    return fault


def _half_left_out(orig):
    """The second half of every planner call's answers never given."""
    def query(self, s, t, **kw):
        return orig(self, s, t, **kw)[:len(s) // 2]
    return query


#: (cell, the QueryPlanner method broken, the fault wrapped round it)
FAULTS = [(bench_tiny.OFFLINE, "_dispatch", _bucket_altered("same_frag")),
          (bench_tiny.OFFLINE, "query", _half_left_out),
          (bench_tiny.LIVE, "query", _answer_altered),
          (bench_tiny.LIVE, "_dispatch", _bucket_altered("cross_frag")),
          (bench_tiny.LIVE, "query", _half_left_out)]


@pytest.mark.parametrize("name,attr,fault", FAULTS)
def test_a_fault_under_the_timed_path_is_not_correct(tiny, name, attr,
                                                     fault, monkeypatch):
    from repro.core.dist_engine import QueryPlanner

    monkeypatch.setattr(QueryPlanner, attr,
                        fault(getattr(QueryPlanner, attr)))
    out = _run(tiny, name)
    assert out["correct"] is False
    c = out["compared"]
    assert c["mismatches"]["value"] > 0 or c["unanswered"]["value"] > 0


@pytest.mark.parametrize("seed", [3, 4, 5])
@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(tiny, name, seed):
    root, built = tiny
    r = calibrate.readings(cells.resolve(name, root), seed, 0.5, built)
    assert r["mismatches"] == 0 and r["unanswered"] == 0
    assert r["control_mismatches"] > run.LIMITS["mismatches"]


def _live_p99(tiny):
    root, built = tiny
    cell = cells.resolve(bench_tiny.LIVE, root)
    ctx = run.Context(cell=cell, seed=9, seconds=1.0, edges=built[0],
                      pool=built[1], engine=built[2], tracing=False)
    res = cell.driver.run_window(ctx, cell.driver.prepare(ctx), 1.0)
    assert res["failed"] == 0
    return window.exact_percentile(res["latency_ms"], 99)


def test_a_stall_inside_the_window_shows_in_p99(tiny, monkeypatch):
    from repro.core.dist_engine import QueryPlanner

    steady = _live_p99(tiny)
    orig = QueryPlanner.query
    calls = []

    def stalled(self, s, t, **kw):
        calls.append(1)
        if len(calls) == 3:
            time.sleep(0.4)
        return orig(self, s, t, **kw)

    monkeypatch.setattr(QueryPlanner, "query", stalled)
    # every request queued behind the stall waits for it: far more
    # than one in a hundred of the window's 200
    assert _live_p99(tiny) > max(200.0, 4 * steady)
