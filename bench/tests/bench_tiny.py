"""A tiny copy of the benchmark for tests on the CPU.

``make_root`` copies ``BENCHMARK.json`` and ``bench/`` into a directory
and adds one small deployment (about 1,900 nodes, a 2-level hierarchy,
16 hub nodes) with an offline and a live cell, by new files and
entries alone, as a later change would add a cell: the live cell brings
its mix, its latency metrics and the per-layer metrics whose readers
``bench/metrics/`` holds for live cells.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

OFFLINE, LIVE = "tiny.offline", "tiny.live"


def make_root(dest: Path) -> Path:
    dest = Path(dest)
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "bench/configs/road64k.json").read_text())
    cfg.update(name="tiny", nodes=2000, hierarchy_levels=2, hub_budget=16,
               max_batch=64, expect={"nodes": 1888, "edges": 2468})
    (dest / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    off = json.loads((REPO / "bench/mixes/uniform-batch1024.json")
                     .read_text())
    off.update(batch=64, job={"batches": 16, "seed": 7},
               check={"uniform": 24, "per_bucket": 8})
    (dest / "bench/mixes/tiny-offline.json").write_text(json.dumps(off))
    live = {"driver": "open_loop", "pairs": {"kind": "uniform"},
            "rate_qps": 200, "drain_s": 30,
            "check": {"uniform": 800, "per_tier": 24}}
    (dest / "bench/mixes/tiny-live.json").write_text(json.dumps(live))
    driver = {w["name"]: json.loads(
        (REPO / "bench/mixes" / f"{w['traffic']}.json").read_text()
        )["driver"] for w in spec["workloads"]}
    spec["configs"].append({"name": "tiny", "source": "tests",
                            "file": "bench/configs/tiny.json",
                            "reduced": ["nodes"], "why": "tests"})
    spec["workloads"] += [
        {"name": OFFLINE, "config": "tiny", "traffic": "tiny-offline",
         "chips": 1, "why": "tests"},
        {"name": LIVE, "config": "tiny", "traffic": "tiny-live",
         "chips": 1, "why": "tests"}]
    # a metric kept to some cells applies to the tiny cell of its kind
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kinds = {driver[w] for w in m["workloads"]}
            m["workloads"].append(
                OFFLINE if "offline_batch" in kinds else LIVE)
    have = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    spec["end_to_end"] += [
        {"name": name, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": [LIVE]}
        for name in ("p99_ms", "p50_ms") if name not in have]
    spec["per_layer"] += [
        {"name": name, "unit": unit, "better": better,
         "source": "program_counter", "layer": "tests", "moves": "p99_ms",
         "workloads": [LIVE]}
        for name, unit, better in (
            ("device_idle_frac.live", "fraction", "lower"),
            ("planner_us_per_query.live", "us/query", "lower"),
            ("batch_occupancy.live", "fraction", "higher"))
        if name not in have]
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dest
