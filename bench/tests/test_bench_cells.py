"""Every cell finds its parts by name, a new cell needs only new files
and entries, and ``BENCHMARK.json`` keeps to the benchmark's contract."""
import hashlib
import json
import re

import bench_tiny
import pytest

from bench import cells
from bench.run import View

SPEC = json.loads((bench_tiny.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(name):
    cell = cells.resolve(name)
    assert cell.chips == 1
    assert callable(cell.driver.prepare) and callable(cell.driver.run_window)
    assert callable(cell.driver.sample) and callable(cell.pairs.draw)
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    # a reader that finds nothing to read returns nothing
    empty = View(trace=None, counters=None, answered=0)
    for _, reader in cell.per_layer:
        assert reader.read(empty) is None


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    before = {p: d for p, d in _digests(bench_tiny.REPO).items()
              if "tests" not in p.parts and "__pycache__" not in p.parts}
    after = _digests(root)
    assert all(after[p] == d for p, d in before.items())
    assert set(after) - set(before) == {
        p.relative_to(root) for p in (
            root / "bench/configs/tiny.json",
            root / "bench/mixes/tiny-offline.json",
            root / "bench/mixes/tiny-live.json")}
    for name in (bench_tiny.OFFLINE, bench_tiny.LIVE):
        cell = cells.resolve(name, root)
        assert cell.config["nodes"] == 2000
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer


def test_benchmark_json_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(SPEC)) <= 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    used = {w["config"] for w in SPEC["workloads"]}
    assert {c["name"] for c in SPEC["configs"]} == used
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        cfg = json.loads((bench_tiny.REPO / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads",
                                                    m["workloads"]))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    texts = [c[k] for c in SPEC["configs"] for k in ("source", "why")] + [
        w["why"] for w in SPEC["workloads"]] + [
        m["layer"] for m in SPEC["per_layer"]]
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
