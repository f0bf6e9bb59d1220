"""Find every part of a cell by its name in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration is
the JSON file its entry names; the mix is ``bench/mixes/<traffic>.json``,
which names its driver (``bench/drivers/<driver>.py``) and its pair
generator (``bench/pairs/<kind>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a configuration, a mix, a driver,
a pair kind or a metric is adding files and entries: nothing here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[1]


def load_module(path: Path) -> ModuleType:
    """Import one file as a module of its own (names may hold dots)."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "bench_part_" + re.sub(r"\W", "_", str(path.with_suffix("")))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    driver: ModuleType
    pairs: ModuleType
    end_to_end: list        # the BENCHMARK.json entries this cell reports
    per_layer: list         # (entry, reader module) pairs
    root: Path


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with every part loaded, or an error naming
    the part that is missing."""
    root = Path(root)
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    bench = root / "bench"
    with open(bench / "mixes" / f"{w['traffic']}.json") as f:
        mix = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=config, mix=mix,
        driver=load_module(bench / "drivers" / f"{mix['driver']}.py"),
        pairs=load_module(bench / "pairs" / f"{mix['pairs']['kind']}.py"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[(m, load_module(bench / "metrics" / f"{m['name']}.py"))
                   for m in spec["per_layer"] if _applies(m, name)],
        root=root)
