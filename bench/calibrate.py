"""Readings that set the limits of ``correct``: the program's and the
control's, seed by seed, at a cell's own size and load.

    python3 bench/calibrate.py --workload <cell>[,<cell>...] --seeds 1,2,3 --seconds 3

Builds each configuration's deployment once, then for each cell and
seed runs the cell's driver for a short window (every answer due in it
is drained) and compares the sampled answers with the reference: the
program's mismatches are the lower reading.  The control, the reference
computed in bfloat16, is compared on the same pairs: its mismatches are
the upper reading.  Prints one JSON line per cell and seed.  Run it on
the chip.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import cells, reference, run  # noqa: E402


def readings(cell, seed: int, seconds: float, built) -> dict:
    """One seed's lower (program) and upper (control) readings."""
    edges, pool, engine = built
    ctx = run.Context(cell=cell, seed=seed, seconds=seconds, edges=edges,
                      pool=pool, engine=engine, tracing=False)
    state = cell.driver.prepare(ctx)
    res = cell.driver.run_window(ctx, state, seconds)
    compared, pairs, want = run.check(ctx, res)
    t0 = time.perf_counter()
    uniq, inv = np.unique(pairs, axis=0, return_inverse=True)
    control = reference.Reference(edges).control_bf16(
        uniq[:, 0], uniq[:, 1])[inv.ravel()]
    return {"cell": cell.name, "seed": seed, "checked": int(pairs.shape[0]),
            "answered": res["answered"], **compared,
            "control_mismatches": reference.mismatches(control, want),
            "control_s": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    todo = [cells.resolve(w, ROOT) for w in args.workload.split(",")]
    run.find_devices(max(c.chips for c in todo), ROOT)
    run.use_compile_cache(ROOT)
    sys.path.insert(0, str(ROOT / "src"))
    built = {}
    for cell in todo:
        name = cell.config["name"]
        if name not in built:
            built[name] = run.build(cell)
        for seed in [int(s) for s in args.seeds.split(",")]:
            print(json.dumps(readings(cell, seed, args.seconds,
                                      built[name])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
