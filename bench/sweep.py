"""Find an open-loop cell's knee: the highest offered rate at which no
backlog builds up over the window.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 500,1000,2000,4000

Builds the cell's deployment once, then runs the cell's driver at each
rate with a fresh runtime (empty cache) and prints one JSON line per
rate: latency percentiles, how long the drain ran past the window's
close, and whether latency grew from the window's first fifth to its
last, and the generator's lateness.  Run it on the chip; it writes
nothing.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import cells, run, window  # noqa: E402


def one_rate(cell, rate, args, edges, pool, engine):
    cell = dataclasses.replace(cell, mix=dict(cell.mix, rate_qps=rate))
    ctx = run.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                      edges=edges, pool=pool, engine=engine,
                      tracing=False)
    state = cell.driver.prepare(ctx)
    return cell.driver.run_window(ctx, state, args.seconds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload, ROOT)
    run.find_devices(cell.chips, ROOT)
    run.use_compile_cache(ROOT)
    sys.path.insert(0, str(ROOT / "src"))
    edges, pool, engine = run.build(cell)
    run.log(f"build done at {time.perf_counter() - T_START:.3f}s")
    for rate in [float(r) for r in args.rates.split(",")]:
        res = one_rate(cell, rate, args, edges, pool, engine)
        lat = np.asarray(res["latency_ms"])        # in arrival order
        k = max(1, lat.size // 5)
        print(json.dumps({
            "rate_qps": rate, "requests": res["attempted"],
            "failed": res["failed"], **res["e2e"],
            "p99_first_fifth_ms": window.exact_percentile(lat[:k], 99),
            "p99_last_fifth_ms": window.exact_percentile(lat[-k:], 99),
            "drain_past_close_s": res["drain_past_close_s"],
            "lateness_p99_ms": res["lateness_p99_ms"],
            "log": res["log"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
