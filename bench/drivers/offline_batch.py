"""Closed loop of offline batches through ``EpochedEngine.query``.

The job is a fixed set of ``mix["job"]["batches"]`` batches of
``mix["batch"]`` pairs, drawn from the mix's own seed: the matrix a
logistics user fills.  Every seed does the same work: batches drawn
afresh per seed would each pad to other bucket sizes, and the seed
would change the work.  The run's seed draws the order of each pass
over the job, a new order for each.  Each batch is sent when the one
before it has its host result; batches are sent while the window is
open, and every batch sent is completed and counted.
``queries_per_s`` is all the queries answered over the time from the
window's start to the end of the last batch.  The check compares a
sample drawn from the seed: some answers drawn uniformly, and some from
each planner bucket that served in the window, so that rare buckets are
covered.
"""
from __future__ import annotations

import time

import numpy as np

from bench import traffic, window


def prepare(ctx) -> dict:
    """Set-up: compile every bucket size a batch can produce, and draw
    the job."""
    mix = ctx.cell.mix
    ctx.engine.warmup(int(mix["batch"]))
    rng = np.random.default_rng(int(mix["job"]["seed"]))
    job = [traffic.draw_pairs(ctx.cell, ctx.edges.n, int(mix["batch"]),
                              rng, ctx.pool)
           for _ in range(int(mix["job"]["batches"]))]
    return {"job": job, "rng": traffic.rng_for(ctx.seed, traffic.PAIRS)}


def _batches(state):
    """(index, batch) of the job's batches, pass after pass, each pass
    in a new order."""
    while True:
        for i in state["rng"].permutation(len(state["job"])):
            yield int(i), state["job"][i]


def run_window(ctx, state, seconds: float) -> dict:
    eng, b = ctx.engine, int(ctx.cell.mix["batch"])
    batches = _batches(state)
    pairs, answers, done, order = [], [], [], []
    with ctx.annotate("bench.window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            i, st = next(batches)
            order.append(i)
            with ctx.annotate("bench.offline.batch"):
                got = eng.query(st[:, 0], st[:, 1])
            done.append(time.perf_counter())
            got = np.asarray(got, np.float64).ravel()
            full = np.full(b, np.nan)       # an answer never given is NaN
            full[:min(b, got.size)] = got[:b]
            pairs.append(st)
            answers.append(full)
    st = np.concatenate(pairs)
    got = np.concatenate(answers)
    # the bucket of every answered query, for the check's sample
    bucket = eng.planner.plan(st[:, 0].astype(np.int32),
                              st[:, 1].astype(np.int32))
    return {
        "e2e": {"queries_per_s": window.closed_loop_rate(
            t0, done, [b] * len(done))},
        "pairs": st, "got": got, "attempted": int(got.size),
        "failed": int(np.sum(np.isnan(got))),
        "answered": int(np.sum(~np.isnan(got))),
        "buckets": bucket, "counters": None,
        "log": [f"{len(done)} batches of {b} in "
                f"{done[-1] - t0:.6f}s; planner buckets "
                + ", ".join(f"{k} {v.size}" for k, v in bucket.items()),
                "job batch: seconds " + ", ".join(
                    f"{i}: {t - t_prev:.4f}" for i, t, t_prev in
                    zip(order, done, [t0] + done[:-1]))],
    }


def sample(ctx, res: dict) -> np.ndarray:
    """Indices of the answers the reference checks."""
    chk = ctx.cell.mix["check"]
    rng = traffic.rng_for(ctx.seed, traffic.SAMPLE)
    total = res["got"].size
    idx = [rng.choice(total, size=min(int(chk["uniform"]), total),
                      replace=False)]
    for case in sorted(res["buckets"]):
        members = res["buckets"][case]
        k = min(int(chk["per_bucket"]), members.size)
        if k:
            idx.append(rng.choice(members, size=k, replace=False))
    return np.unique(np.concatenate(idx))
