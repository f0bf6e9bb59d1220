"""Window and latency arithmetic, shared by every driver.

Open loop: latency runs from each request's scheduled arrival to its
response, so a stall charges every request queued behind it; the
percentiles are exact over all requests scheduled in the window, not
read from a histogram.  Closed loop: the rate is all the work answered
over all the time from the window's start to the end of the last batch.
"""
from __future__ import annotations

import numpy as np


def exact_percentile(values, q: float) -> float:
    """The ``q``-th percentile by nearest rank: the smallest value with
    at least ``q`` percent of all values at or below it."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        raise ValueError("no values")
    return float(np.percentile(v, q, method="inverted_cdf"))


def closed_loop_rate(t0: float, done_times, sizes) -> float:
    """Items per second answered by a closed loop that started at
    ``t0``: every completed batch's items over the time to the end of
    the last one."""
    done = np.asarray(done_times, np.float64)
    if done.size == 0:
        raise ValueError("no batch completed")
    return float(np.sum(sizes)) / float(done.max() - t0)


def uniform_arrivals(rate_qps: float, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets of a Poisson process at ``rate_qps`` over
    ``[0, seconds)``, conditioned on its mean count: the count is
    ``round(rate * seconds)`` for every seed, and given the count a
    Poisson process's arrivals are sorted uniform draws."""
    n = max(1, int(round(rate_qps * seconds)))
    return np.sort(rng.uniform(0.0, seconds, n))


def latency_ms(t_sched, t_done) -> np.ndarray:
    """Per-request latency from scheduled arrival to response, ms."""
    return (np.asarray(t_done, np.float64)
            - np.asarray(t_sched, np.float64)) * 1e3


def lateness_p99_ms(t_sched, t_submit) -> float:
    """How late the generator submitted: p99 of submit minus schedule."""
    return exact_percentile(latency_ms(t_sched, t_submit), 99)
