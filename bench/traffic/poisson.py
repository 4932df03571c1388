"""poisson: open-loop arrivals at `rate_qps`, Poisson-shaped.

Every seed gets the same work: `round(rate_qps * seconds)` requests whose
inter-arrival gaps are the same set of exponential quantiles, scaled to
span exactly `seconds`, in an order the seed shuffles.  So the length and
load of the timeline never depend on the seed; only the order of the gaps
and which held-out query each request asks vary.  The window drives the
whole timeline through `Scheduler.run`, whose clock skips idle gaps.
"""
import numpy as np


def plan(params: dict, seconds: float, n_queries: int, rng):
    """(arrival seconds (N,), held-out query index (N,))."""
    n = max(1, round(params["rate_qps"] * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)   # Exp(1) quantiles
    gaps *= seconds / gaps.sum()
    return np.cumsum(rng.permutation(gaps)), rng.integers(0, n_queries, n)


def drive(sched, requests):
    """(completions, requests offered): the open loop, every request."""
    return sched.run(requests, warmup=False), len(requests)
