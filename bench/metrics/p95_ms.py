"""p95_ms: 95th percentile of the latency of every request of the window,
timed by the scheduler from the request's due time."""
import numpy as np


def read(run):
    if not run.done:
        return None
    return float(np.percentile([c.latency for c in run.done], 95)) * 1e3
