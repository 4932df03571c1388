"""queue_wait_ms: 95th percentile of the time each request of the window
waited before its batch was dispatched, from its due time, on the
scheduler's own clock: the program's `Completion.queued`.  Its batch's
service makes up the rest of its latency.  Nothing where the program's
completions carry no `queued`."""
import numpy as np


def read(run):
    queued = [c.queued for c in run.done
              if getattr(c, "queued", None) is not None]
    return float(np.percentile(queued, 95)) * 1e3 if queued else None
