"""batch_fill: real rows over `max_batch` rows, over every micro-batch the
scheduler handed to the runtime in the window (the runtime wrapper counts
the calls; every completion is one real row)."""


def read(run):
    calls = len(run.step_seconds)
    return len(run.done) / (calls * run.max_batch) if calls else None
