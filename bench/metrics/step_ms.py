"""step_ms: mean host-clock time of one `serve_batch` call in the window.
The call returns host arrays, so the device has finished when it ends."""


def read(run):
    s = run.step_seconds
    return sum(s) / len(s) * 1e3 if s else None
