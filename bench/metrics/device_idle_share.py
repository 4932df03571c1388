"""device_idle_share: 1 - (union of the device's operation intervals over
the traced window), from the profiler trace; nothing without a device
plane in the trace."""


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    return 1.0 - tr.busy_s() / tr.window_s
