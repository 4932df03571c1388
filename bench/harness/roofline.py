"""A kernel's share of its roofline in a traced window: the least time the
work needs on this chip (the larger of its operations over the peak FLOP/s
and its bytes over the peak HBM bandwidth of `bench/peaks.json`) over the
summed device time of the kernel's operations, in %."""
from __future__ import annotations

from .trace import op_label


def kernel_seconds(run, prefix: str):
    """Device seconds of the window's operations whose HLO instruction
    name starts with `prefix`; None without a trace or such operations."""
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    s = tr.kernel_s(lambda e: op_label(e).startswith(prefix))
    return s if s > 0 else None


def rows_run(run) -> int:
    """Query rows the program ran in the window: `max_batch` for every
    runtime call, the padded rows included."""
    return run.max_batch * len(run.step_seconds)


def share(run, prefix: str, ops: float, nbytes: float):
    """100 x least time of (ops, nbytes) over the kernel's device time."""
    kernel_s = kernel_seconds(run, prefix)
    if kernel_s is None:
        return None
    peaks = run.peaks()
    least = max(ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
