"""hop_loop_roofline: the least time the window's hop-loop work needs on
this chip, over the summed device time of the hop-loop kernel's operations
in the traced window, in %.

The work is `bench/work/beam_hops.py` for every real request of the window
(padded rows excluded) at the configuration's hop budget `max_hops`: the
share is against the hop budget, not the hops a query used.  Today's
kernel runs every hop of the budget (a `fori_loop` over `max_hops`), so the
two agree; a change that stops a query early would raise the share without
the kernel being any more efficient per hop, until the program reports the
hops each query used.  The least time is the larger of the operations over
the peak FLOP/s and the bytes over the peak HBM bandwidth of
`bench/peaks.json` (memory sets it on every cell here).  The kernel's
operations are those whose HLO instruction name starts with KERNEL: the
Pallas calls of `repro.kernels.beam_fused`, named after their jitted
wrappers `beam_hops_adc_stream` (the streamed kernel, as a TPU v5e trace
shows it: `%beam_hops_adc_stream.1 = ... custom-call`) and
`beam_hops_adc_pallas` (the VMEM-resident one).  Nothing is returned where
the trace holds none of them.
"""
from harness.trace import op_label

KERNEL = "beam_hops_adc"


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    kernel_s = tr.kernel_s(lambda e: op_label(e).startswith(KERNEL))
    if kernel_s <= 0:
        return None
    c = run.spec.config
    ops, nbytes = run.work("beam_hops").work(
        len(run.done), c["engine"]["max_hops"], c["build"]["r"],
        c["build"]["pq_m"])
    peaks = run.peaks()
    least = max(ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
