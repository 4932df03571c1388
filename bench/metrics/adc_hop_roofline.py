"""adc_hop_roofline: the least time the hop loop's work needs on this chip,
over the summed device time of the hop-loop kernel's operations in the
traced window, in %.

The work is `bench/work/beam_hops.py` at the configuration's `r`, `pq_m`
and hop budget `max_hops`, for every row the kernel ran: `max_batch` rows
for each runtime call of the window, padded rows included, so the share
follows the kernel and not how full the batches were.  The kernel's
operations are those whose HLO instruction name starts with KERNEL: the
Pallas calls of `repro.kernels.beam_fused`, named after their jitted
wrappers (`beam_hops_adc_stream`, `beam_hops_adc_pallas`).  Nothing is
returned where the trace holds none of them.
"""
from harness.roofline import kernel_seconds, rows_run, share

KERNEL = "beam_hops_adc"


def read(run):
    if kernel_seconds(run, KERNEL) is None or not rows_run(run):
        return None
    c = run.spec.config
    ops, nbytes = run.work("beam_hops").work(
        rows_run(run), c["engine"]["max_hops"], c["build"]["r"],
        c["build"]["pq_m"])
    return share(run, KERNEL, ops, nbytes)
