"""entry_adc_roofline: the least time the ADC entry scoring's work needs on
this chip, over the summed device time of the entry-scoring kernel's
operations in the traced window, in %.

The work is `bench/work/pq_adc.py` at the configuration's
`n_entry_cands` and `pq_m`, for every row the kernel ran: `max_batch`
rows for each runtime call of the window, padded rows included.  The
kernel's operations are those whose HLO instruction name starts with
KERNEL: the Pallas call of `repro.kernels.pq_adc`, named after its jitted
wrapper.  Nothing is returned where the trace holds none of them.
"""
from harness.roofline import kernel_seconds, rows_run, share

KERNEL = "pq_adc_pallas"


def read(run):
    if kernel_seconds(run, KERNEL) is None or not rows_run(run):
        return None
    c = run.spec.config
    ops, nbytes = run.work("pq_adc").work(
        rows_run(run), c["engine"]["n_entry_cands"], c["build"]["pq_m"])
    return share(run, KERNEL, ops, nbytes)
