"""host_step_ms: mean host time of one scheduler round that the device
waits through, from the program's own spans in the traced window: each
`bamg.round` span (admission, batching, the runtime call, completions)
less the part of it that its `bamg.device_wait` spans cover.  The spans
are on the profiler's clock, the device operations' own.  Nothing where
the trace holds no round span."""

ROUND = "bamg.round"
DEVICE_WAIT = "bamg.device_wait"


def read(run):
    tr = run.trace
    if tr is None:
        return None
    lo, hi = tr.window
    rounds = [e for e in tr.spans
              if e.name == ROUND and lo <= e.start and e.end <= hi]
    if not rounds:
        return None
    waits = [e for e in tr.spans if e.name == DEVICE_WAIT]
    self_ns = [r.end - r.start
               - sum(max(0.0, min(w.end, r.end) - max(w.start, r.start))
                     for w in waits)
               for r in rounds]
    return sum(self_ns) / len(self_ns) * 1e-6
