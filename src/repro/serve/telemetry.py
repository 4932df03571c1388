"""Host spans of the serve path, on the profiler's clock.

`span(name, **args)` is `jax.profiler.TraceAnnotation`: while a profiler
session records (`jax.profiler.start_trace`), each span becomes an event
of the profiler's host plane, on the same clock as the device operations,
held in memory and written out at `stop_trace`.  With no session
recording a span costs about a microsecond and records nothing, so there
is no switch: a run that is not traced is the run with tracing off.

The serve path's spans, outermost first:

    bamg.round        Scheduler.run: one formation round (admission,
                      batching, every tier's runtime call, completions);
                      arg `round`, the id its `Completion`s carry
    bamg.step         ServeRuntime.serve_batch: one batch through
                      SCATTER / RUN / GATHER / MERGE
    bamg.device_wait  BatchedANNEngine.search_batch: waiting for the
                      jitted search to finish on the device
    bamg.fetch        BatchedANNEngine.search_batch: the one copy of ids,
                      distances and hops to the host

A round's self time (its duration less its `bamg.device_wait`) is the
host work the device waits through.
"""
from __future__ import annotations

import jax

ROUND = "bamg.round"
STEP = "bamg.step"
DEVICE_WAIT = "bamg.device_wait"
FETCH = "bamg.fetch"


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span `name` with keyword `args` as its event's stats."""
    return jax.profiler.TraceAnnotation(name, **args)
