"""Reduction of a profiler trace to the numbers the benchmark reports.

The traced run records its window with `jax.profiler` and marks it with
host spans of its own (`jax.profiler.TraceAnnotation`): `bench.window`
around `Scheduler.run`, `bench.serve_batch` around each runtime call.
From the `.xplane.pb` file this module takes:

- device operations: the events of each TPU plane's `XLA Ops` line, named
  by their HLO instruction (`op_label`);
- host spans: the events of every host plane.

Busy time is the union of a device's operation intervals inside the
window, averaged over the devices used; the idle share is 1 - busy over
the window.  Kernel time is the summed duration of the operations a
matcher accepts.  Timestamps are the profiler's, in ns, on one clock for
host and device.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "bench.window"
STEP_SPAN = "bench.serve_batch"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start: float        # ns
    end: float          # ns


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]            # ns, the `bench.window` span
    ops: dict[str, list[Event]]            # device plane -> its operations
    spans: list[Event]                     # host events

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def window_ops(self) -> list[Event]:
        """Every device operation that overlaps the window, clipped to it."""
        lo, hi = self.window
        return [dataclasses.replace(e, start=max(e.start, lo),
                                    end=min(e.end, hi))
                for evs in self.ops.values() for e in evs
                if e.end > lo and e.start < hi]

    def busy_s(self) -> float:
        """Union of operation intervals inside the window, averaged over
        the device planes that ran any operation."""
        lo, hi = self.window
        per = [_union(_clip(evs, lo, hi)) for evs in self.ops.values()]
        per = [b for b in per if b > 0]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def kernel_s(self, match) -> float:
        """Summed device seconds of the window's operations `match` accepts."""
        return sum(e.end - e.start for e in self.window_ops()
                   if match(e)) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most device time, and the longest idle
        gaps of the first device, each named by the innermost host span
        that covers its middle."""
        by_name: dict[str, float] = defaultdict(float)
        for e in self.window_ops():
            by_name[op_label(e)] += (e.end - e.start) * 1e-9
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        lo, hi = self.window
        first = sorted(self.ops)[0] if self.ops else None
        gaps = _gaps(_clip(self.ops.get(first, []), lo, hi), lo, hi)
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[self.host_activity((a + b) / 2), (b - a) * 1e-9]
                              for a, b in gaps]}

    def host_activity(self, t: float) -> str:
        covering = [e for e in self.spans if e.start <= t <= e.end]
        if not covering:
            return "no host span"
        return min(covering, key=lambda e: e.end - e.start).name


def op_label(e: Event) -> str:
    """An operation's HLO instruction name: a TPU trace names each event
    by its whole HLO text, `%beam_hops_adc_stream.1 = (...) custom-call(...)`."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def _clip(evs, lo, hi):
    return [(max(e.start, lo), min(e.end, hi)) for e in evs
            if e.end > lo and e.start < hi]


def _merged(iv):
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _union(iv) -> float:
    return sum(b - a for a, b in _merged(iv))


def _gaps(iv, lo, hi):
    gaps, t = [], lo
    for a, b in _merged(iv):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def from_profile(pd) -> Trace:
    """A `Trace` from a `jax.profiler.ProfileData`."""
    ops: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            evs = [Event(e.name, e.start_ns, e.end_ns)
                   for e in line.events]
            if device:
                ops.setdefault(plane.name, []).extend(evs)
            elif not plane.name.startswith("/device:"):
                spans.extend(evs)
    windows = [e for e in spans if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} {WINDOW_SPAN!r} "
                         f"spans, expected 1")
    return Trace(window=(windows[0].start, windows[0].end), ops=ops,
                 spans=spans)


def load(trace_dir: Path) -> Trace:
    """The trace `jax.profiler` wrote under `trace_dir`."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"{len(files)} .xplane.pb files under {trace_dir}")
    return from_profile(ProfileData.from_file(str(files[0])))
