"""The reader of the program's own host spans, `host_step_ms`, on a small
recorded trace whose numbers are worked out by hand.

The trace has the layout a TPU run writes: a `/device:TPU:0` plane whose
`XLA Ops` line holds the operations, and a host plane with the
benchmark's spans and the program's.  Times in ns:

    host   bench.window        [  50, 1050)
           bench.serve_batch   [  60,  600)   [ 700, 1000)
           bamg.round          [  55,  650)   [ 690, 1020)   [1100, 1200)
           bamg.device_wait    [  90,  410)   [ 720,  760)   [1110, 1150)
    device hop-loop kernel     [ 100,  400)
           fusion              [ 450,  500)

The rounds inside the window less their device waits take 595 - 320 =
275 ns and 330 - 40 = 290 ns, so `host_step_ms` is 282.5 ns; the third
round ends after the window and is left out.  Busy is [100, 400) +
[450, 500) = 350 ns of the 1000 ns window: the program's spans change no
device metric.
"""
import dataclasses
import json
from types import SimpleNamespace

import pytest

from conftest import BENCH
from harness import trace as tr
from harness.cell import CellSpec, Record
from harness.modules import load_module


def _event(meta: int, start_ns: int, end_ns: int) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {(end_ns - start_ns) * 1000} }}")


RECORDED = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_event(1, 100, 400)}
    {_event(2, 450, 500)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%beam_hops_adc_stream.1 = (s32[64,256]{{1,0}}) custom-call(f32[65536,128]{{1,0}} %pad.9)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.3 = f32[64,256]{{1,0}} fusion(f32[64,256]{{1,0}} %p), kind=kLoop" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_event(1, 50, 1050)}
    {_event(2, 60, 600)}
    {_event(2, 700, 1000)}
    {_event(3, 55, 650)}
    {_event(4, 90, 410)}
    {_event(3, 690, 1020)}
    {_event(4, 720, 760)}
    {_event(3, 1100, 1200)}
    {_event(4, 1110, 1150)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{tr.WINDOW_SPAN}" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "{tr.STEP_SPAN}" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "bamg.round" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "bamg.device_wait" }} }}
}}
"""


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return tr.from_profile(ProfileData.from_text_proto(RECORDED))


def _record(trace):
    config = json.loads((BENCH / "configs" / "sift1m.json").read_text())
    spec = CellSpec("sift1m.steady", {}, config, b"", 1, [], [])
    return Record(spec, BENCH, "TPU v5 lite", 1.0, 1.0,
                  [SimpleNamespace(latency=0.1)] * 640, 1.0, [0.4] * 10,
                  trace)


def _read(metric, run):
    return load_module(BENCH / "metrics" / f"{metric}.py").read(run)


def test_host_step_ms_reads_the_program_spans(recorded):
    assert _read("host_step_ms", _record(recorded)) == pytest.approx(
        282.5e-6)


def test_host_step_ms_without_program_spans_is_nothing(recorded):
    # a program without spans, or no trace: nothing, and no error
    bare = dataclasses.replace(recorded, spans=[
        e for e in recorded.spans if not e.name.startswith("bamg.")])
    assert _read("host_step_ms", _record(bare)) is None
    assert _read("host_step_ms", _record(None)) is None


def test_program_spans_leave_the_device_metrics_alone(recorded):
    assert recorded.busy_s() == pytest.approx(350e-9)
    assert _read("device_idle_share", _record(recorded)) == pytest.approx(
        0.65)
