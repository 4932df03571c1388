"""The trace reduction and the device metrics' readers on a small recorded
trace whose numbers are worked out by hand.

The trace has the layout a TPU run writes: a `/device:TPU:0` plane whose
`XLA Ops` line holds the operations (its `XLA Modules` line must be
ignored), and a host plane with the benchmark's spans.  Times in ns:

    host   bench.window        [  50, 1050)
           bench.serve_batch   [  60,  600)   [ 700, 1000)
    device hop-loop kernel     [ 100,  400)
           fusion              [ 450,  500)
           copy                [ 480,  520)   (overlaps the fusion)
           fusion              [5000, 5100)   (after the window)

Busy is [100, 400) + [450, 520) = 370 ns of a 1000 ns window; the idle
gaps are [520, 1050), [50, 100) and [400, 450).
"""
import dataclasses
from types import SimpleNamespace

import pytest

from conftest import BENCH
from harness import trace as tr
from harness.cell import Record
from harness.modules import load_module


def _event(meta: int, start_ns: int, end_ns: int) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {(end_ns - start_ns) * 1000} }}")


# a TPU trace names each operation by its whole HLO text
HLO = {1: "%beam_hops_adc_stream.1 = (s32[64,256]{1,0}) custom-call(f32[65536,128]{1,0} %pad.9)",
       2: "%fusion.3 = f32[64,256]{1,0} fusion(f32[64,256]{1,0} %p), kind=kLoop",
       3: "%copy.1 = f32[65536,32]{1,0} copy(f32[65536,32]{0,1} %c)"}


RECORDED = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_event(9, 0, 6000)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_event(1, 100, 400)}
    {_event(2, 450, 500)}
    {_event(3, 480, 520)}
    {_event(2, 5000, 5100)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{HLO[1]}" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "{HLO[2]}" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "{HLO[3]}" }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "jit_batched_search(1)" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_event(1, 50, 1050)}
    {_event(2, 60, 600)}
    {_event(2, 700, 1000)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{tr.WINDOW_SPAN}" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "{tr.STEP_SPAN}" }} }}
}}
"""


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return tr.from_profile(ProfileData.from_text_proto(RECORDED))


def test_window_busy_and_kernel_time(recorded):
    assert recorded.window == (50.0, 1050.0)
    assert recorded.window_s == pytest.approx(1000e-9)
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert len(recorded.window_ops()) == 3
    assert recorded.busy_s() == pytest.approx(370e-9)
    assert recorded.kernel_s(
        lambda e: tr.op_label(e) == "beam_hops_adc_stream.1") == pytest.approx(
        300e-9)


def test_breakdown(recorded):
    b = recorded.breakdown()
    assert [n for n, _ in b["device_ops"]] == ["beam_hops_adc_stream.1",
                                              "fusion.3", "copy.1"]
    assert [s for _, s in b["device_ops"]] == pytest.approx(
        [300e-9, 50e-9, 40e-9])
    assert [g for _, g in b["idle_gaps"]] == pytest.approx(
        [530e-9, 50e-9, 50e-9])
    assert [n for n, _ in b["idle_gaps"]] == [tr.STEP_SPAN] * 3


def test_one_window_span_required():
    from jax.profiler import ProfileData
    with pytest.raises(ValueError, match="spans, expected 1"):
        tr.from_profile(ProfileData.from_text_proto(
            RECORDED.replace(f'"{tr.WINDOW_SPAN}"', '"other"')))


def _record(trace, rows=640, kind="TPU v5 lite"):
    import json
    from harness.cell import CellSpec
    config = json.loads((BENCH / "configs" / "sift1m.json").read_text())
    spec = CellSpec("sift1m.steady", {}, config, b"", 1, [],
                    [])
    return Record(spec, BENCH, kind, 1.0, 1.0,
                  [SimpleNamespace(latency=0.1)] * rows, 1.0, [0.4] * 10,
                  trace)


def _read(metric, run):
    return load_module(BENCH / "metrics" / f"{metric}.py").read(run)


def test_device_metric_readers(recorded):
    run = _record(recorded)
    assert _read("device_idle_share", run) == pytest.approx(0.63)
    # 640 rows x 256 hops at R=32, M=64: bytes 640*256*(128+2048) +
    # 640*64*256*4 = 398,458,880; at 819 GB/s that takes 486.5 us, over
    # 300 ns of kernel time
    want = 100 * (398_458_880 / 819e9) / 300e-9
    assert _read("hop_loop_roofline", run) == pytest.approx(want)
    assert _read("batch_fill", run) == pytest.approx(1.0)
    assert _read("step_ms", run) == pytest.approx(400.0)


def test_readers_return_nothing_without_a_device(recorded):
    bare = dataclasses.replace(recorded, ops={})
    for metric in ("device_idle_share", "hop_loop_roofline"):
        assert _read(metric, _record(None)) is None
        assert _read(metric, _record(bare)) is None


def test_unknown_device_kind_is_an_error(recorded):
    with pytest.raises(KeyError, match="no peaks"):
        _read("hop_loop_roofline", _record(recorded, kind="TPU v9"))


def test_work_hand_worked_case():
    work = load_module(BENCH / "work" / "beam_hops.py").work
    # one SIFT step: 64 queries x 256 hops at R=32, M=64 (see the module)
    assert work(64, 256, 32, 64) == (33_554_432, 39_845_888)
