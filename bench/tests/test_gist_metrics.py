"""The gist1m cell's files and its two per-layer readers, on a trace built
by hand.

The trace's window is [0, 2 s).  Device operations, in ns:

    beam_hops_adc_stream.1   [0, 1.0e9)            1 s
    pq_adc_pallas.1          [1.0e9, 1.004e9)      4 ms
    fusion.7                 [1.004e9, 1.005e9)    1 ms

and the window had 10 runtime calls of 64 rows: 640 rows run.
"""
import json
from types import SimpleNamespace

import pytest

from conftest import BENCH, ROOT
from harness import trace as tr
from harness.cell import CellSpec, Record, load_spec
from harness.modules import load_module

CALLS = 10

OPS = [tr.Event("%beam_hops_adc_stream.1 = (s32[64,256]{1,0}) custom-call()",
                0.0, 1.0e9),
       tr.Event("%pq_adc_pallas.1 = f32[64,1024]{1,0} custom-call()",
                1.0e9, 1.004e9),
       tr.Event("%fusion.7 = f32[64,256]{1,0} fusion()", 1.004e9, 1.005e9)]


def _trace(ops=OPS):
    return tr.Trace(window=(0.0, 2.0e9), ops={"/device:TPU:0": list(ops)},
                    spans=[])


def _record(trace, real_rows=100):
    config = json.loads((BENCH / "configs" / "gist1m.json").read_text())
    spec = CellSpec("gist1m.steady", {}, config, b"", 1, [], [])
    return Record(spec, BENCH, "TPU v5 lite", 1.0, 2.0,
                  [SimpleNamespace(latency=0.1)] * real_rows, 1.0,
                  [0.15] * CALLS, trace)


def _read(metric, run):
    return load_module(BENCH / "metrics" / f"{metric}.py").read(run)


def test_adc_hop_roofline_counts_every_row_run():
    # 640 rows x 256 hops at R=32, M=240: bytes 163,840 * (128 + 7,680) +
    # 640 * 240 * 256 * 4 = 1,436,549,120; at 819 GB/s 1.754 ms, over 1 s
    # of kernel time.  Real rows (100 here) do not enter.
    want = 100 * (1_436_549_120 / 819e9) / 1.0
    assert _read("adc_hop_roofline", _record(_trace())) == pytest.approx(want)
    assert _read("adc_hop_roofline", _record(_trace(), real_rows=640)) == \
        pytest.approx(want)


def test_entry_adc_roofline_counts_every_row_run():
    # 640 rows x 1,024 candidates at M=240: bytes 1,024 * 240 + 640 * 240 *
    # 256 * 4 + 640 * 1,024 * 4 = 160,153,600; at 819 GB/s 195.5 us, over
    # 4 ms of kernel time
    want = 100 * (160_153_600 / 819e9) / 4e-3
    assert _read("entry_adc_roofline", _record(_trace())) == \
        pytest.approx(want)


@pytest.mark.parametrize("metric", ("adc_hop_roofline", "entry_adc_roofline"))
def test_readers_return_nothing_without_their_kernel(metric):
    assert _read(metric, _record(None)) is None
    assert _read(metric, _record(_trace(ops=[]))) is None
    assert _read(metric, _record(_trace(ops=OPS[2:]))) is None


def test_pq_adc_work_hand_worked_case():
    work = load_module(BENCH / "work" / "pq_adc.py").work
    # one GIST step: 64 rows x 1,024 candidates at M=240 (see the module)
    assert work(64, 1024, 240) == (15_728_640, 16_236_544)


def test_gist_cell_spec():
    """`gist1m.steady` runs the gist1m configuration on one chip, reports
    every end-to-end metric, and of the per-layer ones exactly the two
    that read its ADC kernels."""
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = load_spec(BENCH, benchmark, "gist1m.steady")
    assert spec.chips == 1
    assert spec.config["name"] == "gist1m"
    assert (spec.config["d"], spec.config["build"]["pq_m"]) == (960, 240)
    assert spec.workload["config"] == "gist1m"
    assert isinstance(spec.workload["traffic"]["rate_qps"], float)
    assert [m["name"] for m in spec.end_to_end] == [
        m["name"] for m in benchmark["end_to_end"]]
    assert sorted(m["name"] for m in spec.per_layer) == [
        "adc_hop_roofline", "entry_adc_roofline"]
