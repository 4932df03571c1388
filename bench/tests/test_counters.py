"""The readers of the program's own counters, `queue_wait_ms` and
`hop_useful_share`: on completions worked out by hand, on completions of a
program that carries no counters, and on the tiny run's own completions."""
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import BENCH
from harness import cell
from harness.modules import load_module


def _read(metric, done):
    run = SimpleNamespace(done=done)
    return load_module(BENCH / "metrics" / f"{metric}.py").read(run)


def test_readers_hand_worked():
    # queued 0, 10, ..., 190 ms: the 95th percentile by linear
    # interpolation is 19 x 0.95 = 18.05 steps of 10 ms
    done = [SimpleNamespace(queued=i * 0.01, hops=h, hops_run=256)
            for i, h in enumerate([256, 128] * 10)]
    assert _read("queue_wait_ms", done) == pytest.approx(180.5)
    # (10 x 256 + 10 x 128) / (20 x 256)
    assert _read("hop_useful_share", done) == pytest.approx(0.75)


def test_readers_without_counters_return_nothing():
    # a program whose completions carry none of the counters, and one
    # whose runtime reported no hops
    bare = [SimpleNamespace(latency=0.1)] * 4
    no_hops = [SimpleNamespace(latency=0.1, queued=0.05, hops=None,
                               hops_run=None)] * 4
    for metric in ("queue_wait_ms", "hop_useful_share"):
        assert _read(metric, bare) is None
    assert _read("hop_useful_share", no_hops) is None
    assert _read("queue_wait_ms", no_hops) == pytest.approx(50.0)


def test_readers_on_the_tiny_run(run_tiny, monkeypatch):
    kept = []

    class Kept(cell.Record):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            kept.append(self)
    monkeypatch.setattr(cell, "Record", Kept)
    r = run_tiny(seed=7, trace=True)
    (rec,) = kept
    queued = np.array([c.queued for c in rec.done])
    hops = np.array([c.hops for c in rec.done])
    run = np.array([c.hops_run for c in rec.done])
    assert (queued >= 0).all()
    assert all(c.latency - c.queued > 0 for c in rec.done)
    assert ((hops >= 0) & (hops <= run)).all() and (hops > 0).any()
    m = r["metrics"]
    # no TPU plane in a CPU trace: the device metrics are left out, and
    # the program's counters and spans are read
    assert set(m) == {"batch_fill", "step_ms", "queue_wait_ms",
                      "hop_useful_share", "host_step_ms"}
    assert 0 < m["hop_useful_share"]["value"] <= 1
    assert m["host_step_ms"]["value"] > 0
    assert m["queue_wait_ms"]["value"] == pytest.approx(
        np.percentile(queued, 95) * 1e3)
    assert m["hop_useful_share"]["value"] == pytest.approx(
        hops.sum() / run.sum())
