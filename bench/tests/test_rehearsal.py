"""CPU rehearsal of a whole run: the tiny configuration through the same
set-up, window, comparison and reduction a chip run takes.  A CPU run
prints no result line and writes no device metric."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT, SECONDS

END_TO_END = {"p95_ms", "recall_at_10", "setup_s"}


def test_untraced_run_is_correct_and_reports_end_to_end(run_tiny):
    r = run_tiny(trace=False)
    assert r["correct"] is True
    assert set(r["metrics"]) == END_TO_END
    assert r["attempted"] == round(200.0 * SECONDS) and r["failed"] == 0
    assert "breakdown" not in r
    assert list(r)[-1] == "checks"
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_traced_run_reports_per_layer_but_no_device_metric(run_tiny):
    r = run_tiny(trace=True)
    assert r["correct"] is True
    # no TPU plane in a CPU trace: the device metrics are left out
    assert set(r["metrics"]) == {"batch_fill", "step_ms"}
    assert 0 < r["metrics"]["batch_fill"]["value"] <= 1
    assert r["device"]["window_s"] > 0 and r["device"]["busy_s"] == 0
    assert list(r)[-1] == "checks"


def _setup_line(lines):
    """(set-up seconds, setup_s, index seconds) of a run's `set-up:` line."""
    (ln,) = [ln for ln in lines if ln.startswith("set-up:")]
    return tuple(float(v) for v in re.findall(r"([0-9.]+) s", ln))


def test_second_run_loads_the_index(run_tiny, index_cache, tiny_spec):
    built, loaded = [], []
    r1 = run_tiny(seed=19, log=built.append)
    r2 = run_tiny(seed=19, log=loaded.append)
    assert any(ln.startswith("index: built") for ln in built)
    assert any(ln.startswith("index: cached") for ln in loaded)
    assert any(ln.endswith("compilations in the window: 0") for ln in loaded)
    # setup_s leaves the index step out, whether it built or loaded
    (t1, s1, i1), (t2, s2, i2) = _setup_line(built), _setup_line(loaded)
    assert i1 > 10 * i2
    assert s1 == pytest.approx(t1 - i1, abs=2e-3)
    assert s2 == pytest.approx(t2 - i2, abs=2e-3)
    assert r1["metrics"]["setup_s"]["value"] == pytest.approx(s1, abs=1e-3)
    # every seed is served at the configured adjacency width
    for path in index_cache.glob("*.npz"):
        with np.load(path) as z:
            assert z["adj"].shape[1] == tiny_spec.config["build"]["r"]


def test_seed_fixes_the_inputs_and_never_the_work(tiny_spec):
    from harness.data import make_corpus
    from harness.traffic import timeline
    a, b = make_corpus(tiny_spec.config, 5), make_corpus(tiny_spec.config, 5)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    big = 2 ** 31 + 11
    traffic = tiny_spec.workload["traffic"]
    t1, q1 = timeline(traffic, 3.0, 256, big)
    t2, q2 = timeline(traffic, 3.0, 256, 4)
    assert len(t1) == len(t2) == 600
    assert abs(t1[-1] - 3.0) < 1e-9 and abs(t2[-1] - 3.0) < 1e-9
    assert np.allclose(np.sort(np.diff(t1, prepend=0)),
                       np.sort(np.diff(t2, prepend=0)))
    assert not np.array_equal(t1, t2) and not np.array_equal(q1, q2)


@pytest.mark.parametrize("key,value,what", [
    ("data", {"generator": "no_such_mixture"}, "data generator"),
    ("traffic", {"arrivals": "no_such_process"}, "arrival process")])
def test_unknown_generator_or_process_is_an_error(tiny_spec, key, value,
                                                   what):
    from harness.data import make_corpus
    from harness.traffic import timeline
    if key == "data":
        call = lambda: make_corpus({**tiny_spec.config, "data": value}, 1)
    else:
        call = lambda: timeline(value, 1.0, 16, 1)
    with pytest.raises(ValueError, match=f"unknown {what}"):
        call()


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", "--workload",
                           "sift1m.steady", "--seed", "1", "--seconds", "1",
                           *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_run_without_a_tpu_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_without_the_program_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".index_cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())["paths"] == [
        "bench"]
