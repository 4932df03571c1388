"""Fixtures of the benchmark's own CPU checks (not part of the program's
test suite):

    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider bench/tests

The tiny configuration is driven through the same set-up, window,
comparison and reduction as a chip run, at n=2,048.
"""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

SECONDS = 2.0


@pytest.fixture(scope="session")
def tiny_spec():
    from harness.cell import CellSpec
    here = Path(__file__).parent
    config_bytes = (here / "tiny.json").read_bytes()
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    return CellSpec("tiny.steady",
                    json.loads((here / "tiny.steady.json").read_text()),
                    json.loads(config_bytes), config_bytes, 1,
                    benchmark["end_to_end"], benchmark["per_layer"])


@pytest.fixture(scope="session")
def index_cache(tmp_path_factory):
    """One index cache for the session: the tiny index is built once."""
    return tmp_path_factory.mktemp("index_cache")


@pytest.fixture
def run_tiny(tiny_spec, index_cache):
    import time
    from harness.cell import run_cell

    def run(seed=7, trace=False, log=lambda m: None, **kw):
        return run_cell(tiny_spec, BENCH, seed, SECONDS, trace,
                        time.monotonic(), index_cache, log=log, **kw)
    return run
