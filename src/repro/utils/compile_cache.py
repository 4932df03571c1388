"""Where JAX keeps its persistent compilation cache for this repository.

The entry points (`chip_smoke.py`, `benchmarks/run.py`,
`python -m repro.launch.serve`, `examples/*.py`) call `use_compile_cache`
before anything else touches JAX.  Importing `repro` never does, so library
users and the test suite keep JAX's own default.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
# the checkout root: src/repro/utils/ -> three levels up
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives in `<checkout>/.jax_cache`:
    a fixed path, so a later run from the same checkout finds it again.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
