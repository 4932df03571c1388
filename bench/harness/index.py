"""Build the program's BAMG index over the corpus, or load the arrays an
earlier run of the same key built.  The adjacency is served at the
configured width r for every seed.

The key is the configuration file's bytes, the seed, and a digest of the
program's sources (`src/repro/**/*.py`), of the configuration's data
generator and of this file.  A deployment serves a prebuilt index too; the
cache spares every later run with the same key the build's minutes.  Which
of the two a run did never shows in `setup_s`, which leaves the index step
out.  Only what the build made is kept (adjacency, PQ codes and
codebooks, entry candidates): the corpus is drawn from the seed again.
"""
from __future__ import annotations

import hashlib
import os
import time
from pathlib import Path

import numpy as np

from .data import generator_file, sub_seed

_KEPT = ("adj", "codes", "codebooks", "entry_cands")
ROOT = Path(__file__).resolve().parents[2]      # the checkout


def cache_key(config: dict, config_bytes: bytes, seed: int) -> str:
    h = hashlib.sha256(config_bytes)
    h.update(str(seed).encode())
    sources = sorted((ROOT / "src" / "repro").rglob("*.py"))
    sources += [generator_file(config), Path(__file__).resolve()]
    for p in sources:
        h.update(str(p.relative_to(ROOT)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:32]


def build_arrays(x: np.ndarray, config: dict, seed: int, log) -> dict:
    """The program's batched BAMG build and its serving arrays."""
    from repro.core.engine import BAMGIndex, BAMGParams

    b = config["build"]
    t = time.perf_counter()
    idx = BAMGIndex.build(x, BAMGParams(
        r=b["r"], pq_m=b["pq_m"], build_backend=b["build_backend"],
        seed=sub_seed(seed, 2)))
    stages = " ".join(f"{k}={v:.3f}s" for k, v in idx.build_seconds.items())
    log(f"index: built in {time.perf_counter() - t:.3f} s; build_seconds "
        f"{stages}")
    arrays = idx.batch_arrays(n_entry_cands=config["engine"]["n_entry_cands"])
    # `batch_arrays` trims the adjacency to the largest degree, which the
    # data decides (31 on some seeds); padding with -1 (no neighbour) to the
    # configured r gives every seed the same served shape, so one compiled
    # program serves them all.  It goes once `batch_arrays` pads to r itself.
    adj = arrays["adj"]
    arrays["adj"] = np.pad(adj, ((0, 0), (0, b["r"] - adj.shape[1])),
                           constant_values=-1)
    return {k: arrays[k] for k in _KEPT}


def load_or_build(x: np.ndarray, config: dict, config_bytes: bytes,
                  seed: int, cache_dir: Path, log) -> dict:
    """Serving arrays for `BatchedANNEngine` (with `x`), from the cache
    when an earlier run of the same key left them there."""
    path = cache_dir / f"{config['name']}-{cache_key(config, config_bytes, seed)}.npz"
    if path.exists():
        t = time.perf_counter()
        with np.load(path, allow_pickle=False) as z:
            arrays = {k: z[k] for k in _KEPT}
        log(f"index: cached ({path.name}), loaded in "
            f"{time.perf_counter() - t:.3f} s")
    else:
        arrays = build_arrays(x, config, seed, log)
        cache_dir.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f".tmp{os.getpid()}.npz")
        np.savez(tmp, **arrays)
        os.replace(tmp, path)
    return {"x": x, **arrays}
