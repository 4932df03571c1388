"""Loading the benchmark's files that are found by name: a metric's reader
(`bench/metrics`), a kernel's work (`bench/work`), a data generator
(`bench/data`) and an arrival process (`bench/traffic`)."""
from __future__ import annotations

import importlib.util
from pathlib import Path


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_named(path: Path, what: str):
    """The module at `path`; a name with no file is an error."""
    if not path.is_file():
        raise ValueError(f"unknown {what} {path.stem!r}: no {path.parent.name}"
                         f"/{path.name} in the benchmark")
    return load_module(path)
