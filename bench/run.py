#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the accelerator of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a `workloads` entry of `BENCHMARK.json`; its traffic is
`bench/workloads/<cell>.json`, its configuration the file its entry names.
Earlier lines of stdout say what the run did (device, index built or
cached, backend, window, compilations in the window); the compared numbers
and their limits are the last lines of stderr; the last line of stdout is
the result object.  With `--trace 0` its metrics are the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics from a profiler trace of
the window.  Without a TPU, or with fewer chips than the cell asks for,
or without the program's `src/repro` beside it, the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T0 = time.monotonic()      # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
INDEX_CACHE = BENCH / ".index_cache"


def fail(msg: str, code: int) -> int:
    print(f"FAIL: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        return fail(f"--seed {args.seed} is negative", 2)
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program's src/repro is not in {ROOT}", 2)
    # the TPU runtime would otherwise log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from harness.cell import load_spec, run_cell

    spec = load_spec(BENCH, json.loads((ROOT / "BENCHMARK.json").read_text()),
                     args.workload)
    from repro.utils.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    # every program, however quick to compile, is kept for the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX platform is {devices[0].platform!r}", 1)
    if len(devices) < spec.chips:
        return fail(f"the cell asks for {spec.chips} chips, JAX sees "
                    f"{len(devices)}", 1)
    print(f"device: platform={devices[0].platform} kind="
          f"{devices[0].device_kind} count={len(devices)}; compile cache "
          f"{cache_dir}", flush=True)
    print(f"cell: {spec.name} config={spec.config['name']} seed={args.seed} "
          f"seconds={args.seconds} trace={args.trace}", flush=True)
    result = run_cell(spec, BENCH, args.seed, args.seconds, bool(args.trace),
                      T0, INDEX_CACHE)
    for name, c in result["checks"].items():
        print(f"check: {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
