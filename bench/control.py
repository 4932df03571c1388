#!/usr/bin/env python3
"""Read the comparison's numbers for the control on several seeds.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

The control is the plain reference in the program's place at the nearest
precision below the configuration's (`harness/control.py`).  Each seed
runs the cell's timeline through `Scheduler` and the comparison, as
`run.py` does, and prints one JSON line with the numbers and limits; the
control has to come out as not correct on every seed.  It needs no index,
so it costs a few seconds a seed.  The benchmark's own runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from harness.cell import load_spec, run_cell
    from harness.control import control_runtime

    spec = load_spec(BENCH, json.loads((ROOT / "BENCHMARK.json").read_text()),
                     args.workload)
    import jax
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run_cell(spec, BENCH, seed, args.seconds, False, time.monotonic(),
                     BENCH / ".index_cache", serve=control_runtime)
        all_failed &= not r["correct"]
        print(json.dumps({"control": spec.name, "seed": seed,
                          "correct": r["correct"], "checks": r["checks"]}),
              flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
