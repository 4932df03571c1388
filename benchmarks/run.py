"""Run every benchmark; print ``name,value,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--only fig5,table2] \
      [--json BENCH.json]

One module per paper table/figure (DESIGN.md §6).  REPRO_BENCH_N scales
corpus sizes (default 4000 -- single-core-CPU friendly).  --json writes
every emitted row (tagged with its suite) plus an environment-metadata
block to the given path -- the machine-readable artifact CI uploads, so
runs are diffable across commits without scraping stdout.
"""
import argparse
import json
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="",
                    help="write suite rows + env metadata to this path")
    args = ap.parse_args()

    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()

    from . import (bench_ablation, bench_alpha, bench_beta, bench_degrees,
                   bench_fresh, bench_indexing, bench_io_pipeline,
                   bench_kernels, bench_memory, bench_nio_recall,
                   bench_qps_recall, bench_roofline, bench_serve, common)

    suites = [
        ("fig4", bench_qps_recall.run),
        ("fig5", bench_nio_recall.run),
        ("fig6_7", bench_indexing.run),
        ("fig8", bench_alpha.run),
        ("fig9", bench_beta.run),
        ("fig10", bench_memory.run),
        ("table2", bench_degrees.run),
        ("fig11", bench_ablation.run),
        ("io_pipeline", bench_io_pipeline.run),
        ("kernels", bench_kernels.run),
        ("roofline", bench_roofline.run),
        ("serve", bench_serve.run),
        ("fresh", bench_fresh.run),
        # named without "serve" so `--only serve` (substring match) does
        # not double-run the sweep alongside the serve suite
        ("load_sweep", bench_serve.run_load_sweep),
    ]
    only = [s for s in args.only.split(",") if s]
    print("name,value,derived")
    failures = 0
    for name, fn in suites:
        if only and not any(o in name for o in only):
            continue
        t0 = time.time()
        row0 = len(common.ROWS)
        try:
            fn()
            status = "ok"
        except Exception as e:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            status = f"FAILED:{type(e).__name__}"
        wall = time.time() - t0
        print(f"bench.{name}.wall_s,{wall:.1f},{status}")
        for row in common.ROWS[row0:]:
            row["suite"] = name
        common.ROWS.append({"name": f"bench.{name}.wall_s",
                            "value": round(wall, 1), "derived": status,
                            "suite": name})
    if args.json:     # written even on failure: partial rows still diff
        d = os.path.dirname(args.json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"meta": common.env_metadata(), "rows": common.ROWS},
                      f, indent=1)
        print(f"# wrote {len(common.ROWS)} rows -> {args.json}",
              file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
