"""One run of one cell: set-up, the measured window, the comparison with the
plain reference, and the metrics the cell reports.

Set-up is everything before the window's first request: the corpus and
queries drawn from the seed, the program's index built or loaded from the
index cache, the engine and runtime constructed, the cell's one batch
shape warmed up, and the timeline planned.  `setup_s` is that time less
the index step, so that it reads the same whether the index was built or
loaded: the build's seconds are printed, by stage, on the run that builds.
The window is the workload's arrival process (`bench/traffic`) driving
`Scheduler`.  Only once it has closed, device memory has been read and the
program's state freed does the reference run.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from . import check, traffic
from . import trace as trace_mod
from .data import make_corpus
from .index import load_or_build
from .modules import load_module

# The server every cell runs: micro-batches of 64 rows, one full-beam tier,
# and the backend the program picks for the device.  With one tier the
# deadline picks no tier, and one SLO for every request makes EDF serve in
# arrival order.
MAX_BATCH = 64
SLO_S = 10.0
BACKEND = "auto"


@dataclasses.dataclass
class CellSpec:
    """Everything one cell names: its workload and configuration files, the
    chips it asks for, and the metrics `BENCHMARK.json` gives it."""
    name: str
    workload: dict
    config: dict
    config_bytes: bytes
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]


def load_spec(bench_dir: Path, benchmark: dict, cell: str) -> CellSpec:
    """The cell's entry in `BENCHMARK.json` and the files it names:
    `bench/workloads/<cell>.json` and the configuration's `file`."""
    entry = next((w for w in benchmark["workloads"] if w["name"] == cell),
                 None)
    if entry is None:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json")
    conf = next(c for c in benchmark["configs"] if c["name"] == entry["config"])
    root = bench_dir.parent
    config_bytes = (root / conf["file"]).read_bytes()
    workload = json.loads((bench_dir / "workloads" / f"{cell}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if cell in m.get("workloads", [cell])]
    return CellSpec(cell, workload, json.loads(config_bytes), config_bytes,
                    entry["chips"], mine(benchmark["end_to_end"]),
                    mine(benchmark["per_layer"]))


class SpanRuntime:
    """The runtime handed to `Scheduler`: a host span around each
    `serve_batch`, and the calls and their host-clock seconds counted.  The
    call returns host arrays, so the device has finished when it returns."""

    def __init__(self, runtime):
        self.runtime = runtime
        self.reset()

    def reset(self) -> None:
        self.seconds: list[float] = []

    def serve_batch(self, queries, k, with_status=False, **kw):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_mod.STEP_SPAN):
            out = self.runtime.serve_batch(queries, k, with_status=with_status,
                                           **kw)
        self.seconds.append(time.perf_counter() - t)
        return out


@dataclasses.dataclass
class Record:
    """What a metric's reader may read of one run."""
    spec: CellSpec
    bench_dir: Path
    device_kind: str
    setup_s: float
    window_s: float
    done: list
    recall: float
    step_seconds: list[float]
    trace: trace_mod.Trace | None
    max_batch: int = MAX_BATCH          # rows of every micro-batch

    def work(self, kernel: str):
        """The module `bench/work/<kernel>.py`."""
        return load_module(self.bench_dir / "work" / f"{kernel}.py")

    def peaks(self) -> dict:
        """This device's row of `bench/peaks.json`; an unknown kind is an
        error."""
        table = json.loads((self.bench_dir / "peaks.json").read_text())
        if self.device_kind not in table["devices"]:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           f"in peaks.json")
        return table["devices"][self.device_kind]


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, counted by
    JAX's own monitoring events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1


def _log(msg: str) -> None:
    print(msg, flush=True)


def program_runtime(x, spec: CellSpec, seed: int, cache_dir: Path,
                    log=_log):
    """(runtime, index seconds): the system under test, the program's BAMG
    index over `x` (built, or loaded from the index cache) served by one
    `BatchedANNEngine` behind one `ServeRuntime`, and the seconds the index
    step took."""
    import jax
    from repro.serve import BatchedANNEngine, EngineConfig, ServeRuntime
    from repro.serve.ann_engine import resolve_backend

    t = time.perf_counter()
    arrays = load_or_build(x, spec.config, spec.config_bytes, seed,
                           cache_dir, log)
    index_s = time.perf_counter() - t
    e = spec.config["engine"]
    engine = BatchedANNEngine(arrays, EngineConfig(
        l=e["l"], max_hops=e["max_hops"], backend=BACKEND,
        n_entry_cands=e["n_entry_cands"]))
    backend = resolve_backend(BACKEND, n=engine.n, r=engine.adj.shape[1],
                              m=engine.codes.shape[1],
                              k=engine.codebooks.shape[1], l=e["l"],
                              max_hops=e["max_hops"])
    log(f"backend: {BACKEND} -> {backend} (l={e['l']} max_hops="
        f"{e['max_hops']} batch={MAX_BATCH})")
    if jax.default_backend() == "tpu" and backend == "ref":
        raise RuntimeError("backend auto resolved to the plain-XLA 'ref' "
                           "path on a TPU")
    return ServeRuntime([np.arange(len(x))], [engine]), index_s


def run_cell(spec: CellSpec, bench_dir: Path, seed: int, seconds: float,
             trace: bool, t0: float, cache_dir: Path,
             serve=program_runtime, log=_log) -> dict:
    """One run of the cell; returns the result object of the last line.

    `serve(x, spec, seed, cache_dir, log)` gives the runtime the window
    drives, the program's by default and the control's in its place, and
    the seconds of its set-up that `setup_s` leaves out."""
    import jax
    from repro.serve import BeamTier, Scheduler, SchedulerConfig
    from repro.serve.runtime.scheduler import Request

    config, work = spec.config, spec.workload
    devices = jax.devices()[:spec.chips]
    platform, kind = devices[0].platform, devices[0].device_kind
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        t = time.perf_counter()
        x, queries = make_corpus(config, seed)
        log(f"data: n={len(x)} d={x.shape[1]} held-out queries="
            f"{len(queries)} in {time.perf_counter() - t:.3f} s")
        runtime, untimed_s = serve(x, spec, seed, cache_dir, log)
        spans = SpanRuntime(runtime)
        sched = Scheduler(spans, SchedulerConfig(
            k=config["k"], max_batch=MAX_BATCH, slo=SLO_S,
            tiers=(BeamTier(),)))
        t = time.perf_counter()
        sched.warmup(x.shape[1])
        first = time.perf_counter() - t
        t = time.perf_counter()
        sched.warmup(x.shape[1])
        log(f"warmup: first call {first:.3f} s, second {time.perf_counter() - t:.3f}"
            f" s; programs compiled or loaded in set-up: {counter.count}")
        spans.reset()

        process = traffic.process(work["traffic"])
        arrivals, query_of = traffic.timeline(work["traffic"], seconds,
                                              len(queries), seed)
        reqs = [Request(rid=i, query=queries[qi], arrival=float(a),
                        deadline=float(a) + SLO_S)
                for i, (a, qi) in enumerate(zip(arrivals, query_of))]
        log(f"timeline: {len(reqs)} requests over {arrivals[-1]:.3f} s, "
            f"traffic {json.dumps(work['traffic'], sort_keys=True)}")

        trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace else None
        if trace:
            jax.profiler.start_trace(str(trace_dir))
        compiles_before = counter.count
        setup_s = time.monotonic() - t0 - untimed_s
        log(f"set-up: {setup_s + untimed_s:.3f} s, setup_s {setup_s:.3f} s "
            f"(the index step's {untimed_s:.3f} s left out)")
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            done, offered = process.drive(sched, reqs)
        window_s = time.perf_counter() - t
        in_window = counter.count - compiles_before
        if trace:
            jax.profiler.stop_trace()
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)

    lat = np.array([c.latency for c in done]) * 1e3
    log(f"window: wall {window_s:.3f} s, offered {offered}, completed "
        f"{len(done)}, calls {len(spans.seconds)}, p50 "
        f"{np.percentile(lat, 50):.3f} ms, p95 {np.percentile(lat, 95):.3f} ms;"
        f" compilations in the window: {in_window}")
    if in_window:
        raise RuntimeError(f"{in_window} programs compiled inside the window")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log(f"memory: peak_bytes_in_use={peak}")
    step_seconds = list(spans.seconds)
    del spans, sched          # the program's state: freed before the reference
    gc.collect()

    t = time.perf_counter()
    checks, recall = check.compare(done, offered, x, queries, query_of,
                                   config["correct"], config["k"])
    log(f"reference: {time.perf_counter() - t:.3f} s")

    tr = None
    if trace:
        t = time.perf_counter()
        tr = trace_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: read in {time.perf_counter() - t:.3f} s; window "
            f"{tr.window_s:.6f} s, device planes {sorted(tr.ops)}, "
            f"operations in the window {len(tr.window_ops())}")

    rec = Record(spec, bench_dir, kind, setup_s, window_s, done, recall,
                 step_seconds, tr)
    metrics = {}
    for m in (spec.per_layer if trace else spec.end_to_end):
        value = load_module(bench_dir / "metrics" / f"{m['name']}.py").read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": check.holds(checks), "attempted": offered,
              "failed": int(sum(checks[c]["value"] for c in
                                ("unanswered", "bad_rows") if c in checks)),
              "metrics": metrics, "device": device}
    if tr is not None:
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["breakdown"] = tr.breakdown()
    result["checks"] = checks
    return result
