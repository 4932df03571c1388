#!/usr/bin/env python3
"""Chip smoke test: serve a SIFT1M-shaped BAMG shard on a TPU through the
normal entry points, and check the answers against exact ground truth.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # four chips: the sharded fleet only

One chip: a SIFT1M-shaped corpus (d=128, f32, L2, made from `--seed`; n
cut from 1,000,000 to 2^18, see ONE_CHIP_N) with exact ground truth, a
batched BAMG build (r=32, PQ M=64), then the
served path `Scheduler` -> `ServeRuntime` -> `BatchedANNEngine` with
`backend="auto"`, which must resolve to a fused Pallas backend.  It fails
on any degraded answer or -1 id, on recall@10 below 0.90, and when the
plain-XLA engine (`backend="ref"`) on the same chip differs in recall by
more than 0.005.

Four chips: a 4-shard fleet placed one shard per chip (each engine's
arrays must sit on its own device), compared with the same shards served
from one device, whose ids must be identical.

Every phase runs in this one process.  The script exits non-zero and
prints no result when JAX finds no TPU, or when the repository's `src/`
is not next to it.  The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SIFT_N = 1_000_000
# the build's host stages (Algorithm 2's ordered-pair scan, BNF blocks,
# navigation-layer selection) are Python loops linear in n: at 2^18 they
# take minutes, at 1M they alone outlast a 1200 s run.  2^18 is also the
# smallest shard `auto` still serves by streaming.
ONE_CHIP_N = 2 ** 18
FLEET_N = 65536          # --chips 4: the fleet path, not the shard size
QUERIES, K = 256, 10
BATCH = 64               # ServeRuntime batch and Scheduler max_batch
REQUESTS, QPS = 320, 200.0
# beam: pool l and hop budget, the smallest that held recall@10 >= 0.90
L_POOL, MAX_HOPS = 256, 256
RECALL_MIN = 0.90
REF_RECALL_TOL = 0.005
# 64 PQ subquantizers (2 dims each): with the default 16 the ADC ranking
# of this isotropic corpus caps recall@10 far below RECALL_MIN
PQ_M = 64


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def serve_in_batches(serve, queries, batch: int):
    """Concatenated (ids, dists) of `serve(q_batch)` over fixed-size
    batches; the last batch is padded by repeating its first row."""
    import numpy as np
    out_i, out_d = [], []
    for s in range(0, len(queries), batch):
        q = queries[s:s + batch]
        b = len(q)
        if b < batch:
            q = np.concatenate([q, np.repeat(q[:1], batch - b, axis=0)])
        ids, d = serve(q)
        out_i.append(ids[:b])
        out_d.append(d[:b])
    return np.concatenate(out_i), np.concatenate(out_d)


def make_corpus(n: int, nq: int, k: int, seed: int):
    from repro.data.synthetic import PAPER_REGIMES, make_vector_dataset
    reg = PAPER_REGIMES["sift-like"]
    t = time.perf_counter()
    ds = make_vector_dataset("sift-like", n, reg["d"], nq, k_gt=k,
                             n_clusters=reg["n_clusters"], seed=seed)
    log(f"corpus: sift-like n={n} d={reg['d']} queries={nq} k={k} "
        f"seed={seed}; generation + exact ground truth "
        f"{time.perf_counter() - t:.3f} s")
    return ds


def build_index(x, seed: int):
    from repro.core.engine import BAMGIndex, BAMGParams
    t = time.perf_counter()
    idx = BAMGIndex.build(x, BAMGParams(r=32, pq_m=PQ_M,
                                        build_backend="batched", seed=seed))
    stages = " ".join(f"{k}={v:.3f}s" for k, v in idx.build_seconds.items())
    log(f"build: total {time.perf_counter() - t:.3f} s; {stages}")
    log(f"index: {idx.graph.members.shape[0]} blocks x capacity "
        f"{idx.graph.capacity}, nav layers "
        f"{[len(layer.vids) for layer in idx.nav.layers]}")
    return idx


def one_chip(args, platform: str) -> None:
    import numpy as np
    from repro.core.distances import recall_at_k
    from repro.serve import (BatchedANNEngine, BeamTier, EngineConfig,
                             Scheduler, SchedulerConfig, ServeRuntime,
                             make_requests, summarize)
    from repro.serve.ann_engine import resolve_backend

    n, k, batch = args.n, K, BATCH
    if n < SIFT_N:
        log(f"reduced: n {SIFT_N} -> {n} (the host build stages do not fit "
            f"the run's time limit at 1M)")
    ds = make_corpus(n, QUERIES, k, args.seed)
    gt = ds.gt[:, :k]
    idx = build_index(ds.base, args.seed)

    cfg = EngineConfig(l=L_POOL, max_hops=MAX_HOPS, backend="auto")
    engine = BatchedANNEngine.from_index(idx, cfg)
    resolved = resolve_backend("auto", n=engine.n, r=engine.adj.shape[1],
                               m=engine.codes.shape[1],
                               k=engine.codebooks.shape[1], l=L_POOL,
                               max_hops=MAX_HOPS, platform=platform)
    log(f"backend: auto -> {resolved} (l={L_POOL} max_hops={MAX_HOPS} "
        f"batch={batch})")
    check(not (platform == "tpu" and resolved == "ref"),
          "backend auto resolved to the plain-XLA 'ref' path on a TPU")
    if platform == "tpu" and n >= ONE_CHIP_N:
        check(resolved == "fused_stream",
              f"auto resolved to {resolved!r}, expected 'fused_stream' at "
              f"n={n}")

    # --- ServeRuntime (answers come back as host arrays, so each call has
    # finished on the device): the first call compiles, the second is steady
    rt = ServeRuntime([np.arange(n)], [engine])
    q0 = ds.queries[:batch]
    t = time.perf_counter()
    rt.serve_batch(q0, k)
    first = time.perf_counter() - t
    t = time.perf_counter()
    rt.serve_batch(q0, k)
    steady = time.perf_counter() - t
    log(f"compile: first call {first:.3f} s, steady call {steady:.3f} s "
        f"(batch {batch})")

    degraded = []

    def serve(q):
        ids, dists, status = rt.serve_batch(q, k, with_status=True)
        degraded.append(status.degraded.any())
        return ids, dists

    t = time.perf_counter()
    ids, dists = serve_in_batches(serve, ds.queries, batch)
    t_rt = time.perf_counter() - t
    check(not any(degraded), "ServeRuntime returned degraded answers")
    check((ids >= 0).all(), "ServeRuntime returned -1 ids")
    check(np.isfinite(dists).all(), "ServeRuntime returned non-finite dists")
    rec = recall_at_k(ids, gt, k)
    log(f"runtime: {len(ids)} queries in {t_rt:.3f} s, recall@{k}={rec:.4f}")
    check(rec >= RECALL_MIN, f"recall@{k}={rec:.4f} < {RECALL_MIN}")

    # --- Scheduler open-loop run over the same runtime
    n_req = REQUESTS
    reqs = make_requests(ds.queries, qps=QPS, slo=3600.0, n=n_req,
                         seed=args.seed)
    sched = Scheduler(rt, SchedulerConfig(k=k, max_batch=batch, slo=3600.0,
                                          tiers=(BeamTier(),)))
    done = sched.run(reqs)
    s_ids = np.stack([c.ids for c in done])
    s_gt = gt[[c.rid % len(gt) for c in done]]
    check(len(done) == n_req, f"scheduler completed {len(done)}/{n_req}")
    check(not any(c.degraded for c in done), "scheduler: degraded answers")
    check((s_ids >= 0).all(), "scheduler returned -1 ids")
    s_rec = recall_at_k(s_ids, s_gt, k)
    summ = summarize(done)
    log(f"scheduler: {n_req} requests offered at {QPS} qps, "
        f"max_batch={batch}: recall@{k}={s_rec:.4f} "
        f"p50={summ['p50_ms']:.3f} ms p99={summ['p99_ms']:.3f} ms "
        f"achieved={summ['achieved_qps']:.3f} qps (host clock)")
    check(s_rec >= RECALL_MIN, f"scheduler recall@{k}={s_rec:.4f}")

    # --- the plain-XLA engine on the same device, same index
    ref = BatchedANNEngine.from_index(
        idx, EngineConfig(l=L_POOL, max_hops=MAX_HOPS, backend="ref"))
    r_ids, _ = serve_in_batches(lambda q: ref.search_batch(q, k),
                                ds.queries, batch)
    r_rec = recall_at_k(r_ids, gt, k)
    same = float((r_ids == ids).all(1).mean())
    log(f"reference: backend=ref recall@{k}={r_rec:.4f} "
        f"(fused {resolved} {rec:.4f}, |diff|={abs(r_rec - rec):.4f}); "
        f"identical id rows {same:.4f}")
    check(abs(r_rec - rec) <= REF_RECALL_TOL,
          f"fused and ref recall differ by {abs(r_rec - rec):.4f}")


def four_chips(args) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.distances import recall_at_k
    from repro.core.engine import BAMGParams
    from repro.serve import (BatchedANNEngine, EngineConfig, ServeRuntime,
                             build_shard_fleet)

    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    n, k, batch = args.n, K, BATCH
    log(f"reduced: n {SIFT_N} -> {n} over 4 shards (the fleet path, not "
        f"the shard size, is under test)")
    ds = make_corpus(n, QUERIES, k, args.seed)
    cfg = EngineConfig(l=L_POOL, max_hops=MAX_HOPS, backend="auto")
    t = time.perf_counter()
    vids, engines, indexes = build_shard_fleet(
        ds.base, 4, params=BAMGParams(r=32, pq_m=PQ_M,
                                      build_backend="batched",
                                      seed=args.seed), config=cfg)
    log(f"build: 4 shards of {[len(v) for v in vids]} in "
        f"{time.perf_counter() - t:.3f} s")

    fleet = ServeRuntime(vids, engines, host_indexes=indexes)
    for s, eng in enumerate(fleet.engines):
        homes = {d for a in BatchedANNEngine._ARRAY_ATTRS
                 for d in getattr(eng, a).devices()}
        log(f"shard {s}: arrays on {sorted(str(d) for d in homes)}")
        check(homes == {devs[s]}, f"shard {s} arrays on {homes}, expected "
              f"only {devs[s]}")

    single = ServeRuntime(
        vids, [BatchedANNEngine.from_index(i, cfg) for i in indexes],
        mesh=Mesh(np.array(devs[:1]), ("data",)))
    check(len(single.placement.workers) == 1, "one-device fleet has "
          f"{len(single.placement.workers)} workers")

    t = time.perf_counter()
    f_ids, f_d = serve_in_batches(
        lambda q: fleet.serve_batch(q, k), ds.queries, batch)
    t_f = time.perf_counter() - t
    t = time.perf_counter()
    s_ids, s_d = serve_in_batches(
        lambda q: single.serve_batch(q, k), ds.queries, batch)
    t_s = time.perf_counter() - t
    rec = recall_at_k(f_ids, ds.gt[:, :k], k)
    log(f"fleet: 4 shards on 4 devices recall@{k}={rec:.4f} ({t_f:.3f} s "
        f"incl. compile); same shards on 1 device ({t_s:.3f} s incl. "
        f"compile): identical ids {bool((f_ids == s_ids).all())}, max "
        f"|dist diff| {float(np.abs(f_d - s_d).max()):.3g}")
    check((f_ids >= 0).all(), "4-chip fleet returned -1 ids")
    check(rec >= RECALL_MIN, f"4-chip fleet recall@{k}={rec:.4f}")
    check((f_ids == s_ids).all(), "4-chip fleet ids differ from one device")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=None,
                    help=f"corpus size (default {ONE_CHIP_N}; {FLEET_N} with "
                         f"--chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.n is None:
        args.n = ONE_CHIP_N if args.chips == 1 else FLEET_N
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"FAIL: the repository's src/repro is not next to "
              f"{os.path.abspath(__file__)}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.utils.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax
    from jax import monitoring
    cache_events = {"/jax/compilation_cache/cache_hits": 0,
                    "/jax/compilation_cache/compile_requests_use_cache": 0}

    def count_cache_event(event: str, **kwargs) -> None:
        if event in cache_events:
            cache_events[event] += 1

    monitoring.register_event_listener(count_cache_event)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"FAIL: no TPU: JAX platform is {dev.platform!r} "
              f"({len(devs)} device(s))", file=sys.stderr)
        return 1
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    log(f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args)
        else:
            one_chip(args, dev.platform)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    log(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    log("compile cache: hits="
        f"{cache_events['/jax/compilation_cache/cache_hits']} requests="
        f"{cache_events['/jax/compilation_cache/compile_requests_use_cache']}")
    log(f"wall: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
