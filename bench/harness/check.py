"""The comparison that decides `correct`: every answer the window returned,
ids and distances alike, against the plain reference.

Each number compared must not exceed its limit:

- `unanswered`: requests offered and never answered (limit 0);
- `bad_rows`: answers flagged degraded, or holding an id outside the
  corpus, a repeated id, a non-finite distance, or distances out of
  ascending order (limit 0);
- `recall_gap`: 1 - mean recall@k against the exact top k (limit: 1 - the
  configuration's stated recall floor);
- `dist_gap`: the largest gap between a served distance and the exact
  float64 distance of the id it was served with, relative to that exact
  distance (limit set from the program's and the control's readings, see
  PERF.md).
"""
from __future__ import annotations

import numpy as np

from . import reference


def compare(done, offered: int, x: np.ndarray, queries: np.ndarray,
            query_of: np.ndarray, limits: dict, k: int) -> tuple[dict, float]:
    """(checks {name: {"value", "limit"}}, recall@k) of the completions
    `done` of `offered` requests; `query_of[rid]` is the held-out query
    request `rid` asked."""
    if not done:
        return {"unanswered": {"value": offered, "limit": 0}}, 0.0
    rids = np.array([c.rid for c in done], np.int64)
    ids = np.stack([c.ids for c in done]).astype(np.int64)
    dists = np.stack([c.dists for c in done]).astype(np.float64)
    degraded = np.array([c.degraded for c in done], bool)

    used, inverse = np.unique(query_of[rids], return_inverse=True)
    q = queries[used]
    true_ids, _ = reference.exact_topk(x, q, k)
    exact = reference.exact_dists(x, q[inverse], ids)

    srt = np.sort(ids, axis=1)
    bad = (degraded | ((ids < 0) | (ids >= len(x))).any(1)
           | (srt[:, 1:] == srt[:, :-1]).any(1)
           | ~np.isfinite(dists).all(1)
           | (np.diff(dists, axis=1) < 0).any(1))
    hits = (ids[:, :, None] == true_ids[inverse][:, None, :]).any(2).sum(1)
    recall = float(hits.mean() / k)
    ok = np.isfinite(exact) & np.isfinite(dists) & (exact > 0)
    gap = float((np.abs(dists - exact)[ok] / exact[ok]).max()) if ok.any() else 0.0

    checks = {
        "unanswered": (offered - len(np.unique(rids)), 0),
        "bad_rows": (int(bad.sum()), 0),
        "recall_gap": (1.0 - recall, 1.0 - limits["recall_at_10_min"]),
        "dist_gap": (gap, limits["dist_gap_max"]),
    }
    return ({name: {"value": v, "limit": lim}
             for name, (v, lim) in checks.items()}, recall)


def holds(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
