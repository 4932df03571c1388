"""A run with the timed path broken underneath it comes out as not
correct: once for each fault a one-chip serving cell can have.  (It keeps
no state across steps and exchanges nothing between chips, so the
training and multi-chip faults do not apply.)"""
import numpy as np
import pytest

from repro.serve import BatchedANNEngine


def _alter_answer(ids, dists):
    """Each answer's nearest id replaced by the next id, where produced."""
    ids = ids.copy()
    ids[:, 0] = (ids[:, 0] + 1) % 2048
    return ids, dists


def _half_batch(ids, dists):
    """Half of the batch left out: every odd row gets the even row's answer."""
    ids, dists = ids.copy(), dists.copy()
    ids[1::2], dists[1::2] = ids[0::2][:len(ids[1::2])], dists[0::2][
        :len(dists[1::2])]
    return ids, dists


@pytest.mark.parametrize("fault", [_alter_answer, _half_batch])
def test_broken_path_is_not_correct(run_tiny, monkeypatch, fault):
    clean = BatchedANNEngine.search_batch

    def broken(self, queries, k, **kw):
        return fault(*clean(self, queries, k, **kw))
    monkeypatch.setattr(BatchedANNEngine, "search_batch", broken)
    r = run_tiny(seed=7)
    assert r["correct"] is False
    assert r["checks"]["dist_gap"]["value"] > r["checks"]["dist_gap"]["limit"]


def test_clean_path_is_correct(run_tiny):
    r = run_tiny(seed=7)
    assert r["correct"] is True
    assert np.isfinite(r["checks"]["dist_gap"]["value"])
