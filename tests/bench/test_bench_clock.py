"""The commit clock: refresh_s and fresh_p95_s from a commit log."""
import numpy as np
import pytest

from bench import clock
from bench.clock import Commit
from repro.mv import DiskStore


def _log():
    # round 1 (set-up) ends at 10.0; rounds 2..4 are the window
    return [
        Commit(0, 1.0, "a", "write"), Commit(1, 9.0, "a", "append"),
        Commit(1, 10.0, "b", "append"),
        Commit(2, 11.0, "a", "append"), Commit(2, 14.0, "b", "append"),
        Commit(3, 15.0, "a", "append"), Commit(3, 16.0, "b", "append"),
        Commit(3, 17.0, "a", "consolidate"),
        Commit(4, 20.0, "a", "append"), Commit(4, 21.0, "b", "append"),
    ]


def test_round_spans_run_back_to_back():
    spans = clock.window_rounds(_log(), 10.0, 2, 4)
    assert spans == {2: (10.0, 14.0), 3: (14.0, 17.0), 4: (17.0, 21.0)}


def test_end_to_end_metrics_take_whole_rounds():
    e2e = clock.end_to_end(_log(), 10.0, 2, 4)
    assert e2e["refresh_s"] == pytest.approx(11.0 / 3)
    lags = [1.0, 4.0, 1.0, 2.0, 3.0, 3.0, 4.0]  # every commit of rounds 2..4
    assert e2e["commits"] == len(lags)
    assert e2e["fresh_p95_s"] == pytest.approx(np.percentile(lags, 95))


def test_end_to_end_leaves_out_rounds_outside_the_window():
    e2e = clock.end_to_end(_log(), 10.0, 2, 3)
    assert e2e["refresh_s"] == pytest.approx(7.0 / 2)
    lags = [1.0, 4.0, 1.0, 2.0, 3.0]
    assert e2e["fresh_p95_s"] == pytest.approx(np.percentile(lags, 95))


def test_round_without_commits_ends_where_it_started():
    log = [Commit(1, 5.0, "a", "write"), Commit(3, 9.0, "a", "append")]
    spans = clock.window_rounds(log, 5.0, 2, 3)
    assert spans == {2: (5.0, 5.0), 3: (5.0, 9.0)}


def test_store_stamps_each_public_commit_once(tmp_path):
    served = [0]
    store = clock.clock_store(DiskStore, tmp_path, lambda: served[0])
    t = {"key": np.arange(4, dtype=np.int64),
         "rid": np.arange(4, dtype=np.int64)}
    store.append("x", t)  # a first append commits through write
    served[0] = 1
    store.append("x", {"key": np.array([9], np.int64),
                       "rid": np.array([9], np.int64)})
    store.consolidate("x")
    store.consolidate("x")  # single part: nothing to fold, no commit
    assert [(c.round, c.entry, c.kind) for c in store.commits] == [
        (0, "x", "append"), (1, "x", "append"), (1, "x", "consolidate")]
    assert store.consolidations == 1
    assert store.ends == {0: store.commits[0].t, 1: store.commits[2].t}
    assert store.read("x")["key"].tolist() == [0, 1, 2, 3, 9]
