"""The round clock: store commits stamped with the round that was served.

``clock_store`` wraps the program's ``DiskStore`` so that each public
commit (``write``, ``append``, ``consolidate``) records ``(round, time,
entry, kind)`` once it returns, and otherwise defers to ``DiskStore``. A
round ends at its last commit and starts where the one before it ended,
since rounds run back to back; a round's time so includes the planner's
solve that precedes its engine run.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, NamedTuple

import numpy as np


class Commit(NamedTuple):
    round: int
    t: float  # time.perf_counter() when the commit returned
    entry: str
    kind: str


def clock_store(store_cls, root, served: Callable[[], int], **store_kw):
    """A ``store_cls`` (the program's ``DiskStore``) at ``root`` whose
    commits land in its ``commits`` list, stamped with ``served()``."""

    class CommitClockStore(store_cls):
        def __init__(self):
            super().__init__(root, **store_kw)
            self.commits: list[Commit] = []
            self.ends: dict[int, float] = {}  # round -> last commit time
            self.consolidations = 0
            self._depth = threading.local()
            self._log_lock = threading.Lock()

        def _stamped(self, kind, name, call):
            # ``append`` of a new entry and ``consolidate`` commit through
            # ``write``: stamp the outermost public call only
            depth = getattr(self._depth, "n", 0)
            self._depth.n = depth + 1
            try:
                out = call()
            finally:
                self._depth.n = depth
            if depth == 0:
                with self._log_lock:
                    c = Commit(served(), time.perf_counter(), name, kind)
                    self.commits.append(c)
                    self.ends[c.round] = c.t
            return out

        def write(self, name, table):
            return self._stamped("write", name,
                                 lambda: super(CommitClockStore, self)
                                 .write(name, table))

        def append(self, name, delta):
            return self._stamped("append", name,
                                 lambda: super(CommitClockStore, self)
                                 .append(name, delta))

        def consolidate(self, name):
            if self.parts(name) <= 1:  # nothing to fold: no commit
                return super().consolidate(name)
            with self._log_lock:
                self.consolidations += 1
            return self._stamped("consolidate", name,
                                 lambda: super(CommitClockStore, self)
                                 .consolidate(name))

    return CommitClockStore()


def round_ends(commits: list[Commit]) -> dict[int, float]:
    """Last commit time of each round that committed anything."""
    ends: dict[int, float] = {}
    for c in commits:
        if c.t > ends.get(c.round, -np.inf):
            ends[c.round] = c.t
    return ends


def window_rounds(commits: list[Commit], window_start: float,
                  first: int, last: int) -> dict[int, tuple[float, float]]:
    """``{round: (start, end)}`` for rounds ``first..last``: round
    ``first`` starts at ``window_start``, each later one where the one
    before it ended; a round that committed nothing ends where it
    started."""
    ends = round_ends(commits)
    out: dict[int, tuple[float, float]] = {}
    start = window_start
    for r in range(first, last + 1):
        end = max(ends.get(r, start), start)
        out[r] = (start, end)
        start = end
    return out


def end_to_end(commits: list[Commit], window_start: float,
               first: int, last: int) -> dict:
    """The window is the whole rounds ``first..last``, from
    ``window_start`` to round ``last``'s last commit.

    ``refresh_s``: the window's time over its number of rounds.
    ``fresh_p95_s``: the 95th percentile (numpy's linear interpolation),
    over every commit of those rounds, of the time from its round's
    start."""
    spans = window_rounds(commits, window_start, first, last)
    lags = [c.t - spans[c.round][0] for c in commits if c.round in spans]
    return {
        "refresh_s": (spans[last][1] - window_start) / len(spans),
        "fresh_p95_s": float(np.percentile(lags, 95)) if lags else None,
        "commits": len(lags),
    }
