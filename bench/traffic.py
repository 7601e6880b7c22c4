"""The benchmark's traffic source: seeded base tables and per-round deltas.

A copy of the scan data and delta generation of the program's
``realize_workload`` (seeded ``make_base_table`` rows, Zipf key draws,
round-monotone rids, ``delta_from_live``), so that the yardstick does not
move with the program. For the same seed it yields the same rows as
``realize_workload`` (``tests/bench/test_bench_traffic.py``), but each scan
keeps only its latest live state instead of one full table per round.

``Window`` ends the measured window at a round boundary: at the first
delta request of the first round that starts after the deadline it raises
``Stop``. On a partitioned workload that request comes from the program's
clean-partition pruner, on the scenario's own thread, after the round's
planner solve and before the engine dispatches anything, so the store then
holds exactly the rounds that were served.
"""
from __future__ import annotations

import threading
import time
from typing import Callable

import numpy as np

WEIGHT = "weight"
_RID_NODE_SLOTS = 1 << 12
_RID_ROW_BITS = 32
# The Zipf order of the keys is fixed, so that the hot keys, and with them
# the partition sizes the planner sees, are alike for every run seed; the
# run seed draws the rows.
KEY_ORDER_SEED = 0


class Stop(Exception):
    """The end of the measured window, raised from a delta request."""


def rid_base(round_idx: int, node_idx: int) -> int:
    """First rid of the rows scan ``node_idx`` inserts at ``round_idx``:
    round-major, so every round's rows sort after all older rows."""
    return (round_idx * _RID_NODE_SLOTS + node_idx) << _RID_ROW_BITS


def zipf_probs(n_keys: int, skew: float, seed: int) -> np.ndarray | None:
    """Zipf(``skew``) over ``n_keys`` ids, shuffled by ``seed`` so the hot
    keys scatter over the id space; ``None`` (uniform) when ``skew <= 0``."""
    if skew <= 0.0:
        return None
    w = np.arange(1, n_keys + 1, dtype=np.float64) ** -float(skew)
    np.random.default_rng(seed).shuffle(w)
    return w / w.sum()


def base_table(n_rows: int, n_cols: int, seed: int, key_mod: int,
               first_rid: int, key_probs: np.ndarray | None) -> dict:
    """Seeded rows: int64 ``key``, int64 ``rid`` and ``n_cols - 1`` float32
    value columns drawn from a standard normal."""
    rng = np.random.default_rng(seed)
    if key_probs is not None:
        keys = rng.choice(len(key_probs), size=n_rows, p=key_probs)
    else:
        keys = rng.integers(0, key_mod, n_rows)
    t = {"key": keys.astype(np.int64),
         "rid": first_rid + np.arange(n_rows, dtype=np.int64)}
    for c in range(n_cols - 1):
        t[f"c{c}"] = rng.standard_normal(n_rows).astype(np.float32)
    return t


class Window:
    """Round bookkeeping shared by the scan sources and the commit clock.

    ``served`` is the round whose deltas were last handed out; the commit
    clock stamps every store commit with it. ``on_request(r)`` acts once
    per round, at its first delta request: after round
    ``first_window_round`` it raises ``Stop`` when the round would start at
    or after the deadline; otherwise it calls each of ``listeners`` with
    ``r`` (the harness opens the window, starts the profiler) and marks
    ``r`` served. A round starts where the one before it ended, at that
    round's last commit (``round_end``)."""

    def __init__(self, first_window_round: int = 2):
        self.first_window_round = first_window_round
        self.served = 0
        self.deadline: float | None = None
        self.round_end: Callable[[int], float | None] = lambda r: None
        self.listeners: list[Callable[[int], None]] = []
        self._lock = threading.Lock()

    def on_request(self, round_idx: int) -> None:
        with self._lock:
            if round_idx <= self.served:
                return
            if round_idx != self.served + 1:
                raise RuntimeError(
                    f"round {round_idx} requested after round {self.served}")
            prev_end = self.round_end(self.served)
            start = time.perf_counter() if prev_end is None else prev_end
            if (self.deadline is not None
                    and round_idx > self.first_window_round
                    and start >= self.deadline):
                raise Stop(f"round {round_idx} starts after the deadline")
            for listener in self.listeners:
                listener(round_idx)
            self.served = round_idx


class ScanSource:
    """One scan's rows: the initial load (round 0) and each round's Z-set
    delta, in ``realize_workload``'s layout (retractions, then updated rows,
    then inserts; an explicit ``weight`` column only when something is
    retracted)."""

    def __init__(self, node_idx: int, rows: int, n_cols: int, seed: int,
                 key_mod: int, key_probs: np.ndarray | None,
                 ingest: float, update: float, delete: float,
                 window: Window):
        self.i = node_idx
        self.rows = rows
        self.n_cols = n_cols
        self.seed = seed
        self.key_mod = key_mod
        self.key_probs = key_probs
        self.fracs = (ingest, update, delete)
        self.window = window
        self._initial: dict | None = None
        self.live: dict | None = None  # content after round ``live_round``
        self.live_round = 0
        self._pending = None  # (round, delta, change) served, not applied
        self._lock = threading.Lock()

    def _seed(self, j: int) -> int:
        return (self.seed * 1000 + self.i) * 1009 + j

    def initial(self) -> dict:
        with self._lock:
            if self._initial is None:
                self._initial = base_table(
                    self.rows, self.n_cols, self._seed(0), self.key_mod,
                    rid_base(0, self.i), self.key_probs)
                self.live = self._initial
            return self._initial

    def fn(self, inputs) -> dict:
        """The SCAN node's compute function (the initial build)."""
        return self.initial()

    def delta_fn(self, round_idx: int, spec=None) -> dict:
        """The SCAN node's ingestion: round ``round_idx``'s delta (round 0:
        the weightless initial load). ``spec`` is the program's UpdateSpec;
        the fractions come from the traffic file."""
        if round_idx == 0:
            return self.initial()
        self.window.on_request(round_idx)
        self.initial()
        with self._lock:
            if self._pending is not None and self._pending[0] == round_idx:
                return self._pending[1]
            if round_idx != self.live_round + 1 + (self._pending is not None):
                raise RuntimeError(
                    f"scan {self.i}: round {round_idx} out of order")
            self._apply_pending()
            delta, change = self._delta(round_idx)
            self._pending = (round_idx, delta, change)
            return delta

    def final(self) -> dict:
        """Live content after the last round served."""
        self.initial()
        with self._lock:
            self._apply_pending()
            return self.live

    def _apply_pending(self) -> None:
        if self._pending is None:
            return
        r, _, (del_idx, upd_idx, upd_rows, ins) = self._pending
        live = {k: np.array(v) for k, v in self.live.items()}
        for k in live:
            if upd_idx.size:
                live[k][upd_idx] = upd_rows[k]
        keep = np.ones(len(live["key"]), bool)
        keep[del_idx] = False
        self.live = {
            k: np.concatenate([v[keep]] + ([ins[k]] if ins else []))
            for k, v in live.items()
        }
        self.live_round = r
        self._pending = None

    def _delta(self, r: int):
        ingest, update, delete = self.fracs
        live = self.live
        rng = np.random.default_rng(self._seed(r) * 2 + 1)
        n_live = len(live["key"])
        n_del = int(n_live * delete)
        n_upd = int(n_live * update)
        perm = rng.permutation(n_live)
        del_idx = np.sort(perm[:n_del])
        upd_idx = np.sort(perm[n_del:n_del + n_upd])
        parts: list[dict] = []
        retract_idx = np.sort(np.concatenate([del_idx, upd_idx]))
        if retract_idx.size:
            t = {k: np.asarray(v)[retract_idx] for k, v in live.items()}
            t[WEIGHT] = np.full(retract_idx.size, -1, np.int64)
            parts.append(t)
        upd_rows: dict = {}
        if upd_idx.size:
            for col in live:
                if col == "key":
                    upd_rows[col] = (
                        rng.choice(self.key_mod, size=n_upd, p=self.key_probs)
                        if self.key_probs is not None
                        else rng.integers(0, self.key_mod, n_upd)
                    ).astype(np.int64)
                elif col == "rid":
                    upd_rows[col] = np.asarray(live["rid"])[upd_idx]
                else:
                    upd_rows[col] = rng.standard_normal(n_upd).astype(
                        np.float32)
            parts.append(dict(upd_rows))
        n_ins = max(int(self.rows * ingest), 1) if ingest > 0 else 0
        ins = None
        if n_ins:
            ins = base_table(n_ins, self.n_cols, self._seed(r), self.key_mod,
                             rid_base(r, self.i), self.key_probs)
            parts.append(dict(ins))
        change = (del_idx, upd_idx, upd_rows, ins)
        if not parts:
            return {k: np.asarray(v)[:0] for k, v in live.items()}, change
        if retract_idx.size:
            for p in parts:
                p.setdefault(WEIGHT, np.ones(len(p["key"]), np.int64))
        if len(parts) == 1:
            return parts[0], change
        return {k: np.concatenate([p[k] for p in parts])
                for k in parts[0]}, change


def make_sources(scan_nodes: list[int], config: dict, traffic: dict,
                 seed: int, window: Window,
                 key_order_seed: int = KEY_ORDER_SEED
                 ) -> dict[int, ScanSource]:
    """One source per scan node, sized as ``realize_workload`` sizes its
    roots: ``bytes_per_root / (8 * n_cols)`` rows over ``rows / 4`` keys,
    in the Zipf key order of ``key_order_seed``. With ``key_order_seed ==
    seed`` the rows are ``realize_workload``'s."""
    n_cols = int(config["n_cols"])
    rows = max(64, int(config["bytes_per_root"]) // (8 * n_cols))
    key_mod = max(rows // 4, 4)
    probs = zipf_probs(key_mod, float(config["key_skew"]), key_order_seed)
    return {
        i: ScanSource(i, rows, n_cols, seed, key_mod, probs,
                      float(traffic["ingest_frac"]),
                      float(traffic["update_frac"]),
                      float(traffic["delete_frac"]), window)
        for i in scan_nodes
    }
