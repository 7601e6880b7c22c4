"""The Z-set delta rule of a UNION whose inputs hold rows under one rid.

Two inputs that descend from one scan (``workloads.rid_origins``) can hold
the same rid with different payloads. After any sequence of rounds the
stored union must equal ``op_union`` of the new inputs bit for bit, in
stored row order, partition by partition:

* ``zset_union_delta`` against the plain union of the new inputs, over
  random rounds of one-branch retractions, updates of the first and of a
  later input, rids entering a branch, deletes from every branch and new
  rows, at P in {1, 2, 8};
* the pass-through rule (the union of the input deltas) fails the smallest
  such round, and stays the rule where the inputs share no rid;
* the engine, incremental against full recompute, on a DAG whose UNION
  inputs share rids, with the ``union.splice`` span and the
  ``union_regroup_rows`` counter.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import CostModel
from repro.mv import (
    DiskStore,
    MVNode,
    UpdateSpec,
    Workload,
    calibrate_sizes,
    realize_workload,
    run_partitioned_scenario,
    run_scenario,
    verify_partitioned_equivalence,
    verify_scenario_equivalence,
)
from repro.mv import tableops as T
from repro.mv.partition import partition_table
from repro.mv.workloads import rid_origins, union_shared_inputs
from repro.obs import trace as obs_trace
from repro.obs.metrics import METRICS

N_INPUTS = 3
KEYS = 12
KINDS = ("retract_one", "update_first", "update_later", "enter_branch",
         "delete_all", "new_row", "move_key")


def _table(rids, keys, vals):
    rids = np.asarray(rids, np.int64)
    order = np.argsort(rids, kind="stable")
    v = np.asarray(vals, np.float32).reshape(-1, 2)[order]
    return {"key": np.asarray(keys, np.int64)[order], "rid": rids[order],
            "c0": v[:, 0].copy(), "c1": v[:, 1].copy()}


def _union(tables):
    out = tables[0]
    for t in tables[1:]:
        out = T.op_union(out, t)
    return out


class Branches:
    """Three inputs over one rid space: each rid has one key (the scan
    row's), and each input holds it at most once, with its own payload."""

    def __init__(self, rng, n_rids=40):
        self.rng = rng
        self.next_rid = n_rids
        self.key = {r: int(rng.integers(KEYS)) for r in range(n_rids)}
        self.held = [
            {r: self._payload() for r in range(n_rids) if rng.random() < 0.6}
            for _ in range(N_INPUTS)
        ]

    def _payload(self):
        return tuple(self.rng.standard_normal(2).astype(np.float32))

    def tables(self):
        out = []
        for h in self.held:
            rids = sorted(h)
            out.append(_table(rids, [self.key[r] for r in rids],
                              [h[r] for r in rids]))
        return out

    def round(self, kinds):
        """Apply one round of changes; returns each input's Z-set delta."""
        rng = self.rng
        old = [dict(h) for h in self.held]
        old_key = dict(self.key)
        for kind in kinds:
            live = sorted(set().union(*self.held))
            if kind == "new_row":
                r = self.next_rid
                self.next_rid += 1
                self.key[r] = int(rng.integers(KEYS))
                for h in self.held:
                    if rng.random() < 0.7:
                        h[r] = self._payload()
                continue
            if not live:
                continue
            r = live[int(rng.integers(len(live)))]
            holders = [i for i, h in enumerate(self.held) if r in h]
            if kind == "retract_one":
                del self.held[holders[int(rng.integers(len(holders)))]][r]
            elif kind == "update_first" and 0 in holders:
                self.held[0][r] = self._payload()
            elif kind == "update_later" and holders[-1] > 0:
                self.held[holders[-1]][r] = self._payload()
            elif kind == "enter_branch" and len(holders) < N_INPUTS:
                free = [i for i in range(N_INPUTS) if i not in holders]
                self.held[free[int(rng.integers(len(free)))]][r] = \
                    self._payload()
            elif kind == "delete_all":
                for i in holders:
                    del self.held[i][r]
            elif kind == "move_key":
                self.key[r] = int(rng.integers(KEYS))
                for i in holders:
                    self.held[i][r] = self._payload()
        deltas = []
        for o, h in zip(old, self.held):
            gone = sorted(r for r in o
                          if r not in h or h[r] != o[r]
                          or self.key[r] != old_key[r])
            came = sorted(r for r in h
                          if r not in o or h[r] != o[r]
                          or self.key[r] != old_key[r])
            neg = T.with_weight(_table(gone, [old_key[r] for r in gone],
                                       [o[r] for r in gone]), -1)
            pos = T.with_weight(_table(came, [self.key[r] for r in came],
                                       [h[r] for r in came]), +1)
            deltas.append({k: np.concatenate([neg[k], pos[k]]) for k in neg})
        return deltas


def _check_rounds(rng, P, rounds):
    b = Branches(rng)
    stored = [_union(ts) for ts in zip(*(partition_table(t, P)
                                         for t in b.tables()))]
    for kinds in rounds:
        olds = [partition_table(t, P) for t in b.tables()]
        deltas = [partition_table(d, P) for d in b.round(kinds)]
        want = [_union(ts) for ts in zip(*(partition_table(t, P)
                                           for t in b.tables()))]
        for p in range(P):
            d = T.zset_union_delta([o[p] for o in olds],
                                   [d[p] for d in deltas], _union)
            stored[p] = T.apply_delta(stored[p], d)
            T.assert_tables_bitwise(stored[p], want[p], f"{kinds} p{p}")


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_union_delta_matches_the_union_of_the_new_inputs(data):
    seed = data.draw(st.integers(0, 2**32 - 1), label="seed")
    P = data.draw(st.sampled_from([1, 2, 8]), label="P")
    rounds = data.draw(st.lists(
        st.lists(st.sampled_from(KINDS), min_size=1, max_size=8),
        min_size=1, max_size=6), label="rounds")
    _check_rounds(np.random.default_rng(seed), P, rounds)


@pytest.mark.parametrize("P", [1, 2, 8])
@pytest.mark.parametrize("kind", KINDS)
def test_each_kind_of_change_alone(kind, P):
    _check_rounds(np.random.default_rng(7), P, [[kind] * 6] * 4)


def test_pass_through_fails_where_inputs_share_a_rid():
    """The smallest failing round: rid 5 is held by inputs 0 and 1 with
    different payloads, and input 1 alone retracts it. The union of the
    input deltas retracts the first copy, input 0's."""
    a = _table([5], [1], [(1.0, 1.0)])
    b = _table([5], [1], [(2.0, 2.0)])
    c = _table([9], [1], [(3.0, 3.0)])
    stored = _union([a, b, c])
    none = T.with_weight(_table([], [], []))
    deltas = [none, T.with_weight(b, -1), none]
    want = _union([a, _table([], [], []), c])
    passed = T.apply_delta(stored, _union(deltas))
    assert passed["c0"].tolist() == [2.0, 3.0]  # input 0's row is gone
    stats = {}
    got = T.apply_delta(stored, T.zset_union_delta([a, b, None], deltas,
                                                   _union, stats=stats))
    T.assert_tables_bitwise(got, want)
    assert stats["regroup_rows"] == 3  # two copies out, one back in


def test_reinsert_into_the_first_input_keeps_input_order():
    """Input 0 gains rid 5 that input 1 already holds: its copy goes
    first, where op_union puts it, not after input 1's."""
    a = _table([], [], [])
    b = _table([5], [1], [(2.0, 2.0)])
    stored = _union([a, b])
    new_a = _table([5], [1], [(1.0, 1.0)])
    deltas = [T.with_weight(new_a), T.with_weight(_table([], [], []))]
    assert T.apply_delta(stored, _union(deltas))["c0"].tolist() == [2.0, 1.0]
    got = T.apply_delta(stored, T.zset_union_delta([a, b], deltas, _union))
    T.assert_tables_bitwise(got, _union([new_a, b]))


@pytest.mark.parametrize("weighted", [False, True])
def test_inputs_that_share_no_rid_emit_the_pass_through_delta(weighted):
    rng = np.random.default_rng(3)
    ins = [T.make_base_table(30, 4, seed=i, key_mod=8,
                             rid_base=T.make_rid_base(0, i))
           for i in range(N_INPUTS)]
    deltas = []
    for i, t in enumerate(ins):
        new = T.make_base_table(5, 4, seed=10 + i, key_mod=8,
                                rid_base=T.make_rid_base(1, i))
        if weighted:
            gone = T.with_weight(T.take_rows(t, rng.choice(30, 4, False)), -1)
            new = {k: np.concatenate([gone[k], T.with_weight(new)[k]])
                   for k in gone}
        deltas.append(new)
    stats = {}
    got = T.zset_union_delta(ins, deltas, _union, stats=stats)
    T.assert_tables_bitwise(got, _union(deltas))
    assert stats == {"regroup_rows": 0, "regroup_bytes": 0}


def test_rid_origins_and_shared_inputs():
    nodes = [MVNode(f"mv{i}", p, op, 1.0, 0.0) for i, (p, op) in enumerate([
        ((), "SCAN"), ((), "SCAN"), ((0, 1), "JOIN"), ((0,), "FILTER"),
        ((1,), "AGG"), ((2, 3, 1), "UNION"), ((1, 4), "UNION"),
        ((5,), "UNION"),
    ])]
    origins = rid_origins(nodes)
    assert origins[2] == origins[3] == {0}
    assert origins[4] == frozenset()
    assert origins[5] == {0, 1} and origins[7] == {0, 1}
    assert union_shared_inputs(origins, nodes[5].parents) == (0, 1)
    assert union_shared_inputs(origins, nodes[6].parents) == ()


# mv3 and mv4 carry mv0's rids with different right payloads; mv5 carries
# mv1's; mv7 reads the union's delta downstream
SHARED_DAG = [((), "SCAN"), ((), "SCAN"), ((), "SCAN"), ((0, 1), "JOIN"),
              ((0, 2), "JOIN"), ((1, 2), "JOIN"), ((3, 4, 5), "UNION"),
              ((6,), "FILTER")]
CM = CostModel(disk_read_bw=50e6, disk_write_bw=50e6, mem_read_bw=1e12,
               mem_write_bw=1e12, disk_latency=0.0)


def _shared_workload(tmp_path):
    nodes = [MVNode(f"mv{i}", p, op, 1e5, 0.0)
             for i, (p, op) in enumerate(SHARED_DAG)]
    wl = realize_workload(Workload("shared_union", nodes),
                          bytes_per_root=1 << 14, seed=5)
    return calibrate_sizes(wl, DiskStore(tmp_path / "calib"))


SPEC = dict(ingest_frac=0.05, update_frac=0.05, delete_frac=0.05,
            n_rounds=4)


def test_engine_regroups_shared_rids_bitwise(tmp_path):
    """Incremental refresh of a UNION whose inputs share rids equals full
    recompute after every scenario; on the pass-through rule it did not."""
    wl = _shared_workload(tmp_path)
    budget = sum(n.size for n in wl.nodes) * 0.3
    stores = {}
    obs_trace.enable(True)
    obs_trace.clear()
    METRICS.clear()
    try:
        for mode in ("incremental", "full"):
            stores[mode] = DiskStore(tmp_path / mode)
            run_scenario(wl, stores[mode], budget, UpdateSpec(mode=mode,
                                                              **SPEC), CM)
            if mode == "incremental":
                splices = [s for s in obs_trace.spans()
                           if s.cat == "union.splice"]
                regrouped = METRICS.counter_value("union_regroup_rows",
                                                  "mv6")
    finally:
        obs_trace.enable(False)
        obs_trace.clear()
        METRICS.clear()
    verify_scenario_equivalence(wl, stores["incremental"], stores["full"])
    assert splices and all(s.name == "mv6" for s in splices)
    assert regrouped > 0 and any(s.nbytes > 0 for s in splices)


@pytest.mark.parametrize("P", [2, 8])
def test_partitioned_engine_regroups_shared_rids_bitwise(tmp_path, P):
    wl = _shared_workload(tmp_path)
    budget = sum(n.size for n in wl.nodes) * 0.3
    ref = DiskStore(tmp_path / "full")
    run_scenario(wl, ref, budget, UpdateSpec(mode="full", **SPEC), CM)
    part = DiskStore(tmp_path / "part")
    run_partitioned_scenario(wl, P, part, budget,
                             UpdateSpec(mode="incremental", **SPEC), CM,
                             n_compute_workers=2)
    verify_partitioned_equivalence(wl, part, P, ref)
