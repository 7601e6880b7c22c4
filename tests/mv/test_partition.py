"""Partitioned MVs (DESIGN.md §7): hash partitioning, partition-granular
planning/storage/catalog, dirty-partition pruning, and the acceptance matrix.

* partitioned == unpartitioned, bitwise: every operator run per partition
  and reassembled in canonical rid order equals unpartitioned execution,
  over random operator chains and over full multi-round refresh scenarios
  ((3 generated DAGs + the five Table III workloads) x update kinds x
  P in {1,2,8} x k in {1,4});
* Z-set deltas route to exactly the partitions their keys hash to, so
  UPDATE/DELETE rounds touch only dirty partitions (clean ones are pruned);
* the planner scores fractional residency: P=1 degenerates to the whole-MV
  plan, and any partition-level plan fits the budget under every k-worker
  interleaving;
* per-partition part-file groups commit atomically at the manifest, and the
  Memory Catalog admits/releases partitions independently.
"""
import functools
import shutil
import tempfile
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import CostModel, serial_plan, solve, solve_partitioned
from repro.core.speedup import partition_shares
from repro.mv import (
    PAPER_WORKLOAD_SPECS,
    DiskStore,
    MemoryCatalog,
    UpdateSpec,
    concat_partitions,
    dirty_partitions,
    generate_workload,
    paper_workloads,
    partition_entry_name,
    partition_of,
    partition_table,
    partition_workload,
    realize_workload,
    run_partitioned_scenario,
    run_scenario,
    verify_partitioned_equivalence,
    verify_scenario_equivalence,
)
from repro.mv import tableops as T
from repro.mv.engine import simulate_events
from repro.mv.partition import canonical_order
# imported here, not inside a test: the module defines @given tests, and
# importing it while a hypothesis test runs nests them
from tests.mv.test_tableops_delta import zset_delta

CM = CostModel(
    disk_read_bw=50e6,
    disk_write_bw=50e6,
    mem_read_bw=1e12,
    mem_write_bw=1e12,
    disk_latency=0.0,
)


def assert_bitwise(a, b, ctx=""):
    assert set(a) == set(b), (ctx, sorted(a), sorted(b))
    for col in a:
        va, vb = np.asarray(a[col]), np.asarray(b[col])
        assert va.dtype == vb.dtype and va.shape == vb.shape, (ctx, col)
        assert va.tobytes() == vb.tobytes(), f"{ctx}: column {col} differs"


# ---------------------------------------------------------------------------
# tableops: partitioned execution equivalence
# ---------------------------------------------------------------------------

def test_partition_roundtrip_is_rid_stable():
    t = T.make_base_table(500, 4, seed=1, key_mod=40,
                          rid_base=T.make_rid_base(0, 0))
    for P in (1, 2, 8):
        parts = partition_table(t, P)
        assert len(parts) == P
        assert sum(len(p["key"]) for p in parts) == 500
        # row order inside each partition is the original (rid) order
        for p in parts:
            assert (np.diff(p["rid"]) > 0).all()
        assert_bitwise(concat_partitions(parts), t, f"P={P}")
    # the hash is deterministic and key-pure
    pid = partition_of(t["key"], 8)
    assert (pid == partition_of(t["key"].copy(), 8)).all()
    assert (pid >= 0).all() and (pid < 8).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1, 2, 8]),
       st.integers(0, 2), st.integers(0, 2))
def test_partitioned_op_chains_bitwise(seed, P, o1, o2):
    """Random FILTER/MAP/PROJECT chains capped by JOIN / AGG / UNION: per-
    partition execution reassembled in canonical order is bitwise-identical
    to unpartitioned execution."""
    rowwise = [
        lambda t: T.op_filter(t, threshold=-0.2),
        T.op_map,
        lambda t: T.op_project(t, keep_frac=0.7),
    ]
    chain = [rowwise[o1], rowwise[o2]]

    def run_chain(t):
        for op in chain:
            t = op(t)
        return t

    left = T.make_base_table(300, 4, seed=seed, key_mod=24,
                             rid_base=T.make_rid_base(0, 0))
    right = T.make_base_table(200, 4, seed=seed + 1, key_mod=24,
                              rid_base=T.make_rid_base(0, 1))
    lp = [run_chain(p) for p in partition_table(left, P)]
    rp = partition_table(right, P)
    full_left = run_chain(left)
    assert_bitwise(concat_partitions(lp), full_left, "chain")
    # co-partitioned JOIN
    assert_bitwise(
        concat_partitions([T.op_join(a, b) for a, b in zip(lp, rp)]),
        T.op_join(full_left, right),
        "join",
    )
    # AGG: disjoint key groups per partition, canonical key order
    assert_bitwise(
        concat_partitions([T.op_agg(p) for p in lp]),
        canonical_order(T.op_agg(full_left)),
        "agg",
    )
    # co-partitioned UNION keeps the canonical rid order
    assert_bitwise(
        concat_partitions(
            [T.op_union(a, b) for a, b in zip(lp, rp)]
        ),
        T.op_union(full_left, right),
        "union",
    )


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([2, 8]))
def test_zset_delta_routes_to_dirty_partitions_only(seed, P):
    """A Z-set delta routes every row to the partition its key hashes to
    (retractions carry the old key, so they land on their victim's
    partition); applying routed deltas per partition equals applying the
    whole delta, and partitions outside ``dirty_partitions`` receive no
    rows."""
    old = T.make_base_table(200, 4, seed=seed, key_mod=16,
                            rid_base=T.make_rid_base(0, 0))
    delta = zset_delta(old, seed + 5, n_ins=12, n_upd=10, n_del=8)
    old_p = partition_table(old, P)
    delta_p = partition_table(delta, P)
    dirty = set(dirty_partitions(delta, P))
    for p in range(P):
        routed = delta_p[p]
        if p not in dirty:
            assert T.n_rows(routed) == 0
        # every retraction's victim rid lives in this partition's old rows
        w = T.weights_of(routed)
        victim = np.asarray(routed["rid"])[w < 0]
        assert np.isin(victim, old_p[p]["rid"]).all()
    assert_bitwise(
        concat_partitions(
            [T.apply_delta(o, d) for o, d in zip(old_p, delta_p)]
        ),
        T.apply_delta(old, delta),
        "routed apply",
    )


# ---------------------------------------------------------------------------
# workload expansion + planner (fractional residency)
# ---------------------------------------------------------------------------

def test_partition_workload_structure_and_degenerate_p1():
    wl = generate_workload(12, seed=4)
    pwl1, pmap1 = partition_workload(wl, 1)
    assert pwl1 is wl and pmap1.n_partitions == 1
    shares = partition_shares(4, skew=1.0, seed=2)
    pwl, pmap = partition_workload(wl, 4, shares=shares)
    assert pwl.n == wl.n * 4
    for v, node in enumerate(wl.nodes):
        for p in range(4):
            e = pwl.nodes[pmap.expanded_index(v, p)]
            assert e.name == partition_entry_name(node.name, p)
            assert e.op == node.op
            # co-partitioned edges: same partition of every parent
            assert e.parents == tuple(
                pmap.expanded_index(q, p) for q in node.parents
            )
            assert e.size == pytest.approx(node.size * shares[p])
        assert sum(
            pwl.nodes[pmap.expanded_index(v, p)].size for p in range(4)
        ) == pytest.approx(node.size)


def test_solve_partitioned_p1_degenerates_to_whole_mv_plan():
    wl = generate_workload(16, seed=6)
    g = wl.to_graph(CM)
    budget = sum(n.size for n in wl.nodes) * 0.1
    whole = solve(g, budget=budget)
    part = solve_partitioned(g, budget, 1)
    assert part.n_partitions == 1
    assert part.plan.flagged == whole.flagged
    assert part.plan.order == whole.order
    assert part.flagged_partitions == {(v, 0) for v in whole.flagged}


def test_solve_partitioned_pins_partitions_of_overbudget_mv():
    """Fractional residency: an MV larger than the whole budget is excluded
    by the whole-MV planner but contributes the partitions that fit."""
    wl = generate_workload(14, seed=9)
    g = wl.to_graph(CM)
    children = [0] * wl.n
    for a, _ in wl.edges():
        children[a] += 1
    hot = max(
        (v for v in range(wl.n) if children[v]),
        key=lambda v: children[v] * wl.nodes[v].size,
    )
    budget = wl.nodes[hot].size * 0.6
    whole = solve_partitioned(g, budget, 1, cost_model=CM)
    assert all(v != hot for v, _ in whole.flagged_partitions)
    part = solve_partitioned(g, budget, 8, cost_model=CM)
    hot_frac = part.residency_fraction(hot)
    assert 0.0 < hot_frac <= 1.0
    assert part.plan.score > 0.0


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([2, 4, 8]),
       st.sampled_from([1, 4]))
def test_partition_plan_budget_feasible_under_every_interleaving(seed, P, k):
    """Acceptance property: any partition-level plan fits the budget under
    every k-worker interleaving — both by the graph's worst-case windowed
    residency accounting and in the event-driven engine's execution."""
    wl = generate_workload(10 + seed % 6, seed=seed)
    budget = sum(n.size for n in wl.nodes) * 0.15
    shares = partition_shares(P, skew=1.0, seed=seed)
    pwl, _ = partition_workload(wl, P, shares=shares)
    g = pwl.to_graph(CM)
    plan = solve(g, budget=budget, n_workers=k)
    assert g.is_feasible(plan.flagged, plan.order, budget, k)
    sim = simulate_events(pwl, plan, CM, mode="sc", n_workers=k)
    assert sim.peak_catalog_bytes <= budget + 1e-6


def test_partition_parallel_refresh_of_single_wide_mv():
    """A chain workload has no inter-MV parallelism: with P=8 the engine
    still refreshes each wide MV data-parallel across k workers, beating
    the k=1 wall clock."""
    from repro.mv import MVNode, Workload

    nodes = [
        MVNode("mv0", (), "SCAN", 8e8, 8.0, base_read=8e8),
        MVNode("mv1", (0,), "FILTER", 6e8, 6.0),
        MVNode("mv2", (1,), "MAP", 6e8, 6.0),
        MVNode("mv3", (2,), "AGG", 1e8, 4.0),
    ]
    wl = Workload("chain", nodes)
    pwl, _ = partition_workload(wl, 8)
    g = pwl.to_graph(CM)
    t1 = simulate_events(pwl, serial_plan(g), CM, mode="serial",
                         n_workers=1).end_to_end
    t4 = simulate_events(pwl, serial_plan(g), CM, mode="serial",
                         n_workers=4).end_to_end
    assert t4 < 0.5 * t1
    # partitions of one MV genuinely overlap in time
    sim = simulate_events(pwl, serial_plan(g), CM, mode="serial", n_workers=4)
    spans = {}
    for name, start, end in sim.timeline:
        spans.setdefault(name.rsplit("@p", 1)[0], []).append((start, end))
    overlapping = any(
        any(s2 < e1 for (_, e1), (s2, _) in zip(sp, sp[1:]))
        for sp in (sorted(v) for v in spans.values())
    )
    assert overlapping


# ---------------------------------------------------------------------------
# storage + catalog at partition granularity
# ---------------------------------------------------------------------------

def test_partition_store_groups_and_manifest(tmp_path):
    store = DiskStore(tmp_path)
    t = T.make_base_table(64, 3, seed=0, key_mod=8,
                          rid_base=T.make_rid_base(0, 0))
    parts = partition_table(t, 4)
    for p, pt in enumerate(parts):
        store.write_partition("mv", p, pt)
    assert store.partition_ids("mv") == [0, 1, 2, 3]
    pm = store.partition_manifest("mv")
    assert set(pm) == {0, 1, 2, 3}
    assert all(pm[p] > 0 for p in pm if len(parts[p]["key"]))
    assert_bitwise(store.read_partitioned("mv"), t)
    # per-partition append: only partition 2's group grows
    delta = T.make_base_table(8, 3, seed=9, key_mod=8,
                              rid_base=T.make_rid_base(1, 0))
    routed = partition_table(delta, 4)
    store.append_partition("mv", 2, routed[2])
    assert store.parts(partition_entry_name("mv", 2)) == 2
    assert store.parts(partition_entry_name("mv", 1)) == 1


def test_partition_manifest_commit_is_crash_atomic(tmp_path):
    """A partition rewrite that crashes before its manifest commit leaves
    that partition's old content (and every sibling partition) intact —
    partition commits are independent."""
    store = DiskStore(tmp_path)
    t = T.make_base_table(64, 3, seed=1, key_mod=8,
                          rid_base=T.make_rid_base(0, 0))
    parts = partition_table(t, 4)
    for p, pt in enumerate(parts):
        store.write_partition("mv", p, pt)
    # simulated crash mid-rewrite of partition 2: the new part file lands on
    # an unreferenced id, the process dies before _record
    pname = partition_entry_name("mv", 2)
    new_id = max(store._part_ids(pname)) + 1
    store._write_part(pname, new_id, {"key": np.zeros(1, np.int64)})
    fresh = DiskStore(tmp_path)
    assert_bitwise(fresh.read_partitioned("mv"), t)
    assert fresh.partition_ids("mv") == [0, 1, 2, 3]
    # the next real write of that partition commits cleanly over the orphan
    fresh.write_partition("mv", 2, parts[2])
    assert_bitwise(fresh.read_partitioned("mv"), t)


def test_catalog_partition_granular_accounting():
    cat = MemoryCatalog(100.0)
    cat.put(partition_entry_name("mv1", 0), object(), 30.0)
    cat.put(partition_entry_name("mv1", 1), object(), 20.0)
    cat.put(partition_entry_name("mv10", 0), object(), 7.0)  # prefix decoy
    cat.put("other", object(), 10.0)
    assert cat.used_bytes == 67.0
    assert cat.used_bytes_for("mv1") == 50.0  # mv10's partitions excluded
    assert cat.used_bytes_for("mv10") == 7.0
    assert cat.entry_bytes(partition_entry_name("mv1", 1)) == 20.0
    # partitions admit/release independently
    cat.release(partition_entry_name("mv1", 0))
    assert cat.used_bytes_for("mv1") == 20.0
    assert partition_entry_name("mv1", 1) in cat
    assert set(cat.resident()) == {
        partition_entry_name("mv1", 1), partition_entry_name("mv10", 0),
        "other",
    }


# ---------------------------------------------------------------------------
# end-to-end scenarios: the acceptance matrix
# ---------------------------------------------------------------------------

KINDS = {
    "insert": dict(ingest_frac=0.25, n_rounds=2),
    "mixed": dict(ingest_frac=0.15, update_frac=0.15, delete_frac=0.1,
                  n_rounds=2),
}


# the scenario matrix's workloads: three generated 8-MV DAGs (by seed) and
# the five Table III workloads, each realized at 4 KiB per root
SCENARIO_WORKLOADS = (
    "seed3", "seed11", "seed2026",
    *(name for name, *_ in PAPER_WORKLOAD_SPECS),
)


@functools.lru_cache(maxsize=None)
def scenario_workload(name):
    """The realized workload ``name`` and its catalog budget (40% of its
    bytes), built once per module."""
    if name.startswith("seed"):
        wl = generate_workload(8, seed=int(name[len("seed"):]))
        wl = realize_workload(wl, bytes_per_root=1 << 12)
    else:
        wi = [n for n, *_ in PAPER_WORKLOAD_SPECS].index(name)
        wl = realize_workload(paper_workloads(100.0)[wi],
                              bytes_per_root=1 << 12, seed=wi)
    return wl, sum(n.size for n in wl.nodes) * 0.4


@pytest.fixture(scope="module")
def full_recompute():
    """The unpartitioned ``mode="full"`` reference store of a (workload,
    kind), run once per module and shared by every P x k case."""
    root = Path(tempfile.mkdtemp(prefix="part_ref_"))
    refs = {}

    def get(name, kind):
        if (name, kind) not in refs:
            wl, budget = scenario_workload(name)
            ref = DiskStore(root / f"{name}_{kind}")
            run_scenario(wl, ref, budget,
                         UpdateSpec(mode="full", **KINDS[kind]), CM)
            refs[name, kind] = ref
        return refs[name, kind]

    yield get
    shutil.rmtree(root, ignore_errors=True)


# acceptance: partitioned refresh output is bitwise-identical to the
# unpartitioned full recompute across workloads x update kinds (insert-only
# and mixed insert/update/delete) x P in {1,2,8} x k in {1,4}
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("P", [1, 2, 8])
@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("workload", SCENARIO_WORKLOADS)
def test_scenario_matrix_partitioned_bitwise_vs_full_recompute(
        workload, kind, P, k, full_recompute):
    wl, budget = scenario_workload(workload)
    kw = KINDS[kind]
    ref = full_recompute(workload, kind)
    tmp_path = Path(tempfile.mkdtemp(prefix=f"part_{workload}_"))
    try:
        store = DiskStore(tmp_path / f"{kind}_p{P}k{k}")
        rep = run_partitioned_scenario(
            wl, P, store, budget,
            UpdateSpec(mode="incremental", **kw), CM,
            n_compute_workers=k,
        )
        assert len(rep.rounds) == kw["n_rounds"] + 1
        if P == 1:
            verify_scenario_equivalence(wl, store, ref)
        else:
            verify_partitioned_equivalence(wl, store, P, ref)
        assert all(
            r.run.peak_catalog_bytes <= budget + 1e-9 for r in rep.rounds
        ), (workload, kind, P, k)
    finally:
        shutil.rmtree(tmp_path, ignore_errors=True)


def test_hierarchical_round_planner_bitwise_and_feasible(tmp_path):
    """The per-round hierarchical solver (``planner="hierarchical"``, forced
    below the flat threshold) must leave the refresh output bitwise
    identical to the unpartitioned full recompute — plans change which
    partitions are pinned, never what is computed — and every round's plan
    must stay budget-feasible at the engine's worker count."""
    wl = realize_workload(
        generate_workload(8, seed=7), bytes_per_root=1 << 12, key_skew=1.2,
        seed=7,
    )
    budget = sum(n.size for n in wl.nodes) * 0.4
    spec_kw = dict(ingest_frac=0.15, update_frac=0.1, delete_frac=0.05,
                   n_rounds=2)
    ref = DiskStore(tmp_path / "ref")
    run_scenario(wl, ref, budget, UpdateSpec(mode="full", **spec_kw), CM)
    for P, k in ((4, 1), (8, 2)):
        store = DiskStore(tmp_path / f"h_p{P}k{k}")
        rep = run_partitioned_scenario(
            wl, P, store, budget, UpdateSpec(mode="incremental", **spec_kw),
            CM, n_compute_workers=k, planner="hierarchical",
        )
        verify_partitioned_equivalence(wl, store, P, ref)
        for r in rep.rounds:
            assert r.plan.n_workers == k
            assert r.run.peak_catalog_bytes <= budget + 1e-9, (P, k, r.round_idx)
        # the solver actually engaged partition granularity somewhere
        assert any(
            "@p" in rep.workload.nodes[v].name
            for r in rep.rounds for v in r.plan.flagged
        )


def test_hierarchical_auto_planner_matches_flat_on_small_rounds(tmp_path):
    """``planner="auto"`` falls back to the flat exact solve below the n·P
    threshold, so small scenarios produce the identical plans (and bytes)
    as ``planner="flat"``."""
    wl = realize_workload(generate_workload(6, seed=21), bytes_per_root=1 << 12)
    budget = sum(n.size for n in wl.nodes) * 0.4
    spec = UpdateSpec(mode="incremental", ingest_frac=0.2, n_rounds=1)
    reps = {}
    for planner in ("auto", "flat"):
        store = DiskStore(tmp_path / planner)
        reps[planner] = run_partitioned_scenario(
            wl, 4, store, budget, spec, CM, planner=planner
        )
    for ra, rf in zip(reps["auto"].rounds, reps["flat"].rounds):
        assert ra.plan.order == rf.plan.order
        assert ra.plan.flagged == rf.plan.flagged


def test_skewed_keys_give_uneven_partitions_on_real_executor(tmp_path):
    """``realize_workload(key_skew=...)``: the real executor's partition
    sizes follow the Zipf key population — hot partitions carry a
    multiple of the cold ones — and the skewed scenario still refreshes
    bitwise-identically to the unpartitioned full recompute."""
    P = 8
    wl = realize_workload(
        generate_workload(6, seed=17), bytes_per_root=1 << 13, seed=17,
        key_skew=1.3,
    )
    scan = next(n for n in wl.nodes if not n.parents)
    rows = [len(p["key"]) for p in partition_table(scan.delta_fn(0, 0.1), P)]
    assert max(rows) >= 3 * max(min(rows), 1), f"no skew: {rows}"
    budget = sum(n.size for n in wl.nodes) * 0.4
    spec_kw = dict(ingest_frac=0.2, n_rounds=2)
    ref = DiskStore(tmp_path / "ref")
    run_scenario(wl, ref, budget, UpdateSpec(mode="full", **spec_kw), CM)
    store = DiskStore(tmp_path / "skew")
    rep = run_partitioned_scenario(
        wl, P, store, budget, UpdateSpec(mode="incremental", **spec_kw), CM
    )
    verify_partitioned_equivalence(wl, store, P, ref)
    # stored partition groups are genuinely uneven
    sizes = [
        store.manifest().get(partition_entry_name(scan.name, p), 0.0)
        for p in range(P)
    ]
    assert max(sizes) >= 2.5 * max(min(sizes), 1.0), sizes


def test_clean_partitions_are_pruned_per_round(tmp_path):
    """Dirty-partition pruning: with P=8 and a small per-round delta, the
    partitions whose keys receive no rows are skipped (never dispatched)
    while the MV as a whole still refreshes."""
    wl = realize_workload(
        generate_workload(6, seed=13), bytes_per_root=1 << 12, key_mod=12
    )
    budget = sum(n.size for n in wl.nodes) * 0.5
    P = 8
    spec = UpdateSpec(mode="incremental", ingest_frac=0.02, n_rounds=2)
    rep = run_partitioned_scenario(
        wl, P, DiskStore(tmp_path / "s"), budget, spec, CM
    )
    scan = next(i for i, n in enumerate(wl.nodes) if not n.parents)
    scan_name = wl.nodes[scan].name
    pruned = refreshed = 0
    for r in rep.rounds[1:]:
        delta = wl.nodes[scan].delta_fn(r.round_idx, spec)
        dirty = set(dirty_partitions(delta, P))
        clean = {
            partition_entry_name(scan_name, p)
            for p in range(P)
            if p not in dirty
        }
        assert clean <= set(r.run.skipped), "clean partitions must be skipped"
        pruned += len(clean)
        refreshed += sum(
            1 for name, s in r.statuses.items()
            if name.startswith(scan_name + "@p") and s != "static"
        )
    # with a 2% ingest and 12 distinct keys, both sets must be non-trivial
    assert pruned > 0 and refreshed > 0


def test_partitioned_scenario_flags_partitions_in_catalog(tmp_path):
    """Partition-granular residency in the real engine: catalog entries are
    per-partition names, admitted and released independently."""
    wl = realize_workload(generate_workload(8, seed=5), bytes_per_root=1 << 13)
    budget = sum(n.size for n in wl.nodes) * 0.5
    spec = UpdateSpec(mode="incremental", ingest_frac=0.3, n_rounds=1)
    rep = run_partitioned_scenario(
        wl, 4, DiskStore(tmp_path / "s"), budget, spec, CM
    )
    build = rep.rounds[0]
    assert build.run.catalog_hits > 0
    flagged_names = {
        rep.workload.nodes[v].name for v in build.plan.flagged
    }
    assert any("@p" in n for n in flagged_names)
