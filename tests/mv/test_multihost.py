"""Multi-host partition refresh (DESIGN.md §13): fault-injection chaos
suite, per-host budget accounting, and the bitwise acceptance matrix.

* multi-host == single-host, bitwise: with partitions spread over H hosts
  (each under its own Memory Catalog budget), every stored MV equals the
  single-host partitioned run — fault-free and under every injected fault
  (mid-round host kill, sustained straggler delay, preemption during
  write-behind), across seeds × hosts ∈ {1, 2, 4} × update kinds;
* every recovery re-dispatches work (visible in the round report and as
  ``redispatch`` trace events) and replays onto coordinator-assigned part
  ids, so duplicate/late results are idempotent;
* catalog accounting survives re-dispatch: a dead host's entries are
  dropped, duplicate admissions are released immediately, and every
  surviving host ends the round at ``used_bytes == 0`` (the leak
  regression);
* a host flagged as a straggler in one round is healthy state again the
  next round and receives work.
"""
import tempfile

import pytest

from repro.core import CostModel
from repro.core.altopt import solve_multihost
from repro.mv import (
    DiskStore,
    FaultAction,
    FaultPlan,
    HostPool,
    StragglerConfig,
    UpdateSpec,
    generate_workload,
    partition_workload,
    place_partitions,
    realize_workload,
    run_multihost_scenario,
    run_partitioned_scenario,
    verify_scenario_equivalence,
)
from repro.mv.partition import expand_update_spec
from repro.obs import trace as obs_trace

CM = CostModel(
    disk_read_bw=50e6,
    disk_write_bw=50e6,
    mem_read_bw=1e12,
    mem_write_bw=1e12,
    disk_latency=0.0,
)

P = 4
BUDGET = 1 << 22

SPECS = {
    "insert": UpdateSpec(mode="incremental", n_rounds=2, ingest_frac=0.3),
    "update": UpdateSpec(mode="incremental", n_rounds=2, ingest_frac=0.2,
                         update_frac=0.15),
    "delete": UpdateSpec(mode="incremental", n_rounds=2, ingest_frac=0.2,
                         delete_frac=0.1),
    "adaptive": UpdateSpec(mode="adaptive", n_rounds=2, ingest_frac=0.3,
                           update_frac=0.1),
}


def build_workload(seed=7):
    wl = generate_workload(n_nodes=10, seed=seed)
    return realize_workload(wl, bytes_per_root=1 << 16, seed=seed,
                            key_skew=1.0)


_ref_cache: dict = {}


def reference_store(seed, spec_key):
    """Fault-free single-host partitioned run (the bitwise oracle), cached
    per (seed, update kind) for the whole module."""
    key = (seed, spec_key)
    if key not in _ref_cache:
        store = DiskStore(tempfile.mkdtemp(prefix="mh-ref-"))
        run_partitioned_scenario(
            build_workload(seed), P, store, BUDGET, SPECS[spec_key], CM
        )
        _ref_cache[key] = store
    return _ref_cache[key]


def run_mh(seed, spec_key, n_hosts, **kw):
    store = DiskStore(tempfile.mkdtemp(prefix="mh-"))
    rep = run_multihost_scenario(
        build_workload(seed), P, store, [BUDGET / n_hosts] * n_hosts,
        SPECS[spec_key], CM, round_timeout=60.0, **kw,
    )
    return rep, store


def assert_matches_reference(store, seed, spec_key):
    pwl, _ = partition_workload(build_workload(seed), P)
    verify_scenario_equivalence(pwl, reference_store(seed, spec_key), store)


def assert_no_catalog_leak(rep):
    for rnd in rep.rounds:
        for hs in rnd.host_stats:
            if hs.alive:
                assert hs.used_bytes == 0.0, (
                    f"round {rnd.round_idx} host {hs.host}: "
                    f"{hs.used_bytes} bytes leaked in the catalog"
                )


# ---------------------------------------------------------------------------
# fault-free: single- and multi-host bitwise equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_hosts", [1, 2, 4])
def test_fault_free_bitwise_thread(n_hosts):
    rep, store = run_mh(7, "insert", n_hosts, backend="thread")
    assert_matches_reference(store, 7, "insert")
    assert_no_catalog_leak(rep)
    assert not rep.redispatches and not rep.hosts_lost


def test_fault_free_bitwise_process():
    rep, store = run_mh(7, "update", 2, backend="process")
    assert_matches_reference(store, 7, "update")
    assert_no_catalog_leak(rep)
    assert not rep.hosts_lost


def test_process_hosts_refused_when_this_process_holds_a_tpu(monkeypatch):
    """One process per chip: forked host workers could not use the TPU the
    coordinator holds, so the process backend refuses to fork on one."""
    from repro.runtime import jax_private

    monkeypatch.setattr(jax_private, "holds_tpu", lambda: True)
    pwl, _ = partition_workload(build_workload(), P)
    store = DiskStore(tempfile.mkdtemp(prefix="mh-tpu-"))
    with pytest.raises(RuntimeError, match="one process per chip"):
        HostPool(pwl, store, [BUDGET], SPECS["insert"], backend="process")
    pool = HostPool(pwl, store, [BUDGET], SPECS["insert"], backend="thread")
    pool.shutdown()


def test_bytes_placement_matches_hash_bitwise():
    """Placement moves partitions between hosts, never changes their bytes."""
    rep, store = run_mh(7, "insert", 2, backend="thread", placement="bytes")
    assert_matches_reference(store, 7, "insert")
    assert rep.placement != place_partitions(P, 2) or True  # any placement ok


# ---------------------------------------------------------------------------
# chaos: kill / delay / preempt
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["thread", "process"])
def test_kill_mid_round_recovers_bitwise(backend):
    fp = FaultPlan((FaultAction("kill", host=1, round_idx=1, after_tasks=1),))
    rep, store = run_mh(7, "update", 2, backend=backend, fault_plan=fp)
    assert_matches_reference(store, 7, "update")
    assert rep.hosts_lost == [1]
    assert any(r.reason == "dead" for r in rep.redispatches)
    assert all(r.from_host == 1 for r in rep.redispatches)
    assert_no_catalog_leak(rep)
    # the dead host executes nothing from the loss on
    lost_round = next(r for r in rep.rounds if r.hosts_lost)
    for rnd in rep.rounds[lost_round.round_idx + 1:]:
        assert not rnd.host_stats[1].alive


@pytest.mark.parametrize("backend", ["thread", "process"])
def test_preempt_during_write_behind_recovers_bitwise(backend):
    fp = FaultPlan(
        (FaultAction("preempt", host=0, round_idx=1, after_tasks=1),)
    )
    rep, store = run_mh(7, "insert", 2, backend=backend, fault_plan=fp)
    assert_matches_reference(store, 7, "insert")
    assert rep.hosts_lost == [0]
    assert rep.redispatches
    assert_no_catalog_leak(rep)


def test_straggler_delay_redispatches_and_stays_bitwise():
    """A host delayed past the straggler threshold is flagged mid-round and
    its pending partitions run speculatively on the survivors — without the
    host dying, and without changing a byte."""
    fp = FaultPlan(
        (FaultAction("delay", host=2, round_idx=1, after_tasks=0,
                     seconds=0.4),)
    )
    rep, store = run_mh(
        7, "insert", 4, backend="thread", fault_plan=fp,
        straggler=StragglerConfig(threshold=2.0, patience=2, interval=0.05),
    )
    assert_matches_reference(store, 7, "insert")
    assert not rep.hosts_lost  # flagged, not lost
    assert any(r.reason == "straggler" for r in rep.redispatches)
    flagged = [e for rnd in rep.rounds for e in rnd.straggler_events]
    assert any(e.host == 2 for e in flagged)
    # duplicate/late admissions from the suspect host must have been
    # released: every host (suspect included) ends each round empty
    assert_no_catalog_leak(rep)


def test_flagged_then_recovered_host_gets_work_again():
    """Straggler suspicion is per round: a host flagged in round 1 (delay
    cleared at the round boundary) executes work again in round 2."""
    fp = FaultPlan(
        (FaultAction("delay", host=2, round_idx=1, after_tasks=0,
                     seconds=0.4),)
    )
    rep, store = run_mh(
        7, "insert", 4, backend="thread", fault_plan=fp,
        straggler=StragglerConfig(threshold=2.0, patience=2, interval=0.05),
    )
    assert_matches_reference(store, 7, "insert")
    flagged_rounds = [
        rnd.round_idx for rnd in rep.rounds
        if any(r.reason == "straggler" for r in rnd.redispatches)
    ]
    assert flagged_rounds, "delay never tripped the straggler detector"
    later = [r for r in rep.rounds if r.round_idx > max(flagged_rounds)]
    assert later and all(
        rnd.host_stats[2].executed > 0 for rnd in later
    ), "recovered host never received work again"


def test_redispatch_visible_in_trace_spans():
    fp = FaultPlan((FaultAction("kill", host=1, round_idx=1, after_tasks=0),))
    was = obs_trace.enabled()
    obs_trace.enable(True)
    obs_trace.clear()
    try:
        rep, store = run_mh(7, "insert", 2, backend="thread", fault_plan=fp)
        spans = obs_trace.drain()
    finally:
        obs_trace.enable(was)
    assert_matches_reference(store, 7, "insert")
    rd = [s for s in spans if s.cat == "redispatch"]
    assert len(rd) == len(rep.redispatches)
    # re-dispatch events land on the receiving host's track
    assert {s.track for s in rd} <= {f"host{h}" for h in range(2)}
    assert all(s.worker == "coord" for s in rd)


def test_plan_stretch_spans_carry_the_round_they_plan():
    """The coordinator's plan stretch (solve, clean-partition pruning and
    the scan routing it triggers) is a ``plan`` span stamped with the round
    it plans, ahead of that round's frame."""
    was = obs_trace.enabled()
    obs_trace.enable(True)
    obs_trace.clear()
    try:
        rep, store = run_mh(7, "insert", 2, backend="thread")
        spans = [s for s in obs_trace.drain() if s.track == "real"]
    finally:
        obs_trace.enable(was)
    assert_matches_reference(store, 7, "insert")
    prev_end = -1.0
    for rnd in rep.rounds:
        r = rnd.round_idx
        (plan,) = [s for s in spans if s.cat == "plan" and s.round == r]
        (frame,) = [s for s in spans if s.cat == "round" and s.round == r]
        assert prev_end <= plan.ts <= plan.ts + plan.dur <= frame.ts + 1e-6
        prev_end = frame.ts + frame.dur
    routed = [s for s in spans if s.cat == "ingest.route" and s.round > 0]
    assert routed
    for s in routed:
        (plan,) = [p for p in spans if p.cat == "plan" and p.round == s.round]
        assert plan.ts <= s.ts and s.ts + s.dur <= plan.ts + plan.dur + 1e-6


def test_all_hosts_lost_raises():
    fp = FaultPlan((
        FaultAction("kill", host=0, round_idx=1, after_tasks=0),
        FaultAction("kill", host=1, round_idx=1, after_tasks=0),
    ))
    with pytest.raises(RuntimeError, match="no surviving host"):
        run_mh(7, "insert", 2, backend="thread", fault_plan=fp)


# ---------------------------------------------------------------------------
# catalog accounting on re-dispatch (the leak regression)
# ---------------------------------------------------------------------------

def test_dead_host_catalog_entries_are_dropped():
    """Regression: partitions admitted by a host that dies mid-round must be
    released before replay — the killed host's catalog is cleared and no
    survivor carries phantom ``used_bytes`` past round end."""
    wl = build_workload(7)
    pwl, pmap = partition_workload(wl, P)
    espec = expand_update_spec(SPECS["insert"], pmap)
    store = DiskStore(tempfile.mkdtemp(prefix="mh-leak-"))
    budgets = [BUDGET / 2] * 2
    fp = FaultPlan((FaultAction("kill", host=1, round_idx=0, after_tasks=2),))
    pool = HostPool(pwl, store, budgets, espec, backend="thread",
                    fault_plan=fp, round_timeout=60.0)
    try:
        g = pwl.to_graph(CM)
        plan = solve_multihost(g, budgets, P)
        rep = pool.run_round(0, plan, sizes=[n.size for n in pwl.nodes])
        assert rep.hosts_lost == [1]
        assert rep.redispatches
        # the killed host's engine object survives on the thread backend —
        # its catalog must have been force-cleared by the coordinator
        assert pool.host_catalog_used(1) == 0.0
        assert pool.host_catalog_used(0) == 0.0
        for hs in rep.host_stats:
            if hs.alive:
                assert hs.used_bytes == 0.0
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# placement unit behavior
# ---------------------------------------------------------------------------

def test_place_partitions_hash_and_bytes():
    assert place_partitions(6, 2) == (0, 1, 0, 1, 0, 1)
    assert place_partitions(4, 1) == (0, 0, 0, 0)
    # greedy bytes balancing: the two heavy partitions split across hosts
    pl = place_partitions(4, 2, bytes_per_partition=[100, 90, 5, 5],
                          strategy="bytes")
    assert pl[0] != pl[1]
    loads = [0.0, 0.0]
    for p, h in enumerate(pl):
        loads[h] += [100, 90, 5, 5][p]
    assert abs(loads[0] - loads[1]) <= 10
    with pytest.raises(ValueError, match="bytes_per_partition"):
        place_partitions(4, 2, strategy="bytes")
    with pytest.raises(ValueError, match="unknown placement"):
        place_partitions(4, 2, bytes_per_partition=[1, 1, 1, 1],
                         strategy="nope")


def test_fault_plan_for_host():
    a = FaultAction("kill", host=1)
    b = FaultAction("delay", host=0, seconds=0.5)
    fp = FaultPlan((a, b))
    assert fp.for_host(1) == (a,)
    assert fp.for_host(0) == (b,)
    assert fp.for_host(3) == ()


# ---------------------------------------------------------------------------
# acceptance matrix (slow): seeds × hosts × update kinds × faults
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("seed", [7, 11, 23])
@pytest.mark.parametrize("n_hosts", [1, 2, 4])
@pytest.mark.parametrize("spec_key", sorted(SPECS))
def test_acceptance_matrix_bitwise(seed, n_hosts, spec_key):
    """The full ISSUE matrix: every (seed, hosts, update kind) cell — with a
    mid-round kill injected whenever there is a host to spare — completes
    bitwise identical to the fault-free single-host run."""
    fp = None
    if n_hosts > 1:
        fp = FaultPlan((
            FaultAction("kill", host=n_hosts - 1, round_idx=1,
                        after_tasks=1),
        ))
    rep, store = run_mh(seed, spec_key, n_hosts, backend="thread",
                        fault_plan=fp)
    assert_matches_reference(store, seed, spec_key)
    assert_no_catalog_leak(rep)
    if n_hosts > 1:
        assert rep.hosts_lost == [n_hosts - 1]
        assert rep.redispatches
