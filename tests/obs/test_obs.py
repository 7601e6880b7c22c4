"""Observability layer (DESIGN.md §12): span recorder, metrics registry,
Chrome-trace export/validation, real-vs-sim overlay, and the
predicted-vs-realized plan audit.

Covers the §12 contracts:
* disabled tracing is allocation-free (``span()`` returns one shared null
  context) and records nothing;
* traced and untraced scenario runs store bitwise-identical MVs (tracing is
  passive);
* the real engine's spans and ``RunReport.timeline`` respect plan-order /
  parent-completion causality, and the simulator emits the *same* span
  schema so the two tracks overlay;
* the exported Chrome trace passes the structural validator (and a broken
  document does not);
* the audit joins per-round plans against the trace into per-(mv, partition)
  drift rows with sane accounting.
"""
import json
import tracemalloc

import numpy as np
import pytest

from repro.core import CostModel, solve
from repro.mv import (
    Controller,
    DiskStore,
    UpdateSpec,
    calibrate_sizes,
    generate_workload,
    realize_workload,
    run_partitioned_scenario,
    run_scenario,
    simulate,
    simulate_scenario,
    verify_scenario_equivalence,
)
from repro.mv import dataplane
from repro.obs import METRICS, MetricsRegistry, trace as tr
from repro.obs.audit import audit_scenario
from repro.obs.export import (
    diff_tracks,
    overlay_timelines,
    summarize,
    to_chrome_trace,
    validate_chrome_trace,
)

CM = CostModel(
    disk_read_bw=50e6,
    disk_write_bw=50e6,
    mem_read_bw=1e12,
    mem_write_bw=1e12,
    disk_latency=0.0,
)


@pytest.fixture(autouse=True)
def _clean_trace():
    """Every test starts and ends with tracing off and buffers empty."""
    tr.enable(False)
    tr.clear()
    METRICS.clear()
    yield
    tr.enable(False)
    tr.clear()
    METRICS.clear()


def build(tmp_path, n_nodes=10, seed=3, bytes_per_root=1 << 14):
    wl = realize_workload(
        generate_workload(n_nodes=n_nodes, seed=seed),
        bytes_per_root=bytes_per_root,
    )
    return calibrate_sizes(wl, DiskStore(tmp_path / "calib"))


# ---------------------------------------------------------------------------
# recorder basics
# ---------------------------------------------------------------------------

def test_disabled_fast_path_is_allocation_free_and_silent():
    assert not tr.enabled()
    # the null context is a process singleton: no per-call allocation
    a = tr.span("compute", "mv1")
    b = tr.span("io.read", "mv2", 123.0)
    assert a is b
    with a as ctx:
        ctx.set(nbytes=5.0)  # no-op, must not raise
    tr.record("compute", "mv1", 0.0, 1.0)
    tr.instant("admit", "mv1", 10.0)
    tr.counter("catalog.bytes", 42.0)
    assert tr.drain() == []


def test_disabled_dataplane_call_is_allocation_free():
    """With tracing off the ``dp.*`` call sites get the shared null context
    and the recorder allocates nothing, on the path that would record."""
    keys = np.arange(100, dtype=np.int64)
    uniq = np.arange(0, 200, 3, dtype=np.int64)
    with dataplane.use_impl("xla"):
        want = (dataplane.hash64(keys), dataplane.probe_sorted(uniq, keys))
        tracemalloc.start()
        try:
            got = (dataplane.hash64(keys), dataplane.probe_sorted(uniq, keys))
            snap = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
    assert np.array_equal(got[0], want[0])
    assert all(np.array_equal(a, b) for a, b in zip(got[1], want[1]))
    in_trace = snap.filter_traces([tracemalloc.Filter(True, tr.__file__)])
    assert in_trace.statistics("lineno") == []
    assert tr.drain() == []


def test_enabled_recording_round_context_and_entry_parsing():
    tr.enable(True)
    tr.set_round(7)
    tr.record("compute", "mv3@p2", 1.0, 0.5, nbytes=64.0, worker="w0")
    with tr.span("io.read", "mv1") as sp:
        sp.set(nbytes=32.0)
    spans = tr.drain()
    assert len(spans) == 2
    s = spans[0]
    assert (s.cat, s.name, s.mv, s.partition) == ("compute", "mv3@p2", "mv3", 2)
    assert s.round == 7 and s.worker == "w0" and s.track == "real"
    assert spans[1].nbytes == 32.0 and spans[1].dur >= 0.0
    assert tr.split_entry("mv10") == ("mv10", -1)
    assert tr.split_entry("mv1@p15") == ("mv1", 15)
    assert tr.drain() == []  # drained


def test_sim_offset_accumulates_and_resets_on_clear():
    tr.set_sim_offset(12.5)
    assert tr.sim_offset() == 12.5
    tr.clear()
    assert tr.sim_offset() == 0.0


def test_metrics_registry_counters_gauges_histograms(tmp_path):
    """The registry keeps counters only: occupancy and round walls are
    read from the ``catalog.bytes`` samples and the ``round`` spans."""
    m = MetricsRegistry()
    m.inc("bytes_read", 100.0, entry="mv1")
    m.inc("bytes_read", 50.0, entry="mv1")
    m.inc("bytes_read", 10.0, entry="mv2")
    m.inc("join_fallbacks")
    assert m.counter_value("bytes_read", "mv1") == 150.0
    assert m.counter_value("join_fallbacks") == 1.0
    assert m.counter_family("bytes_read") == {"mv1": 150.0, "mv2": 10.0}
    snap = m.snapshot()
    assert set(snap) == {"counters"}
    assert not hasattr(m, "gauge") and not hasattr(m, "observe")
    p = m.export_json(tmp_path / "metrics.json")
    assert json.loads(p.read_text())["counters"]["bytes_read"]["mv1"] == 150.0
    m.clear()
    assert m.snapshot() == {"counters": {}}


# ---------------------------------------------------------------------------
# engine integration: spans, timeline, entry stats
# ---------------------------------------------------------------------------

def test_traced_run_emits_spans_and_wall_clock_timeline(tmp_path):
    wl = build(tmp_path)
    g = wl.to_graph(CM)
    budget = sum(g.sizes) * 0.4
    plan = solve(g, budget=budget, n_workers=2)
    assert plan.flagged

    tr.enable(True)
    store = DiskStore(tmp_path / "run")
    rep = Controller(wl, store, budget, n_compute_workers=2).run(plan)
    spans = tr.drain()

    cats = {s.cat for s in spans}
    assert {"task", "compute", "round"} <= cats
    assert "write.behind" in cats  # flagged nodes materialize off-channel
    assert {"admit", "release", "counter"} <= cats  # catalog lifecycle
    assert "io.write" in cats  # DiskStore part writes

    # RunReport.timeline: one (name, start, end) row per executed node, on
    # the run's wall clock, same shape as SimReport.timeline
    assert len(rep.timeline) == len(rep.executed)
    assert {n for n, _, _ in rep.timeline} == set(rep.executed)
    for name, start, end in rep.timeline:
        assert 0.0 <= start <= end
    # causality: a child never starts before every parent has completed
    done = {name: end for name, _, end in rep.timeline}
    by_name = {n.name: n for n in wl.nodes}
    for name, start, _ in rep.timeline:
        for p in by_name[name].parents:
            pname = wl.nodes[p].name
            assert start >= done[pname] - 1e-9, (
                f"{name} started before parent {pname} completed"
            )

    # per-entry catalog stats surface on the report
    assert rep.entry_stats
    assert sum(es["hits"] for es in rep.entry_stats.values()) == rep.catalog_hits
    # every span of the run carries the round frame it nests in
    rounds = {s.round for s in spans}
    assert rounds == {0}
    frame = [s for s in spans if s.cat == "round"]
    assert len(frame) == 1
    lo, hi = frame[0].ts, frame[0].ts + frame[0].dur
    for s in spans:
        if s.cat != "counter":
            assert lo - 1e-6 <= s.ts and s.ts + s.dur <= hi + 1e-6


def test_sim_track_shares_schema_and_overlays_real(tmp_path):
    wl = build(tmp_path)
    g = wl.to_graph(CM)
    budget = sum(g.sizes) * 0.4
    plan = solve(g, budget=budget, n_workers=2)

    tr.enable(True)
    store = DiskStore(tmp_path / "run")
    rep = Controller(wl, store, budget, n_compute_workers=2).run(plan)
    real_spans = tr.drain()
    sim = simulate(wl, plan, CM, mode="sc", n_workers=2)
    sim_spans = tr.drain()

    assert {s.track for s in real_spans} == {"real"}
    assert {s.track for s in sim_spans} == {"sim"}
    # same vocabulary on both tracks for the shared categories
    for cat in ("task", "compute", "round"):
        assert any(s.cat == cat for s in sim_spans), cat
    # whole-node task spans exist for the same node set
    real_tasks = {s.name for s in real_spans if s.cat == "task"}
    sim_tasks = {s.name for s in sim_spans if s.cat == "task"}
    assert real_tasks == sim_tasks == {n.name for n in wl.nodes}

    # timeline overlay: every node aligned, both sides present
    rows = overlay_timelines(rep.timeline, sim.timeline)
    assert len(rows) == len(wl.nodes)
    for row in rows:
        assert row["real_dur"] is not None and row["sim_dur"] is not None
        assert row["sim_over_real"] is None or row["sim_over_real"] > 0.0

    # per-(mv, round) diff built from the merged span stream
    d = diff_tracks(real_spans + sim_spans)
    assert d and all(
        r["real_s"] is not None and r["sim_s"] is not None for r in d
    )

    agg = summarize(real_spans + sim_spans)
    assert agg["real/task"]["count"] == agg["sim/task"]["count"]


def test_traced_and_untraced_runs_are_bitwise_identical(tmp_path):
    wl = build(tmp_path)
    spec = UpdateSpec(mode="incremental", n_rounds=2, ingest_frac=0.2,
                      update_frac=0.05)
    budget = sum(n.size for n in wl.nodes) * 0.5

    tr.enable(False)
    store_off = DiskStore(tmp_path / "off")
    run_scenario(wl, store_off, budget, spec, CM, n_compute_workers=2)
    assert tr.drain() == []

    tr.enable(True)
    store_on = DiskStore(tmp_path / "on")
    run_scenario(wl, store_on, budget, spec, CM, n_compute_workers=2)
    assert tr.drain()

    verify_scenario_equivalence(wl, store_on, store_off)


# ---------------------------------------------------------------------------
# export + validation
# ---------------------------------------------------------------------------

def test_chrome_trace_export_validates_and_broken_doc_fails(tmp_path):
    wl = build(tmp_path)
    spec = UpdateSpec(mode="incremental", n_rounds=2, ingest_frac=0.2)
    budget = sum(n.size for n in wl.nodes) * 0.5

    tr.enable(True)
    store = DiskStore(tmp_path / "run")
    run_scenario(wl, store, budget, spec, CM, n_compute_workers=2)
    real_spans = tr.drain()
    simulate_scenario(wl, spec, CM, budget, n_workers=2)
    sim_spans = tr.drain()

    doc = to_chrome_trace(real_spans + sim_spans)
    assert validate_chrome_trace(doc) == []
    # multi-round sim rounds must not stack at ts=0: round frames disjoint
    sim_frames = sorted(
        (e["ts"], e["ts"] + e["dur"])
        for e in doc["traceEvents"]
        if e.get("cat") == "round" and e["name"].startswith("round")
        and any(
            m["ph"] == "M" and m["name"] == "process_name"
            and m["pid"] == e["pid"] and m["args"]["name"] == "sc-sim"
            for m in doc["traceEvents"]
        )
    )
    for (a_lo, a_hi), (b_lo, b_hi) in zip(sim_frames, sim_frames[1:]):
        assert b_lo >= a_hi - 1e-6

    assert validate_chrome_trace({}) == ["traceEvents missing or empty"]
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": -5.0, "dur": -1.0},
        {"ph": "i", "pid": 1, "tid": 1, "ts": 0.0},
    ]}
    problems = validate_chrome_trace(bad)
    assert any("negative" in p for p in problems)
    assert any("missing" in p for p in problems)


# ---------------------------------------------------------------------------
# predicted-vs-realized audit
# ---------------------------------------------------------------------------

def test_audit_joins_plans_against_trace(tmp_path):
    wl = build(tmp_path)
    spec = UpdateSpec(mode="incremental", n_rounds=2, ingest_frac=0.2)
    budget = sum(n.size for n in wl.nodes) * 0.5

    tr.enable(True)
    store = DiskStore(tmp_path / "run")
    rep = run_scenario(wl, store, budget, spec, CM, n_compute_workers=2)
    spans = tr.drain()

    assert any(r.plan.flagged for r in rep.rounds)
    assert all(len(r.scores) == len(wl.nodes) for r in rep.rounds)

    audit = audit_scenario(wl, rep, spans, CM)
    assert audit.rows
    names = [n.name for n in wl.nodes]
    # every flagged (mv, round) of every plan has an audit row
    audited = {(r.entry, r.round) for r in audit.rows}
    for rr in rep.rounds:
        for v in rr.plan.flagged:
            assert (names[v], rr.round_idx) in audited
    for row in audit.rows:
        assert row.realized_s == pytest.approx(
            row.realized_read_s + row.realized_write_s
        )
        assert row.drift_s == pytest.approx(row.realized_s - row.predicted_s)
        assert row.hits >= 0 and row.hold_s >= 0.0
        if row.flagged:
            v = names.index(row.entry)
            assert row.predicted_s == pytest.approx(
                rep.rounds[row.round].scores[v]
            )
        else:
            assert row.predicted_s == 0.0
        if row.wasted:
            assert row.flagged and row.hits == 0

    # the per-(mv, partition) rollup covers every row and sums drift exactly
    rollup = audit.by_mv_partition()
    assert sum(a["drift_s"] for a in rollup.values()) == pytest.approx(
        audit.drift_s
    )
    # serialization + table rendering
    d = audit.to_dict()
    assert d["schema"] == "sc-audit/v1"
    assert len(d["rows"]) == len(audit.rows)
    assert "drift(s)" in audit.table()
    p = audit.save_json(tmp_path / "drift.json")
    assert json.loads(p.read_text())["totals"]["drift_s"] == pytest.approx(
        audit.drift_s
    )


def test_traced_scenario_metrics_fold_per_entry(tmp_path):
    wl = build(tmp_path)
    spec = UpdateSpec(mode="incremental", n_rounds=1, ingest_frac=0.2)
    budget = sum(n.size for n in wl.nodes) * 0.5

    tr.enable(True)
    store = DiskStore(tmp_path / "run")
    rep = run_scenario(wl, store, budget, spec, CM, n_compute_workers=2)
    snap = METRICS.snapshot()
    total_hits = sum(
        sum(r.run.entry_stats[e]["hits"] for e in r.run.entry_stats)
        for r in rep.rounds
    )
    assert sum(snap["counters"].get("catalog_hits", {}).values()) == total_hits
    assert sum(snap["counters"]["bytes_written"].values()) > 0
    # one engine ``round`` span per round, stamped with its round
    frames = [s.round for s in tr.spans() if s.cat == "round"]
    assert sorted(frames) == [r.round_idx for r in rep.rounds]


def test_traced_partitioned_scenario_spans_plan_ingest_and_dataplane(tmp_path):
    """A traced P=2 scenario on the ``xla`` data plane: every round's plan
    stretch (view, solve, prune), scan routing and device calls are spans
    stamped with the round they serve, device calls nest in a ``compute``
    or ``ingest.route`` span of their own thread, and the export still
    validates (a round's frame is its plan span plus its engine run)."""
    wl = build(tmp_path)
    n_rounds = 2
    spec = UpdateSpec(mode="incremental", n_rounds=n_rounds, ingest_frac=0.2,
                      update_frac=0.05)
    budget = sum(n.size for n in wl.nodes) * 0.5

    tr.enable(True)
    with dataplane.use_impl("xla"):
        run_partitioned_scenario(wl, 2, DiskStore(tmp_path / "run"), budget,
                                 spec, CM, n_compute_workers=2)
    spans = tr.drain()
    tr.enable(False)

    cats = {s.cat for s in spans}
    assert {"plan", "plan.view", "plan.solve", "plan.prune", "ingest.route",
            "ingest.source", "round"} <= cats
    dp = [s for s in spans if s.cat.startswith("dp.")]
    assert {s.cat for s in dp} >= {"dp.pid", "dp.probe"}
    assert all(s.nbytes > 0 for s in dp)
    traces = [s for s in spans if s.cat == "jit.trace"]
    assert traces and all(s.dur == 0.0 and s.name.startswith("dp.")
                          for s in traces)

    def only(cat, r):
        found = [s for s in spans if s.cat == cat and s.round == r]
        assert len(found) == 1, (cat, r, found)
        return found[0]

    def inside(child, parent):
        return (parent.ts - 1e-6 <= child.ts
                and child.ts + child.dur <= parent.ts + parent.dur + 1e-6)

    prev_end = -1.0
    for r in range(n_rounds + 1):
        plan, frame = only("plan", r), only("round", r)
        # round r's plan runs after round r - 1's engine run and before its
        # own: the stamp is the round it plans, not the previous one
        assert prev_end <= plan.ts
        assert plan.ts + plan.dur <= frame.ts + 1e-6
        for cat in ("plan.view", "plan.solve", "plan.prune"):
            assert inside(only(cat, r), plan)
        prev_end = frame.ts + frame.dur

    def parents(s, cats_):
        return [p for p in spans if p.cat in cats_ and p.worker == s.worker
                and p.round == s.round and p is not s and inside(s, p)]

    routes = [s for s in spans if s.cat == "ingest.route"]
    assert {s.round for s in routes} == set(range(n_rounds + 1))
    for s in routes:
        assert parents(s, {"plan.prune", "compute"}), s
    for s in spans:
        if s.cat == "ingest.source":
            assert parents(s, {"ingest.route"}), s
    for s in dp:
        assert parents(s, {"compute", "ingest.route"}), s

    assert validate_chrome_trace(to_chrome_trace(spans)) == []

