"""Delta-safety passes over the operator IR: rule coverage on hand-built
IRs (weight closure, rid stability, AGG overflow bounds, fallback
reachability) and clean gating output on realized default workloads.
"""
import numpy as np
import pytest

from repro.analysis import gating
from repro.analysis.delta_safety import (
    DELTA_RULES,
    analyze_workload,
    check_ir,
    est_rows,
)
from repro.mv import ir as mvir
from repro.mv.tableops import AGG_QUANTUM


def node(name, op, parents=(), schema=None, size=0.0, lifted=True):
    return mvir.OpNode(
        name=name, op=op, parents=tuple(parents), schema=schema, size=size,
        lifted=lifted,
    )


SCAN_S = mvir.scan_table_schema(4)
RIDLESS = mvir.Schema((("key", "<i8"), ("c0", "<f4")))
AGG_S = mvir.Schema((("key", "<i8"), ("c0", "<f4")))


def rules(findings):
    return {f.rule for f in findings}


def test_every_engine_op_has_a_delta_rule():
    assert set(DELTA_RULES) == {
        "SCAN", "FILTER", "PROJECT", "MAP", "JOIN", "UNION", "AGG"
    }


def test_unknown_op_is_weight_closure_error():
    ir = mvir.ViewIR((
        node("src", "SCAN", schema=SCAN_S, size=1e4),
        node("w", "WINDOW", parents=(0,), schema=SCAN_S, size=1e4),
    ))
    got = check_ir(ir)
    assert any(
        f.rule == "weight-closure" and f.level == "error" and f.symbol == "w"
        for f in got
    )


def test_unlifted_node_is_opaque_view_warning():
    ir = mvir.ViewIR((
        node("src", "SCAN", schema=SCAN_S, size=1e4),
        node("m", "MAP", parents=(0,), schema=SCAN_S, size=1e4,
             lifted=False),
    ))
    assert "opaque-view" in rules(check_ir(ir))


def test_rid_stability_infos():
    ir = mvir.ViewIR((
        node("a", "SCAN", schema=SCAN_S, size=1e4),
        node("b", "SCAN", schema=RIDLESS, size=1e4),
        node("j", "JOIN", parents=(1, 0), schema=RIDLESS, size=1e4),
        node("u", "UNION", parents=(0, 1), schema=SCAN_S, size=1e4),
    ))
    got = check_ir(ir, retractions=True)
    assert "join-ridless-left" in rules(got)     # j's left input b: no rid
    assert "union-ridless-input" in rules(got)   # u's input b: no rid
    assert "ridless-retraction" in rules(got)    # j's own output: no rid
    # all rid-stability findings are info: statically inevitable fallbacks
    # are correct, just worth knowing
    assert not gating([f for f in got if f.rule != "opaque-view"])


def test_ridless_retraction_needs_retracting_mix():
    ir = mvir.ViewIR((
        node("a", "SCAN", schema=SCAN_S, size=1e4),
        node("p", "PROJECT", parents=(0,), schema=RIDLESS, size=1e4),
    ))
    assert "ridless-retraction" not in rules(check_ir(ir, retractions=False))
    assert "ridless-retraction" in rules(check_ir(ir, retractions=True))


def test_agg_overflow_warning_then_error():
    # est_rows = size / bytes-per-row; SCAN_S is 8+8+3*4 = 28 B/row
    rows = 1e6
    ir = mvir.ViewIR((
        node("src", "SCAN", schema=SCAN_S, size=rows * 28),
        node("agg", "AGG", parents=(0,), schema=AGG_S, size=1e4),
    ))
    assert np.isclose(est_rows(ir.nodes[0]), rows)
    ok = check_ir(ir, value_scale=64.0)
    assert "agg-overflow" not in rules(ok)
    # pick scales so rows * scale * AGG_QUANTUM lands in [2^62, 2^63) and
    # then past 2^63
    warn_scale = (2.0 ** 62) / (rows * AGG_QUANTUM) * 1.5
    warn = [f for f in check_ir(ir, value_scale=warn_scale)
            if f.rule == "agg-overflow"]
    assert [f.level for f in warn] == ["warning"]
    err = [f for f in check_ir(ir, value_scale=warn_scale * 2)
           if f.rule == "agg-overflow"]
    assert [f.level for f in err] == ["error"]


def test_join_fallback_reachability_requires_dirty_probe_side():
    static_right = mvir.ViewIR((
        node("a", "SCAN", schema=SCAN_S, size=1e4),
        node("b", "SCAN", schema=SCAN_S, size=1e4),
        node("j", "JOIN", parents=(0, 1), schema=SCAN_S, size=1e4),
    ))
    # only the left scan ingests: the probe side is static, no fallback
    quiet = check_ir(static_right, ingest=frozenset({0}))
    assert "join-fallback-reachable" not in rules(quiet)
    fires = check_ir(static_right, ingest=frozenset({1}))
    assert "join-fallback-reachable" in rules(fires)


def test_agg_downstream_full_only_with_consumers():
    ir = mvir.ViewIR((
        node("src", "SCAN", schema=SCAN_S, size=1e4),
        node("agg", "AGG", parents=(0,), schema=AGG_S, size=1e4),
        node("m", "MAP", parents=(1,), schema=AGG_S, size=1e4),
    ))
    got = check_ir(ir)
    hits = [f for f in got if f.rule == "agg-downstream-full"]
    assert [f.symbol for f in hits] == ["agg"]
    leaf = mvir.ViewIR(ir.nodes[:2])
    assert "agg-downstream-full" not in rules(check_ir(leaf))


def test_realized_default_workload_is_gating_clean(tmp_path):
    from repro.mv import (
        DiskStore, calibrate_sizes, generate_workload, realize_workload,
    )

    wl = calibrate_sizes(
        realize_workload(
            generate_workload(n_nodes=10, seed=3), bytes_per_root=1 << 13
        ),
        DiskStore(tmp_path / "calib"),
    )
    ir, findings = analyze_workload(wl)
    assert ir.n == len(wl.nodes)
    assert not gating(findings)
    assert all(f.path == f"ir:{wl.name}" for f in findings)


def test_union_shared_rids_info_needs_inputs_of_one_scan():
    ir = mvir.ViewIR((
        node("a", "SCAN", schema=SCAN_S, size=1e4),
        node("b", "SCAN", schema=SCAN_S, size=1e4),
        node("f", "FILTER", parents=(0,), schema=SCAN_S, size=1e4),
        node("m", "MAP", parents=(0,), schema=SCAN_S, size=1e4),
        node("u", "UNION", parents=(2, 3, 1), schema=SCAN_S, size=1e4),
        node("v", "UNION", parents=(2, 1), schema=SCAN_S, size=1e4),
    ))
    got = [f for f in check_ir(ir) if f.rule == "union-shared-rids"]
    assert [(f.symbol, f.level) for f in got] == [("u", "info")]
    assert "['f', 'm']" in got[0].message
    # only b ingests: the inputs that share a rid never change
    assert not [f for f in check_ir(ir, ingest=frozenset({1}))
                if f.rule == "union-shared-rids"]
