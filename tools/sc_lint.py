#!/usr/bin/env python
"""sc-lint: static verifier for delta-safety, kernel determinism, and plan
feasibility.

Runs every analysis pass of ``repro.analysis`` over the repo and over
representative workloads, then gates error/warning findings against the
checked-in baseline (``tools/sc_lint_baseline.json``). Info findings are
report-only. The fixture selftest additionally asserts the linter still
FIRES on the must-fire fixtures (``repro.analysis.fixtures``: the two
historical bugs plus the forged captured-threshold MQO merge) and stays
quiet on the shipped fixes — a rotted lint rule fails CI even when the repo
itself is clean.

Usage:
    PYTHONPATH=src python tools/sc_lint.py             # human report
    PYTHONPATH=src python tools/sc_lint.py --ci        # gate + JSON report
    PYTHONPATH=src python tools/sc_lint.py --update-baseline

Exit status: 0 clean, 1 new gating findings or fixture regression.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))

from repro.analysis import (  # noqa: E402
    Finding,
    format_findings,
    gating,
    load_baseline,
    new_findings,
    save_baseline,
    stale_entries,
    to_json,
)
from repro.analysis import determinism, fixtures  # noqa: E402

BASELINE = REPO / "tools" / "sc_lint_baseline.json"
DEFAULT_REPORT = REPO / "results" / "sc_lint" / "report.json"


def describe_dataplane() -> str:
    """One-line report header: the data plane's configured impl and what
    "auto" resolves to on this platform."""
    from repro.mv import dataplane

    try:
        resolved = dataplane.resolve_impl("auto")
    except Exception as e:  # jax missing/broken: still describable
        resolved = f"unresolvable ({e})"
    return (f"data plane impl: configured={dataplane.configured_impl()!r} "
            f"resolves_to={resolved!r}")


def _source_findings() -> list[Finding]:
    return determinism.lint_paths(REPO)


def _jaxpr_findings() -> list[Finding]:
    return determinism.lint_dataplane_kernels()


def _delta_safety_findings() -> list[Finding]:
    """Lift + type representative realized workloads and run the delta
    passes: the unpartitioned scenario-matrix workload and its P=4
    partitioned expansion, under a retracting update mix."""
    from repro.analysis.delta_safety import analyze_workload
    from repro.mv import (
        DiskStore,
        UpdateSpec,
        calibrate_sizes,
        generate_workload,
        realize_workload,
    )
    from repro.mv.partition import partition_workload

    out: list[Finding] = []
    spec = UpdateSpec(mode="incremental", update_frac=0.2, delete_frac=0.1)
    with tempfile.TemporaryDirectory() as td:
        wl = calibrate_sizes(
            realize_workload(
                generate_workload(n_nodes=14, seed=3),
                bytes_per_root=1 << 15,
            ),
            DiskStore(Path(td) / "calib"),
        )
        _, f1 = analyze_workload(wl, spec=spec)
        out.extend(f1)
        pwl, _ = partition_workload(wl, 4)
        _, f2 = analyze_workload(pwl, spec=spec)
        out.extend(f2)
    return out


def _plan_findings() -> list[Finding]:
    """Feasibility-check the solver's own output on a flat instance and on a
    hierarchical P=16 instance (the path that historically needed the shed
    loop)."""
    from repro.analysis.plan_check import check_plan
    from repro.core.altopt import solve, solve_hierarchical
    from repro.mv import generate_workload

    out: list[Finding] = []

    graph = generate_workload(n_nodes=24, seed=0).to_graph()
    budget = 0.3 * sum(graph.sizes)
    for k in (1, 4):
        plan = solve(graph, budget, n_workers=k)
        out.extend(check_plan(
            graph, plan.flagged, plan.order, budget, k,
            path="plan:flat_n24_s0", symbol=f"k{k}",
        ))

    P = 16
    pplan = solve_hierarchical(graph, budget, P, n_workers=2)
    expanded, _ = graph.expand_partitions(P, None)
    out.extend(check_plan(
        expanded, pplan.plan.flagged, pplan.plan.order, budget,
        pplan.plan.n_workers, path=f"plan:hier_n24_P{P}", symbol="k2",
    ))
    return out


def _mqo_findings() -> list[Finding]:
    """Merge-soundness (DESIGN.md §11): run ``check_merged`` over
    representative ``merge_workload`` outputs — the shared-prefix MQO
    workload (realized, so the fingerprints come from real lifted closures)
    and the scenario-matrix generator workload (which has no duplicate
    definitions; its merge must be a no-op and still verify)."""
    from repro.analysis.mqo_check import check_merged
    from repro.mv import generate_workload, realize_workload
    from repro.mv.mqo import merge_workload, shared_prefix_workload

    out: list[Finding] = []
    wl = realize_workload(
        shared_prefix_workload(n_views=3), bytes_per_root=1 << 15, seed=3
    )
    out.extend(check_merged(merge_workload(wl)))
    wl2 = realize_workload(
        generate_workload(n_nodes=14, seed=3), bytes_per_root=1 << 15
    )
    out.extend(check_merged(merge_workload(wl2)))
    return out


def _fixture_findings() -> list[Finding]:
    """Must-fire selftest: each historical-bug fixture must trip its rule,
    and the shipped fix must be quiet. A miss is a gating, un-baselineable
    regression of the linter itself."""
    import numpy as np

    out: list[Finding] = []

    def regression(symbol: str, msg: str):
        out.append(Finding(
            "fixture-regression", "error", "repro/analysis/fixtures.py",
            symbol, msg,
        ))

    legacy = determinism.lint_source(
        fixtures.LEGACY_FILTER_MASK_SRC, "fixture:legacy_filter_mask"
    )
    if not any(f.rule == "static-arg-retrace" for f in legacy):
        regression("LEGACY_FILTER_MASK_SRC",
                   "static-arg-retrace no longer fires on the historical "
                   "static-threshold _filter_mask")
    shipped = determinism.lint_source(
        fixtures.SHIPPED_FILTER_MASK_SRC, "fixture:shipped_filter_mask"
    )
    if gating(shipped):
        regression("SHIPPED_FILTER_MASK_SRC",
                   "linter fires on the shipped traced-threshold filter")

    f32 = np.linspace(-1.0, 1.0, 8, dtype=np.float32)
    fused = determinism.lint_jaxpr(
        fixtures.legacy_fused_map(), f32, f32,
        symbol="legacy_fused_map", path="fixture:legacy_fused_map",
    )
    rules = {f.rule for f in fused}
    if "transcendental-kernel" not in rules:
        regression("legacy_fused_map",
                   "transcendental-kernel no longer fires on the fused tanh "
                   "MAP kernel")
    if "fma-contraction" not in rules:
        regression("legacy_fused_map",
                   "fma-contraction no longer fires on the fused mul+add "
                   "MAP kernel")
    for i, k in enumerate(fixtures.shipped_map_kernels()):
        hits = determinism.lint_jaxpr(
            k, f32, symbol=f"shipped_map_{i}", path="fixture:shipped_map",
        )
        if gating(hits):
            regression(f"shipped_map_{i}",
                       "linter fires on a shipped softsign map kernel: "
                       + "; ".join(f.rule for f in hits))

    from repro.analysis.mqo_check import check_merged

    forged = check_merged(fixtures.forged_threshold_merge())
    if not any(f.rule == "unsound-merge" for f in forged):
        regression("forged_threshold_merge",
                   "unsound-merge no longer fires on the forged "
                   "captured-threshold merge")
    honest = check_merged(fixtures.genuine_shared_prefix_merge())
    if gating(honest):
        regression("genuine_shared_prefix_merge",
                   "merge-soundness pass fires on an honest merge_workload "
                   "result: " + "; ".join(f.rule for f in honest))
    return out


PASSES = (
    ("source", _source_findings),
    ("jaxpr", _jaxpr_findings),
    ("delta-safety", _delta_safety_findings),
    ("plan", _plan_findings),
    ("mqo", _mqo_findings),
    ("fixtures", _fixture_findings),
)


def collect(verbose: bool = True) -> tuple[list[Finding], dict[str, int]]:
    findings: list[Finding] = []
    counts: dict[str, int] = {}
    for name, pass_fn in PASSES:
        got = pass_fn()
        counts[name] = len(got)
        findings.extend(got)
        if verbose:
            print(f"  pass {name:13s} {len(got)} finding(s)")
    return findings, counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ci", action="store_true",
                    help="gate against the baseline and write a JSON report")
    ap.add_argument("--update-baseline", action="store_true",
                    help="record current gating findings as accepted debt")
    ap.add_argument("--report", type=Path, default=None,
                    help=f"JSON report path (default {DEFAULT_REPORT} "
                         "under --ci)")
    ap.add_argument("--baseline", type=Path, default=BASELINE)
    args = ap.parse_args(argv)

    print(f"sc-lint over {REPO}")
    print(describe_dataplane())
    findings, counts = collect()

    if args.update_baseline:
        fps = save_baseline(args.baseline, findings)
        print(f"baseline updated: {len(fps)} fingerprint(s) -> "
              f"{args.baseline}")
        return 0

    baseline = load_baseline(args.baseline)
    new = new_findings(findings, baseline)
    stale = stale_entries(findings, baseline)
    info = [f for f in findings if f.level == "info"]

    if findings:
        print()
        print(format_findings(findings))
    print()
    print(f"{len(findings)} finding(s): {len(gating(findings))} gating "
          f"({len(new)} new vs baseline), {len(info)} info")
    for fp in stale:
        print(f"stale baseline entry (finding gone — prune it): {fp}")

    report_path = args.report or (DEFAULT_REPORT if args.ci else None)
    if report_path is not None:
        report_path.parent.mkdir(parents=True, exist_ok=True)
        report_path.write_text(json.dumps({
            "dispatch": describe_dataplane(),
            "counts": counts,
            "baseline": sorted(baseline),
            "new_fingerprints": [f.fingerprint for f in new],
            "stale_baseline_entries": stale,
            "findings": to_json(findings),
        }, indent=2) + "\n")
        print(f"report -> {report_path}")

    if new:
        print(f"FAIL: {len(new)} new gating finding(s) not in baseline")
        return 1
    print("OK: no new gating findings")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
