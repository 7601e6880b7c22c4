"""Benchmark orchestrator: ``PYTHONPATH=src python -m benchmarks.run``.

Runs every paper-figure benchmark (Figs. 9–14, Tables IV–V), the
full-vs-incremental update comparison, the real-executor wall-clock
validation, the operator-throughput microbenchmark, and the roofline report
from whatever dry-run records exist. ``--quick`` trims sweep sizes;
``--smoke`` runs only the fast scenario-regression subset (the incremental
benchmark in quick mode, plus the data-plane parity gate) for CI. Exit code
is non-zero if any module raises.

Host-parallel JAX data plane
----------------------------
``--hostdev N`` sets ``--xla_force_host_platform_device_count=N`` *before*
any benchmark module imports JAX, so the CPU backend exposes N devices and
the jitted data plane can be measured host-parallel (benchmark imports are
deferred into ``main`` for exactly this reason — XLA reads the flag once at
backend init). For stable large-allocation behavior pair it with tcmalloc,
the recipe the HomebrewNLP runs use:

    LD_PRELOAD=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \\
    TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000 \\
    PYTHONPATH=src python -m benchmarks.run --hostdev 8 --only tableops
"""
from __future__ import annotations

import argparse
import os
import time
import traceback


def _modules():
    """Import benchmark modules and build the registry. Deferred so
    ``--hostdev`` can set XLA_FLAGS before anything pulls in JAX."""
    from . import (
        fig9_end_to_end,
        fig10_scales,
        fig12_ablation,
        fig13_opttime,
        fig14_sweep,
        incremental,
        mqo_bench,
        multihost_sweep,
        parallel_sweep,
        partition_sweep,
        planner_scale,
        real_executor,
        roofline,
        table4_readtime,
        table5_cluster,
        tableops_bench,
    )

    return [
        ("fig9_end_to_end", fig9_end_to_end.run),
        ("fig10_scales", fig10_scales.run),
        ("fig11_memcat+table4", table4_readtime.run),  # table4 drives fig11
        ("fig12_ablation", fig12_ablation.run),
        ("table5_cluster", table5_cluster.run),
        ("parallel_sweep", parallel_sweep.run),
        ("partition_sweep", partition_sweep.run),
        ("planner_scale", planner_scale.run),
        ("incremental", incremental.run),
        ("mqo_bench", mqo_bench.run),
        ("multihost_sweep", multihost_sweep.run),
        ("fig13_opttime", fig13_opttime.run),
        ("fig14_sweep", fig14_sweep.run),
        ("real_executor", real_executor.run),
        ("tableops_bench", tableops_bench.run),
        ("roofline", lambda quick: roofline.run(mesh="single", quick=quick)),
    ]


# scenario-regression gate for CI: fast, asserts the paper-shaped invariants
# across the INSERT / UPDATE / DELETE update kinds — for inserts, every
# workload must show incremental < full and S/C > 1x; for update/delete
# churn, at least one workload must show S/C > 1x — plus bitwise identity of
# incremental vs full recompute on the real executor for insert-only and
# mixed churn (see benchmarks/incremental.py for the exact assertions).
# partition_sweep additionally asserts the partition-granular acceptance
# claim: with the budget below the hottest MV, P>=8 S/C strictly beats
# whole-MV S/C on the skewed workload (JSON artifact uploaded by CI).
# planner_scale asserts the hierarchical-planner criteria: >= 10x faster
# solves than flat at P=64, end-to-end speedup within 5% of flat across the
# sweep, and bitwise P=1 degeneracy.
# tableops_bench (smoke mode) is the data-plane parity gate: every ported
# operator must be bitwise-equal across numpy / jitted-XLA / interpret-mode
# Pallas, asserted in-run (DESIGN.md §9).
# mqo_bench asserts the shared-subexpression acceptance claims (DESIGN.md
# §11): each shared subtree refreshes exactly once per round, merged output
# bitwise-identical to unshared, >= 1.3x refresh speedup at k=1, and the
# shared intermediates earn Memory Catalog residency under default budget.
# multihost_sweep asserts the multi-host acceptance claims (DESIGN.md §13):
# e2e refresh improves 1 -> 4 hosts on the Zipf-skewed workload, every
# multi-host store is bitwise identical to the single-host run, and the
# injected-fault scenario (host killed mid-round) recovers via re-dispatch
# with the store still bitwise identical to the fault-free single-host run.
SMOKE_MODULES = [
    "incremental", "mqo_bench", "multihost_sweep", "partition_sweep",
    "planner_scale", "tableops_bench",
]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset (implies --quick)")
    ap.add_argument("--hostdev", type=int, default=0, metavar="N",
                    help="expose N XLA host (CPU) devices before importing "
                         "JAX (--xla_force_host_platform_device_count)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.quick = True
    if args.hostdev > 0:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.hostdev}"
        ).strip()
    from .compile_cache import enable_compile_cache

    print(f"[benchmarks] compile cache: {enable_compile_cache()}")

    failures = []
    for name, fn in _modules():
        if args.only and args.only not in name:
            continue
        if args.smoke and name not in SMOKE_MODULES:
            continue
        print(f"\n{'='*72}\n[benchmarks] {name}\n{'='*72}")
        t0 = time.perf_counter()
        try:
            from . import common
            common.begin_module(name)
            fn(quick=args.quick)
            print(f"[benchmarks] {name} done in {time.perf_counter()-t0:.1f}s")
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nAll benchmarks completed.")


if __name__ == "__main__":
    main()
