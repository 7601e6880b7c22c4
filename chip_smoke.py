"""Run one bitwise-verified, partitioned MV refresh on a TPU chip.

    python chip_smoke.py [--bytes-per-root BYTES] [--parity-rows N]

The main path end to end, through the entry points a user calls:

1. every data-plane primitive runs at ``--parity-rows`` rows on the device
   path and on numpy, and must be bitwise-equal;
2. a ten-MV workload over five Zipf-skewed base tables (2^28 bytes per
   root, about 8.4e6 rows each) is calibrated, built, and refreshed for two
   mixed-churn incremental rounds at P=8 partitions with four compute
   workers, on the data plane the platform selects (``xla`` on a TPU), with
   a Memory Catalog budget of 1.6% of the dataset bytes;
3. the same scenario runs again, unpartitioned, on the numpy reference, and
   every MV must reassemble bitwise-identically
   (``verify_partitioned_equivalence``).

Stores live under ``results/chip_smoke/`` and are removed at the end. Wall
times include compilation: this is a smoke run, not a benchmark. Exits
nonzero, with no result line, when JAX finds no TPU or any phase fails; the
last line of standard output is the JSON result naming the device.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

DEFAULT_BYTES_PER_ROOT = 1 << 28
N_PARTITIONS = 8
N_WORKERS = 4
CATALOG_FRACTION = 0.016  # the paper's catalog-to-dataset ratio
SPEC_KW = dict(ingest_frac=0.01, update_frac=0.005, delete_frac=0.005,
               n_rounds=2)


class CompileCounter:
    """Counts backend compiles per refresh round (JAX's compile event,
    keyed by the round the engine is running)."""

    def __init__(self):
        from repro.obs import trace as obs_trace
        from repro.runtime.jax_private import backend_compile_event

        self._event = backend_compile_event()
        self._round = obs_trace.current_round
        self.per_round: dict[int, list[float]] = {}

    def __call__(self, event: str, duration: float, **_):
        if event == self._event:
            self.per_round.setdefault(self._round(), []).append(duration)

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self)


def refresh_check(bytes_per_root: int, work_dir: Path, impl: str = "auto",
                  log=print) -> dict:
    """Calibrate, refresh at P=8 on ``impl`` (``"auto"``: the platform's
    data plane), rerun unpartitioned on numpy, and verify bitwise. Returns
    the per-phase wall times and the per-round compile counts."""
    from repro.core import CostModel
    from repro.mv import (
        DiskStore, UpdateSpec, calibrate_sizes, generate_workload,
        realize_workload, run_partitioned_scenario,
        verify_partitioned_equivalence,
    )
    from repro.mv import dataplane as dp

    shutil.rmtree(work_dir, ignore_errors=True)
    work_dir.mkdir(parents=True)
    times: dict[str, float] = {}
    used = dp.resolve_impl(impl)
    log(f"impl={used}")
    with dp.use_impl(used):
        t0 = time.perf_counter()
        wl = realize_workload(
            generate_workload(10, seed=23), bytes_per_root=bytes_per_root,
            seed=23, key_skew=1.3,
        )
        wl = calibrate_sizes(wl, DiskStore(work_dir / "calib"))
        shutil.rmtree(work_dir / "calib")
        times["calibrate"] = time.perf_counter() - t0
        dataset = sum(n.size for n in wl.nodes)
        budget = CATALOG_FRACTION * dataset
        log(f"workload {wl.name}: {wl.n} MVs, {dataset / 1e9:.3f} GB, "
            f"catalog budget {budget / 1e6:.1f} MB")
        spec = UpdateSpec(mode="incremental", **SPEC_KW)
        store = DiskStore(work_dir / "dev")
        with CompileCounter() as compiles:
            t0 = time.perf_counter()
            rep = run_partitioned_scenario(
                wl, N_PARTITIONS, store, budget, spec, CostModel(),
                n_compute_workers=N_WORKERS,
            )
            times["scenario"] = time.perf_counter() - t0
    for r in rep.rounds:
        name = "build" if r.round_idx == 0 else f"round{r.round_idx}"
        times[name] = r.elapsed
        c = compiles.per_round.get(r.round_idx, [])
        log(f"{name}: {r.elapsed:.3f}s engine wall, {len(c)} compiles "
            f"({sum(c):.3f}s), {len(r.run.executed)} tasks run, "
            f"peak catalog {r.run.peak_catalog_bytes / 1e6:.1f} MB")
    with dp.use_impl("numpy"):
        ref = DiskStore(work_dir / "ref")
        t0 = time.perf_counter()
        run_partitioned_scenario(wl, 1, ref, budget, spec, CostModel(),
                                 n_compute_workers=N_WORKERS)
        times["reference"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    verify_partitioned_equivalence(wl, store, N_PARTITIONS, ref)
    times["verify"] = time.perf_counter() - t0
    log(f"verify: P={N_PARTITIONS} {used} store == unpartitioned numpy "
        f"reference, bitwise, {wl.n} MVs")
    shutil.rmtree(work_dir, ignore_errors=True)
    return dict(impl=used, times=times, compiles={
        r: len(c) for r, c in sorted(compiles.per_round.items())
    })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--bytes-per-root", type=int,
                    default=DEFAULT_BYTES_PER_ROOT)
    ap.add_argument("--parity-rows", type=int, default=10_000_000)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform})",
              file=sys.stderr)
        return 1
    from benchmarks.compile_cache import enable_compile_cache
    from benchmarks.tableops_bench import parity_report
    from repro.mv import dataplane as dp

    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    print(f"compile cache: {enable_compile_cache()}")
    if args.bytes_per_root != DEFAULT_BYTES_PER_ROOT:
        print(f"size cut: bytes_per_root={args.bytes_per_root} "
              f"(full size {DEFAULT_BYTES_PER_ROOT})")
    impl = dp.resolve_impl()
    if impl != "xla":
        print(f"chip_smoke: data plane resolved to {impl!r}, not 'xla'",
              file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    parity = parity_report(args.parity_rows, impl)
    for op, verdict in parity.items():
        print(f"parity {op} n={args.parity_rows} {impl}: {verdict}")
    print(f"parity: {time.perf_counter() - t0:.3f}s")
    if any(v != "bitwise-equal" for v in parity.values()):
        return 1

    out = refresh_check(args.bytes_per_root, REPO / "results" / "chip_smoke")
    print("phase times (s): " + " ".join(
        f"{k}={v:.3f}" for k, v in out["times"].items()))
    sizes = {k: v._cache_size() for k, v in dp._jk().items()}
    with dp.use_impl(impl), dp._lazy_x64():
        probe = dp._jk()["hash"](np.arange(8, dtype=np.int64))
    where = sorted({d.platform for d in probe.devices()})
    print(f"device work: jit cache sizes {sizes}, kernel output on {where}")
    if sum(sizes.values()) == 0 or where != ["tpu"]:
        print("chip_smoke: no data-plane kernel ran on the TPU",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
