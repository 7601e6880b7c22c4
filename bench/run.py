"""Back-to-back MV refresh rounds on one TPU chip, for a fixed window.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: refuses to run without a TPU; turns on the persistent compile
cache; loads the cell's configuration, traffic and store tier by name
(``bench/cells.py``); generates the base tables from ``--seed``
(``bench/traffic.py``); calibrates the sizes; and drives the program's
``run_partitioned_scenario`` on the platform's data plane. The build
(round 0), a warm-up of the data plane's programs (``bench/warmup.py``)
and one warm refresh round are set-up; whole refresh rounds then
run back to back until ``--seconds`` have passed, timed at the store's
commits (``bench/clock.py``). Afterwards every partition of every MV in
the store is compared with a full numpy recompute (``bench/reference.py``).

The last line of standard output is the result (JSON); the numbers the
comparison checked, each beside its limit, are the last lines of standard
error and the result's last key. With ``--trace 1`` the program's spans
and a JAX profiler trace of the window give the per-layer metrics of
``bench/metrics/`` instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cells, clock, devtrace, traffic  # noqa: E402
from bench.observe import Observed, load_reader  # noqa: E402
from bench.reference import Reference, compare  # noqa: E402
from bench.warmup import warm_data_plane  # noqa: E402

WORK_DIR = ROOT / ".bench_work"
WARM_ROUND = 1  # round 0 builds; round 1 warms; the window starts after it


def build_workload(config: dict, sources_for):
    """The program's realization of the configured paper workload, with
    each SCAN node's rows coming from the benchmark's traffic sources."""
    from repro.mv import paper_workloads, realize_workload

    named = {w.name.split("@")[0]: w
             for w in paper_workloads(float(config["scale_gb"]))}
    w = named[config["program_workload"]]
    got = [(n.op, list(n.parents)) for n in w.nodes]
    want = [(n["op"], n["parents"]) for n in config["nodes"]]
    if got != want:
        raise ValueError(f"{w.name}: the program's DAG differs from "
                         f"{config['name']}'s configuration file")
    wl = realize_workload(w, bytes_per_root=int(config["bytes_per_root"]),
                          n_cols=int(config["n_cols"]),
                          key_skew=float(config["key_skew"]))
    scans = [i for i, n in enumerate(wl.nodes) if not n.parents]
    sources = sources_for(scans)
    nodes = [
        dataclasses.replace(n, fn=sources[i].fn, delta_fn=sources[i].delta_fn)
        if i in sources else n
        for i, n in enumerate(wl.nodes)
    ]
    return dataclasses.replace(wl, nodes=nodes), sources


def _counter_totals(names) -> dict[str, float]:
    from repro.obs.metrics import METRICS

    return {n: sum(METRICS.counter_family(n).values()) for n in names}


_COUNTERS = ("catalog_hits", "catalog_misses")


@dataclasses.dataclass
class Drive:
    """One driven window: what ``report`` reads once the scenario stopped."""

    cell: dict
    config: dict
    store: object  # the commit-clock DiskStore
    sources: dict  # scan node -> traffic.ScanSource
    window: traffic.Window
    budget: float
    compiles: object  # compiles.CompileCounter
    failed: int
    state: dict  # window start, per-round parts, counter snapshots
    devices: list
    profiler: devtrace.Profiler | None = None
    xplane: Path | None = None


def drive(name: str, seed: int, seconds: float, trace: bool, *,
          on_chip: bool = True, overrides: dict | None = None,
          work_dir: Path = WORK_DIR, log=print) -> Drive:
    """Set up cell ``name`` and run refresh rounds until the window ends.

    ``on_chip=False`` skips the compile cache (the CPU rehearsals in
    ``tests/bench``); ``overrides`` replaces keys of the configuration (the
    rehearsals' tiny sizes). With ``trace`` the program's sc-trace spans
    are on for the whole run and the profiler from the warm round on."""
    import jax

    from repro.core import CostModel
    from repro.mv import DiskStore, UpdateSpec, calibrate_sizes
    from repro.mv import run_partitioned_scenario
    from repro.mv import dataplane as dp
    from repro.obs import trace as obs_trace
    from repro.obs.metrics import METRICS

    from bench.compiles import CompileCounter

    cell = cells.load_cell(name)
    config = dict(cell["config"], **(overrides or {}))
    tfc, tier = cell["traffic"], cell["store"]
    devices = jax.devices()
    if on_chip:
        from benchmarks.compile_cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; data plane {dp.resolve_impl()}")

    shutil.rmtree(work_dir, ignore_errors=True)
    work_dir.mkdir(parents=True)
    obs_trace.enable(trace)
    obs_trace.clear()
    METRICS.clear()

    window = traffic.Window(first_window_round=WARM_ROUND + 1)
    wl, sources = build_workload(
        config, lambda scans: traffic.make_sources(scans, config, tfc, seed,
                                                   window))
    wl = calibrate_sizes(wl, DiskStore(work_dir / "calib"))
    shutil.rmtree(work_dir / "calib")
    dataset = sum(n.size for n in wl.nodes)
    budget = float(config["catalog_fraction"]) * dataset
    log(f"workload {config['name']}: {wl.n} MVs x P={config['n_partitions']}"
        f", {dataset:.0f} calibrated bytes, catalog budget {budget:.0f} "
        "bytes")

    store = clock.clock_store(
        DiskStore, work_dir / "store", lambda: window.served,
        read_bw=tier["read_bw"], write_bw=tier["write_bw"],
        latency=float(tier["latency"]))
    window.round_end = store.ends.get
    spec = UpdateSpec(mode=tfc["mode"], ingest_frac=float(tfc["ingest_frac"]),
                      update_frac=float(tfc["update_frac"]),
                      delete_frac=float(tfc["delete_frac"]),
                      n_rounds=1 << 40)
    d = Drive(cell=cell, config=config, store=store, sources=sources,
              window=window, budget=budget, compiles=CompileCounter(),
              failed=0, state={"parts": []}, devices=devices,
              profiler=devtrace.Profiler(work_dir / "trace") if trace
              else None)

    def mean_parts() -> float:
        entries = store.manifest()
        return sum(store.parts(e) for e in entries) / max(len(entries), 1)

    def on_round(r: int) -> None:
        # on the scenario's thread, at round r's first delta request:
        # round r - 1 has committed everything, round r nothing yet
        d.state["parts"].append(mean_parts())
        if r == WARM_ROUND:
            t0 = time.perf_counter()
            built = d.compiles.programs_between(-math.inf, t0)
            calls = warm_data_plane(built, int(config["n_partitions"]))
            log(f"data plane warmed: {calls} calls in "
                f"{time.perf_counter() - t0:.3f} s")
            if d.profiler is not None:
                d.profiler.start()
        if r == WARM_ROUND + 1:
            d.state["start"] = store.ends[WARM_ROUND]
            window.deadline = d.state["start"] + seconds
            d.state["counters0"] = _counter_totals(_COUNTERS)

    window.listeners.append(on_round)
    with d.compiles:
        try:
            run_partitioned_scenario(
                wl, int(config["n_partitions"]), store, budget, spec,
                CostModel(**tier["cost_model"]),
                n_compute_workers=int(config["n_compute_workers"]),
                n_writers=int(config["n_writers"]),
                consolidate_ratio=float(config["consolidate_ratio"]),
            )
        except traffic.Stop:
            pass
        except Exception:  # the round failed: report it, with the traceback
            d.failed = 1
            traceback.print_exc()
    d.state["parts"].append(mean_parts())
    d.state["counters1"] = _counter_totals(_COUNTERS)
    if d.profiler is not None and d.profiler.anchor is not None:
        d.xplane = d.profiler.stop()
    return d


def _per_layer(d: Drive, rounds: dict, lo: float, hi: float, log):
    """Per-layer metrics, device busy time and the breakdown of a traced
    window, and the catalog's peak share of its budget."""
    from repro.obs import trace as obs_trace

    offset = time.perf_counter() - obs_trace.now()
    every = obs_trace.spans()
    spans = [(s.cat, s.round, s.ts + offset, s.dur, s.worker)
             for s in every if s.track == "real" and s.round in rounds]
    ops = modules = None
    device, breakdown = {}, None
    if d.xplane is not None:
        ops = devtrace.clip(devtrace.device_events(
            d.xplane, d.profiler.anchor, devtrace.OPS_LINE), lo, hi)
        modules = devtrace.clip(devtrace.device_events(
            d.xplane, d.profiler.anchor, devtrace.MODULES_LINE), lo, hi)
        device = {"busy_s": devtrace.busy_seconds(ops), "window_s": hi - lo}
        round_spans = {r: s for c, r, s, _, _ in spans if c == "round"}
        plan = [(rounds[r][0], round_spans[r]) for r in rounds
                if r in round_spans]
        host = [(c, s, s + dur) for c, _, s, dur, _ in spans
                if dur > 0 and c != "round"]
        breakdown = {
            "device_ops": devtrace.top_ops(devtrace.name_ops(ops, modules)),
            "idle_gaps": devtrace.longest_gaps(
                devtrace.idle_gaps(ops, lo, hi), host, plan),
        }
        log(f"device trace lines: {devtrace.device_lines(d.xplane)}")
        log(f"device ops in window: {len(ops)}, modules: {len(modules)}")
    c0, c1 = d.state["counters0"], d.state["counters1"]
    obs = Observed(
        rounds=rounds, spans=spans,
        counters={k: c1[k] - c0.get(k, 0.0) for k in c1},
        n_workers=int(d.config["n_compute_workers"]), window=(lo, hi),
        device_ops=ops, device_modules=modules)
    metrics = {}
    for m in d.cell["per_layer"]:
        v = load_reader(m["name"])(obs)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    samples = [s.value for s in every if s.track == "real"
               and s.cat == "counter" and s.name == "catalog.bytes"]
    peak = max(samples, default=0.0) / d.budget if d.budget else 0.0
    return metrics, device, breakdown, peak


def report(d: Drive, t_start: float, work_dir: Path = WORK_DIR,
           log=print) -> dict:
    """The result line's object: metrics of the window, the device, and
    the comparison of every stored partition with the reference."""
    from repro.mv import DiskStore
    from repro.obs import trace as obs_trace

    dev = d.devices[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(d.devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    # a round is whole once the next one is requested; after a failure the
    # round last served is taken as the one that failed
    first, last = WARM_ROUND + 1, d.window.served - d.failed
    opened = "start" in d.state and last >= first
    rounds = clock.window_rounds(d.store.commits, d.state["start"], first,
                                 last) if opened else {}
    lo, hi = (d.state["start"], rounds[last][1]) if opened else (0.0, 0.0)
    metrics: dict = {}
    breakdown = peak = None
    if opened and d.profiler is None:
        e2e = clock.end_to_end(d.store.commits, d.state["start"], first,
                               last)
        values = {"refresh_s": e2e["refresh_s"],
                  "fresh_p95_s": e2e["fresh_p95_s"],
                  "setup_s": d.state["start"] - t_start}
        for m in d.cell["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        log(f"freshness samples: {e2e['commits']} commits")
    elif opened:
        metrics, dev_trace, breakdown, peak = _per_layer(d, rounds, lo, hi,
                                                         log)
        device.update(dev_trace)
    obs_trace.enable(False)
    obs_trace.clear()

    in_window = d.compiles.between(lo, hi) if opened else []
    log(f"rounds in window: {len(rounds)} ({first}..{last}), "
        f"window {hi - lo:.6f} s")
    log(f"compiles in window: {len(in_window)} ({sum(in_window):.6f} s); "
        f"in all: {len(d.compiles.times)}")
    for fn, avals in (d.compiles.programs_between(lo, hi) if opened else []):
        log(f"  traced in window: {fn} {avals}")
    log(f"consolidations: {d.store.consolidations}")
    log("mean parts per MV entry at each round's end: " + " ".join(
        f"r{r}={p:.3f}" for r, p in enumerate(d.state["parts"])))
    log(f"catalog budget: {d.budget:.0f} bytes")

    # the reference runs after the window and the device reading
    t_ref = time.perf_counter()
    expected = Reference(d.config).evaluate(
        {i: s.final() for i, s in d.sources.items()})
    reader = DiskStore(work_dir / "store")
    cmp = compare(
        expected, lambda mv, p: reader.read(f"{mv}@p{p}"),
        [n["name"] for n in d.config["nodes"]],
        int(d.config["n_partitions"]))
    log(f"reference: {cmp['total']} entries compared after round {last} in "
        f"{time.perf_counter() - t_ref:.3f} s; {cmp['bad']} differ "
        f"{cmp['bad_names']}, {cmp['order_only']} of them in row order "
        "alone")

    checks = {"bad_entries": {"value": cmp["bad"], "limit": 0}}
    if peak is not None:
        checks["catalog_peak_share"] = {"value": peak, "limit": 1.0}
    correct = (not d.failed and opened
               and all(c["value"] <= c["limit"] for c in checks.values()))
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    result = {"correct": bool(correct), "attempted": len(rounds) + d.failed,
              "failed": d.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START, on_chip: bool = True,
             overrides: dict | None = None, work_dir: Path = WORK_DIR,
             log=print) -> dict:
    """One run of cell ``name``: ``drive`` then ``report``; the working
    directory is removed afterwards."""
    try:
        d = drive(name, seed, seconds, trace, on_chip=on_chip,
                  overrides=overrides, work_dir=work_dir, log=log)
        return report(d, t_start, work_dir, log)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    import jax

    import repro.mv  # noqa: F401  (the system under test, or no run)

    chips = int(cells.load_cell(args.workload)["workload"]["chips"])
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
