"""Operator-throughput microbenchmark for the MV data plane (DESIGN.md §9).

Roofline-style per-op report: rows/s and GB/s for every ported hot-path
primitive — splitmix64 hash, fused partition index, filter compare, the
two-kernel map expression, fixed-point AGG and its merge, and the join
probe — across ``impl`` in {numpy, jax} and row counts, the way planner
solve time is tracked by ``planner_scale``. The numpy column is the bitwise reference the
jitted path must beat; ``speedup`` is jax rows/s over numpy rows/s.

``--smoke`` (CI) swaps throughput for the parity gate: every primitive runs
at a small size on numpy + jitted-XLA + interpret-mode Pallas and the
outputs are asserted bitwise-equal in-run, then a single quick timing pass
records the numbers. The JSON artifact lands in ``results/bench/`` either
way.

Full mode asserts the acceptance claim: at the largest size (>= 1e7 rows),
at least two ported ops reach >= 2x rows/s over numpy on the jax path.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.mv import dataplane as dp
from repro.mv import tableops as T

from .common import fmt_table, save_json

N_PARTITIONS = 64
JOIN_INDEX_KEYS = 1 << 20


def _mk_inputs(n: int, seed: int = 7, subnormal: bool = False):
    """Inputs for every op at ``n`` rows; with ``subnormal``, every fifth
    value of the float columns is a float32 subnormal of either sign."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, max(n // 16, 4), n).astype(np.int64)
    a = rng.standard_normal(n).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    if subnormal:
        tiny = np.finfo(np.float32).tiny
        for col in (a, b):
            m = len(col[::5])
            col[::5] = (rng.uniform(1e-6, 1.0, m) * tiny).astype(
                np.float32) * rng.choice(np.float32([-1, 1]), m)
    w = rng.choice(np.asarray([-2, -1, 1, 2, 3], np.int64), n)
    uniq = np.unique(
        rng.integers(0, 1 << 40, min(JOIN_INDEX_KEYS, max(n // 8, 4)))
    ).astype(np.int64)
    probe = rng.choice(uniq, n) if len(uniq) else keys
    agg_table = {"key": keys, "c0": a, "c1": b, "weight": w}
    # two signed partial aggregates: merge_agg re-encodes their float64 sums
    half = n // 2
    partials = tuple(
        T.op_agg({k: v[sl] for k, v in agg_table.items()})
        for sl in (slice(0, half), slice(half, n))
    )
    return dict(keys=keys, a=a, b=b, w=w, uniq=uniq, probe=probe,
                agg=agg_table, partials=partials)


def _ops(inp):
    """name -> (thunk, logical bytes moved) for one input set."""
    n = len(inp["keys"])
    return {
        "hash": (lambda: dp.hash64(inp["keys"]), 16 * n),
        "partition_index": (
            lambda: dp.partition_index(inp["keys"], N_PARTITIONS), 24 * n
        ),
        "filter": (lambda: dp.filter_mask(inp["a"], 0.0), 5 * n),
        "map": (lambda: dp.map_derived(inp["a"], inp["b"]), 12 * n),
        "agg": (lambda: T.op_agg(inp["agg"]), 28 * n),
        "merge_agg": (lambda: T.merge_agg(*inp["partials"]), 32 * n),
        "join_probe": (
            lambda: dp.probe_sorted(inp["uniq"], inp["probe"]), 17 * n
        ),
    }


def _best_of(fn, reps: int) -> float:
    fn()  # warmup: jit traces/compiles land here, not in the timing
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _assert_bitwise_equal(name: str, impl: str, ref, got) -> None:
    ref_items = ref.items() if isinstance(ref, dict) else enumerate(
        ref if isinstance(ref, tuple) else (ref,)
    )
    got_seq = got if isinstance(got, (dict, tuple)) else (got,)
    for k, rv in ref_items:
        gv = got_seq[k]
        rv, gv = np.asarray(rv), np.asarray(gv)
        assert rv.dtype == gv.dtype and rv.shape == gv.shape and (
            rv.tobytes() == gv.tobytes()
        ), f"{name}[{k}]: {impl} output not bitwise-equal to numpy"


# the ops that read the float columns, checked again on subnormal inputs
_FLOAT_OPS = ("filter", "map", "agg", "merge_agg")


def parity_report(n: int, impl: str, seed: int = 7) -> dict[str, str]:
    """op -> "bitwise-equal" or the first difference, for every op at ``n``
    rows on ``impl`` against the numpy reference, and as "op+subnormal"
    for the float ops on inputs that hold subnormal values."""
    cases = list(_ops(_mk_inputs(int(n), seed)).items())
    sub = _ops(_mk_inputs(int(n), seed, subnormal=True))
    cases += [(f"{op}+subnormal", sub[op]) for op in _FLOAT_OPS]
    out = {}
    for op_name, (thunk, _) in cases:
        with dp.use_impl("numpy"):
            ref = thunk()
        with dp.use_impl(impl):
            got = thunk()
        try:
            _assert_bitwise_equal(op_name, impl, ref, got)
            out[op_name] = "bitwise-equal"
        except AssertionError as e:
            out[op_name] = f"DIFFERS: {e}"
    return out


def run(quick: bool = False, smoke: bool = False, sizes=None,
        assert_speedup: bool | None = None):
    smoke = smoke or quick
    if sizes is None:
        sizes = [200_000] if smoke else [1_000_000, 10_000_000]
    impls = ["numpy", "jax", "interpret"] if smoke else ["numpy", "jax"]
    if assert_speedup is None:
        assert_speedup = not smoke
    reps = 2 if smoke else 3

    records = []
    rows = []
    parity_checked = []
    for n in sizes:
        inp = _mk_inputs(int(n))
        ops = _ops(inp)
        for op_name, (thunk, nbytes) in ops.items():
            ref = None
            base_rate = None
            for impl in impls:
                with dp.use_impl(impl):
                    if impl == "numpy":
                        ref = thunk()
                    else:
                        _assert_bitwise_equal(op_name, impl, ref, thunk())
                        parity_checked.append((op_name, impl))
                    secs = _best_of(thunk, reps)
                rate = n / secs
                if impl == "numpy":
                    base_rate = rate
                rec = dict(
                    op=op_name, n=int(n), impl=impl, ms=secs * 1e3,
                    rows_per_s=rate, gb_per_s=nbytes / secs / 1e9,
                    speedup_vs_numpy=rate / base_rate,
                )
                records.append(rec)
                rows.append([
                    op_name, f"{int(n):.0e}", impl, f"{secs * 1e3:.1f}",
                    f"{rate / 1e6:.1f}M", f"{nbytes / secs / 1e9:.2f}",
                    f"{rate / base_rate:.2f}x",
                ])

    print(fmt_table(
        ["op", "rows", "impl", "ms", "rows/s", "GB/s", "vs numpy"], rows
    ))
    if parity_checked:
        n_ops = len({o for o, _ in parity_checked})
        print(f"\nparity gate: {n_ops} ops bitwise-equal across "
              f"{sorted({i for _, i in parity_checked})} vs numpy")

    top_n = max(sizes)
    fast = sorted(
        (r["speedup_vs_numpy"], r["op"]) for r in records
        if r["impl"] == "jax" and r["n"] == top_n
        and r["speedup_vs_numpy"] >= 2.0
    )
    print(f"jax ops >= 2x at n={top_n:.0e}: "
          f"{[f'{o} {s:.2f}x' for s, o in fast]}")
    payload = dict(
        sizes=[int(s) for s in sizes], impls=impls, records=records,
        parity_ops_checked=sorted({o for o, _ in parity_checked}),
        jax_ops_ge_2x_at_top=[o for _, o in fast],
    )
    save_json("tableops", payload, seed=7, speedups={
        f"jax_{o}_vs_numpy": s for s, o in fast
    })
    if assert_speedup:
        assert len(fast) >= 2, (
            f"acceptance: expected >=2 jax ops at >=2x rows/s over numpy at "
            f"n={top_n}, got {fast}"
        )
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="parity gate + quick timings (CI)")
    ap.add_argument("--sizes", type=int, nargs="*", default=None)
    args = ap.parse_args(argv)
    run(smoke=args.smoke, sizes=args.sizes)


if __name__ == "__main__":
    main()
