"""Data-plane dispatch and parity (DESIGN.md §9).

The acceptance claim of the JAX/Pallas data plane: interpret-mode Pallas and
jitted-XLA outputs are **bitwise-equal** to the numpy reference for every
ported operator — hash partitioning, filter/project/map, fixed-point
agg/merge_agg, and the zset_join_delta probe — across seeds × update kinds,
including the edge cases the property suite skips (empty tables, empty
deltas, all-tombstone deltas, |w|>1 weights at the AGG_QUANTUM boundary).
End-to-end: the full partitioned scenario matrix under ``SC_DATAPLANE=jax``
is bitwise-identical to the numpy-path full recompute.

Dispatch contract: env read once at import, runtime overrides through
``set_impl``/``use_impl`` (which restores the JAX x64 setting), and with
nothing configured the platform decides.
"""
import shutil
import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro.mv import dataplane as dp
from repro.mv import tableops as T
from repro.mv.partition import dirty_partitions, partition_of, partition_table

IMPLS = ["jax", "interpret"]  # compared against the numpy reference
SEEDS = [3, 11, 2026]


def assert_bitwise(a, b, ctx=""):
    """Bitwise table equality (column set, dtype, shape, bytes)."""
    T.assert_tables_bitwise(dict(a), dict(b), ctx)


def assert_arrays_bitwise(ref, got, ctx=""):
    ref = ref if isinstance(ref, tuple) else (ref,)
    got = got if isinstance(got, tuple) else (got,)
    assert len(ref) == len(got), ctx
    for i, (r, g) in enumerate(zip(ref, got)):
        r, g = np.asarray(r), np.asarray(g)
        assert r.dtype == g.dtype, (ctx, i, r.dtype, g.dtype)
        assert r.shape == g.shape, (ctx, i, r.shape, g.shape)
        assert r.tobytes() == g.tobytes(), (ctx, i, "bytes differ")


def make_delta(base, kind, seed, n=400):
    """A Z-set delta of one update kind over ``base``."""
    rng = np.random.default_rng(seed)
    cols = list(base)
    idx = rng.choice(T.n_rows(base), min(n, T.n_rows(base)), replace=False)
    retr = {k: np.asarray(base[k])[idx].copy() for k in cols}
    retr["weight"] = -rng.choice(np.asarray([1, 1, 2], np.int64), len(idx))
    ins = T.make_base_table(
        n, len([k for k in cols if k != "rid"]), seed=seed + 1,
        rid_base=T.make_rid_base(1, 0),
    )
    ins = {k: ins.get(k, np.zeros(n, np.asarray(base[k]).dtype))
           for k in cols}
    ins["weight"] = rng.choice(np.asarray([1, 1, 2, 3], np.int64), n)
    if kind == "insert":
        return ins
    if kind == "tombstone":  # all-retraction delta (pure DELETE round)
        return retr
    return T.concat_tables([retr, ins])  # mixed update/delete/insert


@pytest.fixture(params=SEEDS)
def tables(request):
    seed = request.param
    base = T.make_base_table(3000, 4, seed=seed, rid_base=0)
    right = T.make_base_table(800, 3, seed=seed + 50, rid_base=1 << 40)
    return dict(seed=seed, base=base, right=right)


# ---------------------------------------------------------------------------
# per-primitive parity: jitted-XLA and interpret-Pallas vs numpy, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
def test_hash_partition_primitives_bitwise(tables, impl):
    keys = tables["base"]["key"]
    ref = (dp.hash64(keys), dp.partition_ids(keys, 13),
           *dp.partition_index(keys, 13))
    with dp.use_impl(impl):
        got = (dp.hash64(keys), dp.partition_ids(keys, 13),
               *dp.partition_index(keys, 13))
    assert_arrays_bitwise(ref, got, f"hash/{impl}")


@pytest.mark.parametrize("impl", IMPLS)
def test_partition_table_and_dirty_bitwise(tables, impl):
    delta = make_delta(tables["base"], "mixed", tables["seed"])
    ref_parts = partition_table(delta, 7)
    ref_pid = partition_of(delta["key"], 7)
    ref_dirty = dirty_partitions(delta, 7)
    with dp.use_impl(impl):
        got_parts = partition_table(delta, 7)
        assert_arrays_bitwise(ref_pid, partition_of(delta["key"], 7),
                              f"pid/{impl}")
        assert dirty_partitions(delta, 7) == ref_dirty
    assert len(got_parts) == len(ref_parts)
    for p, (rp, gp) in enumerate(zip(ref_parts, got_parts)):
        assert_bitwise(rp, gp, f"partition {p}/{impl}")


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("kind", ["insert", "mixed", "tombstone"])
def test_row_ops_bitwise_across_update_kinds(tables, impl, kind):
    delta = make_delta(tables["base"], kind, tables["seed"])
    ref = {
        "filter": T.op_filter(delta, "c0", 0.1),
        "project": T.op_project(delta, 0.6),
        "map": T.op_map(delta),
        "agg": T.op_agg(delta),
    }
    with dp.use_impl(impl):
        assert_bitwise(ref["filter"], T.op_filter(delta, "c0", 0.1),
                       f"filter/{impl}/{kind}")
        assert_bitwise(ref["project"], T.op_project(delta, 0.6),
                       f"project/{impl}/{kind}")
        assert_bitwise(ref["map"], T.op_map(delta), f"map/{impl}/{kind}")
        assert_bitwise(ref["agg"], T.op_agg(delta), f"agg/{impl}/{kind}")


@pytest.mark.parametrize("impl", IMPLS)
def test_filter_compare_dtype_pinning(impl):
    rng = np.random.default_rng(5)
    for dtype in (np.float32, np.float64, np.int64):
        col = (rng.standard_normal(2000) * 100).astype(dtype)
        ref = dp.filter_mask(col, 0.5)
        with dp.use_impl(impl):
            got = dp.filter_mask(col, 0.5)
        assert_arrays_bitwise(ref, got, f"filter[{np.dtype(dtype)}]/{impl}")


@pytest.mark.parametrize("impl", IMPLS)
def test_map_single_and_two_column_bitwise(tables, impl):
    base = tables["base"]
    one_col = {k: base[k] for k in ("key", "rid", "c0")}
    ref2, ref1 = T.op_map(base), T.op_map(one_col)
    with dp.use_impl(impl):
        assert_bitwise(ref2, T.op_map(base), f"map2/{impl}")
        assert_bitwise(ref1, T.op_map(one_col), f"map1/{impl}")


@pytest.mark.parametrize("impl", IMPLS)
def test_agg_merge_roundtrip_bitwise(tables, impl):
    base, seed = tables["base"], tables["seed"]
    delta = make_delta(base, "mixed", seed)
    ref_old = T.op_agg(base)
    ref_d = T.op_agg(delta)
    ref_merged = T.merge_agg(ref_old, ref_d)
    with dp.use_impl(impl):
        got_old = T.op_agg(base)
        got_d = T.op_agg(delta)
        got_merged = T.merge_agg(got_old, got_d)
    assert_bitwise(ref_old, got_old, f"agg/{impl}")
    assert_bitwise(ref_d, got_d, f"agg-delta/{impl}")
    assert_bitwise(ref_merged, got_merged, f"merge/{impl}")


@pytest.mark.parametrize("impl", IMPLS)
def test_join_and_zset_join_delta_bitwise(tables, impl):
    base, right, seed = tables["base"], tables["right"], tables["seed"]
    ld = make_delta(base, "mixed", seed)
    rd = make_delta(right, "mixed", seed + 7, n=120)
    ref_join = T.op_join(base, right)
    ref_delta, ref_corr = T.zset_join_delta(base, ld, right, rd)
    with dp.use_impl(impl):
        assert_bitwise(ref_join, T.op_join(base, right), f"join/{impl}")
        got_delta, got_corr = T.zset_join_delta(base, ld, right, rd)
    assert got_corr == ref_corr
    assert_bitwise(ref_delta, got_delta, f"join-delta/{impl}")


# ---------------------------------------------------------------------------
# edge cases the property suite skips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["numpy"] + IMPLS)
def test_empty_tables_and_deltas(impl):
    empty = T.empty_like({"key": np.int64, "rid": np.int64,
                          "c0": np.float32, "weight": np.int64})
    with dp.use_impl(impl):
        assert T.n_rows(T.op_filter(empty, "c0", 0.0)) == 0
        assert T.n_rows(T.op_map(empty)) == 0
        agg = T.op_agg(empty)
        assert T.n_rows(agg) == 0 and set(agg) == {"key", "sum_c0", "count"}
        assert dirty_partitions(empty, 8) == []
        parts = partition_table(empty, 4)
        assert len(parts) == 4 and all(T.n_rows(p) == 0 for p in parts)
        base = T.make_base_table(100, 3, seed=1, rid_base=0)
        d, corr = T.zset_join_delta(base, empty, base, empty)
        assert T.n_rows(d) == 0 and corr == 0
        hit, pos = dp.probe_sorted(np.empty(0, np.int64), base["key"])
        assert not hit.any() and (pos == 0).all()


@pytest.mark.parametrize("impl", ["numpy"] + IMPLS)
def test_all_tombstone_delta_ops(impl):
    base = T.make_base_table(500, 4, seed=9, rid_base=0)
    tomb = make_delta(base, "tombstone", 9)
    ref = {}
    with dp.use_impl("numpy"):
        ref = dict(agg=T.op_agg(tomb), flt=T.op_filter(tomb, "c0", 0.0),
                   mp=T.op_map(tomb))
    with dp.use_impl(impl):
        assert_bitwise(ref["agg"], T.op_agg(tomb), f"tomb-agg/{impl}")
        assert_bitwise(ref["flt"], T.op_filter(tomb, "c0", 0.0),
                       f"tomb-filter/{impl}")
        assert_bitwise(ref["mp"], T.op_map(tomb), f"tomb-map/{impl}")
        # every weight stays negative through the row ops
        assert (T.weights_of(T.op_map(tomb)) < 0).all()


@pytest.mark.parametrize("impl", ["numpy"] + IMPLS)
def test_large_weights_at_quantum_boundary(impl):
    """|w|>1 contributions at values straddling the AGG_QUANTUM rounding
    boundary: sum must be weight * fixed_point(v) exactly, and a retraction
    with the same |w| must cancel bitwise."""
    half_ulp = 0.5 / T.AGG_QUANTUM
    vals = np.asarray(
        [half_ulp, -half_ulp, 3 * half_ulp, 1.0 + half_ulp, 123.456],
        np.float64,
    )
    keys = np.arange(len(vals), dtype=np.int64)
    w = np.asarray([7, -7, 5, 1000, -3], np.int64)
    t = {"key": keys, "v": vals, "weight": w}
    with dp.use_impl(impl):
        agg = T.op_agg(t)
    fp = np.rint(vals * T.AGG_QUANTUM).astype(np.int64)
    np.testing.assert_array_equal(
        agg["sum_v"], (fp * w).astype(np.float64) / T.AGG_QUANTUM
    )
    np.testing.assert_array_equal(agg["count"], w)
    # retract exactly: merge of +w and -w partials nets to no groups
    t_neg = dict(t, weight=-w)
    with dp.use_impl(impl):
        merged = T.merge_agg(T.op_agg(t), T.op_agg(t_neg))
    assert T.n_rows(merged) == 0


# ---------------------------------------------------------------------------
# dispatch contract
# ---------------------------------------------------------------------------

def test_env_read_once_and_override_hook(monkeypatch):
    # mutating the environment mid-run must NOT flip the resolved impl...
    monkeypatch.setenv("SC_DATAPLANE", "jax")
    assert dp.resolve_impl("auto") == "numpy"  # config captured at import
    # ...the explicit hook does
    prev = dp.set_impl("jax")
    try:
        assert dp.resolve_impl("auto") == "xla"
        # ``None`` re-reads the environment
        assert dp.set_impl(None) == "xla"
        assert dp.resolve_impl("auto") == "xla"
    finally:
        dp.set_impl(prev)
    assert dp.resolve_impl("auto") == "numpy"


def test_auto_resolves_from_the_platform(monkeypatch):
    """With nothing configured: numpy reference on CPU, the jitted xla
    path on a TPU."""
    monkeypatch.setattr(dp, "_configured", "auto")
    assert dp.platform() == "cpu"
    assert dp.resolve_impl("auto") == "numpy"
    monkeypatch.setattr(dp, "platform", lambda: "tpu")
    assert dp.resolve_impl("auto") == "xla"
    # an explicit impl still wins over the platform
    assert dp.resolve_impl("numpy") == "numpy"
    with dp.use_impl("interpret"):
        assert dp.resolve_impl("auto") == "interpret"


def test_use_impl_restores_impl_and_x64():
    import jax

    before_impl = dp.configured_impl()
    before_x64 = bool(jax.config.jax_enable_x64)
    with dp.use_impl("jax"):
        assert dp.resolve_impl("auto") == "xla"
        dp.hash64(np.arange(4, dtype=np.int64))  # first primitive call...
        assert bool(jax.config.jax_enable_x64)  # ...enables the int64 path
    assert dp.configured_impl() == before_impl
    assert bool(jax.config.jax_enable_x64) == before_x64


def test_impl_aliases_and_validation():
    assert dp.resolve_impl("jax") == "xla"
    with pytest.raises(ValueError):
        dp.set_impl("cuda")
    with pytest.raises(ValueError):
        dp.resolve_impl("not-an-impl")


# ---------------------------------------------------------------------------
# size-model cache (catalog admission path)
# ---------------------------------------------------------------------------

def test_table_sizes_cached_and_consistent():
    base = T.make_base_table(1000, 3, seed=2, rid_base=0)
    d = T.with_weight(base, 2)
    phys, weighted = T.table_sizes(d)
    assert phys == T.table_nbytes(d)
    assert weighted == T.weighted_nbytes(d)
    # cache hit returns the same value; weakref entry keyed by the array
    assert T.table_sizes(d)[1] == weighted
    key = id(d["weight"])
    assert key in T._LIVE_ROWS_CACHE
    # dropping the array evicts the entry (no stale id reuse)
    del d, base
    assert key not in T._LIVE_ROWS_CACHE


def test_weighted_nbytes_mutation_safe_vs_cached_path():
    d = T.with_weight(T.make_base_table(100, 3, seed=4, rid_base=0), 3)
    first = T.table_sizes(d)[1]
    d["weight"] = np.full(100, 1, np.int64)  # rebind, not in-place: new key
    assert T.table_sizes(d)[1] != first
    assert T.weighted_nbytes(d) == T.table_sizes(d)[1]


# ---------------------------------------------------------------------------
# end-to-end: the partitioned scenario matrix on the jax data plane,
# bitwise vs the numpy-path full recompute (the cross-impl acceptance)
# ---------------------------------------------------------------------------

KINDS = {
    "insert": dict(ingest_frac=0.25, n_rounds=2),
    "mixed": dict(ingest_frac=0.15, update_frac=0.15, delete_frac=0.1,
                  n_rounds=2),
}


@pytest.mark.parametrize("impl", ["jax"])
def test_scenario_matrix_jax_dataplane_bitwise_vs_numpy_reference(impl):
    from repro.core import CostModel
    from repro.mv import (
        DiskStore, UpdateSpec, generate_workload, realize_workload,
        run_partitioned_scenario, run_scenario,
        verify_partitioned_equivalence, verify_scenario_equivalence,
    )

    cm = CostModel(disk_read_bw=50e6, disk_write_bw=50e6, mem_read_bw=1e12,
                   mem_write_bw=1e12, disk_latency=0.0)
    tmp = Path(tempfile.mkdtemp(prefix="dp_e2e_"))
    try:
        wl = realize_workload(
            generate_workload(8, seed=11), bytes_per_root=1 << 12
        )
        budget = sum(n.size for n in wl.nodes) * 0.4
        for kind, kw in KINDS.items():
            # reference: full recompute on the NUMPY path
            ref = DiskStore(tmp / f"ref_{kind}")
            run_scenario(wl, ref, budget, UpdateSpec(mode="full", **kw), cm)
            with dp.use_impl(impl):
                for P in (1, 4):
                    store = DiskStore(tmp / f"{kind}_p{P}")
                    run_partitioned_scenario(
                        wl, P, store, budget,
                        UpdateSpec(mode="incremental", **kw), cm,
                        n_compute_workers=2,
                    )
                    if P == 1:
                        verify_scenario_equivalence(wl, store, ref)
                    else:
                        verify_partitioned_equivalence(wl, store, P, ref)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# x64 exception safety, retrace buckets, and the stable-sort contract
# ---------------------------------------------------------------------------

def test_lazy_x64_restored_when_kernel_raises(monkeypatch):
    """A broken jitted path must not leak global x64 state: the error
    propagates AND jax_enable_x64 returns to its prior value."""
    import jax

    jax.config.update("jax_enable_x64", False)

    def boom():
        raise RuntimeError("kernel build failed")

    monkeypatch.setattr(dp, "_jk", boom)
    with dp.use_impl("jax"):
        with pytest.raises(RuntimeError, match="kernel build failed"):
            dp.hash64(np.arange(4, dtype=np.int64))
        assert not bool(jax.config.jax_enable_x64)
    assert not bool(jax.config.jax_enable_x64)


def test_lazy_x64_stays_enabled_on_success():
    import jax

    with dp.use_impl("jax"):
        dp.hash64(np.arange(4, dtype=np.int64))
        # lazy: left enabled so later primitives pay nothing
        assert bool(jax.config.jax_enable_x64)
    # use_impl's own exit restores the pre-context state


def test_probe_one_trace_per_pow2_bucket():
    """n_real is traced, so every uniq length inside one power-of-two pad
    bucket shares a single compiled probe (the historical static-argnums
    version retraced per distinct length)."""
    probe = np.array([2, 9, 64], dtype=np.int64)
    with dp.use_impl("jax"):
        kernel = dp._jk()["probe"]
        if not hasattr(kernel, "_cache_size"):
            pytest.skip("jax version without _cache_size introspection")
        before = kernel._cache_size()
        for n in (5, 6, 7):  # all pad to 8
            uniq = np.arange(n, dtype=np.int64) * 2
            hit, pos = dp.probe_sorted(uniq, probe)
            ref_hit, ref_pos = dp.probe_sorted(uniq, probe, impl="numpy")
            assert np.array_equal(hit, ref_hit)
            assert np.array_equal(pos, ref_pos)
        assert kernel._cache_size() - before <= 1


def test_pow2_padded_pads_to_the_next_power_of_two():
    for n in (1, 7, 8, 9, 41, 64, 65, 1000):
        keys = np.arange(n, dtype=np.int64) + 1
        w = -keys
        m, (pk, pw) = dp._pow2_padded(keys, w)
        assert m == n and len(pk) == len(pw) == dp._pow2_pad(n)
        assert len(pk) >= max(n, 8) and len(pk) & (len(pk) - 1) == 0
        assert np.array_equal(pk[:n], keys) and np.array_equal(pw[:n], w)
        assert not pk[n:].any() and not pw[n:].any()
        assert pk.dtype == keys.dtype and pw.dtype == w.dtype


def test_elementwise_kernel_one_compile_per_size_bucket():
    """Lengths in one bucket share a compile, and the zero padding never
    changes a real row's result."""
    with dp.use_impl("jax"):
        kernel = dp._jk()["hash"]
        dp.hash64(np.arange(41, dtype=np.int64))  # bucket 64
        before = kernel._cache_size()
        for n in (33, 41, 57, 64):
            keys = np.arange(n, dtype=np.int64) * 7919 - 3
            assert np.array_equal(dp.hash64(keys),
                                  dp.hash64(keys, impl="numpy"))
        assert kernel._cache_size() == before


def _with_subnormals(n, seed):
    """Standard-normal float32 draws with every fifth value a subnormal of
    either sign (XLA flushes those to zero)."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal(n).astype(np.float32)
    tiny = np.finfo(np.float32).tiny
    sub = (rng.uniform(1e-6, 1.0, n // 5) * tiny).astype(np.float32)
    a[::5][: len(sub)] = sub * rng.choice(np.float32([-1, 1]), len(sub))
    assert np.any((a != 0) & (np.abs(a) < tiny))
    return a


@pytest.mark.parametrize("impl", IMPLS)
def test_subnormal_inputs_stay_bitwise(impl):
    """Filter, map and AGG encode of float32 columns holding subnormals give
    the numpy reference's rows and bits on every impl."""
    a, b = _with_subnormals(3000, 1), _with_subnormals(3000, 2)
    w = np.resize(np.asarray([-2, -1, 1, 3], np.int64), 3000)
    ref = (dp.filter_mask(a, 0.0), dp.map_derived(a, b),
           dp.map_derived(a, None), dp.fixed_point_encode(a, w))
    with dp.use_impl(impl):
        got = (dp.filter_mask(a, 0.0), dp.map_derived(a, b),
               dp.map_derived(a, None), dp.fixed_point_encode(a, w))
    assert_arrays_bitwise(ref, got, f"subnormal/{impl}")


def test_map_multiply_flags_subnormal_columns():
    """The jitted multiply reports whether its column holds a subnormal, so
    map_derived multiplies such a column on the host."""
    a = _with_subnormals(64, 3)
    with dp.use_impl("jax"), dp._lazy_x64():
        kernel = dp._jk()["map_mul"]
        prod, flag = kernel(np.abs(a) + np.float32(1))
        assert not bool(flag)
        assert np.array_equal(np.asarray(prod),
                              (np.abs(a) + np.float32(1)) * np.float32(1.0001))
        assert bool(kernel(a)[1])
        assert bool(kernel(np.float32([0.0, -0.0, 1e-45]))[1])
        assert not bool(kernel(np.float32([0.0, -0.0, np.inf, np.nan]))[1])


def test_parity_report_bitwise_with_subnormal_inputs():
    """The chip smoke's parity gate covers inputs with subnormal values and
    passes on both jitted paths at a small size."""
    from benchmarks.tableops_bench import parity_report

    for impl in ("xla", "interpret"):
        report = parity_report(4096, impl)
        assert {"map", "map+subnormal", "filter+subnormal"} <= set(report)
        assert set(report.values()) == {"bitwise-equal"}, (impl, report)


def test_group_reduce_stable_flag_bitwise_equal_for_int_sums():
    """op_agg's declared contract: exact int64 sums commute, so the unstable
    grouping sort and the pinned stable sort give bitwise-equal results."""
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 50, size=4000).astype(np.int64)
    vals = rng.normal(size=4000).astype(np.float32)
    w = rng.integers(-3, 4, size=4000).astype(np.int64)
    with dp.use_impl("jax"):
        a = dp.group_reduce(keys, {"s": (vals, "fixed")}, w, stable=False)
        b = dp.group_reduce(keys, {"s": (vals, "fixed")}, w, stable=True)
    for x, y in zip(a, b):
        if isinstance(x, dict):
            assert set(x) == set(y)
            for name in x:
                assert np.array_equal(x[name], y[name])
        else:
            assert np.array_equal(x, y)
