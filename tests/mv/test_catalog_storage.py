import random
import threading

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.mv import CatalogOverflowError, DiskStore, MemoryCatalog, table_nbytes


def test_catalog_accounting_and_overflow():
    cat = MemoryCatalog(100.0)
    cat.put("a", object(), 60.0)
    assert cat.used_bytes == 60.0
    assert cat.fits(40.0) and not cat.fits(41.0)
    with pytest.raises(CatalogOverflowError):
        cat.put("b", object(), 50.0)
    cat.put("b", object(), 40.0)
    assert cat.peak_bytes == 100.0
    cat.release("a")
    assert cat.used_bytes == 40.0
    assert "a" not in cat and "b" in cat
    # release is idempotent
    cat.release("a")


def test_catalog_rejects_duplicate():
    cat = MemoryCatalog(10.0)
    cat.put("a", 1, 1.0)
    with pytest.raises(KeyError):
        cat.put("a", 2, 1.0)


def test_diskstore_roundtrip_and_manifest(tmp_path):
    store = DiskStore(tmp_path)
    t = {"key": np.arange(10, dtype=np.int64), "c0": np.ones(10, np.float32)}
    store.write("mv1", t)
    assert store.exists("mv1")
    back = store.read("mv1")
    assert set(back) == set(t)
    for k in t:
        np.testing.assert_array_equal(back[k], t[k])
    assert store.manifest()["mv1"] == table_nbytes(t)
    store.delete("mv1")
    assert not store.exists("mv1")


def test_diskstore_throttle_and_counters(tmp_path):
    # 1 MB at 10 MB/s -> >= 0.1 s
    store = DiskStore(tmp_path, read_bw=10e6, write_bw=10e6, latency=0.0)
    t = {"x": np.zeros(1 << 18, np.float32)}  # 1 MiB
    wdt = store.write("big", t)
    assert wdt >= 0.09
    store.reset_counters()
    store.read("big")
    assert store.read_seconds >= 0.09


def _rows_table(n):
    rng = np.random.default_rng(n)
    vals = rng.standard_normal((3, n)).astype(np.float32)
    if n >= 4:  # bit patterns a value-level comparison would not tell apart
        vals[0, :4] = [-0.0, np.nan, np.float32(1e-45), np.inf]
    return {"key": rng.integers(0, 1 << 40, n, dtype=np.int64),
            "rid": np.arange(n, dtype=np.int64),
            "c0": vals[0], "c1": vals[1], "c2": vals[2]}


_PART_TABLES = {
    "rows": _rows_table(3550),
    "zset_delta": {
        "rid": np.array([7, 3, 7, 11], np.int64),
        "weight": np.array([-1, -1, 1, 1], np.int64),
        "key": np.array([5, 2, 5, 9], np.int64),
        "c0": np.array([1.5, -0.0, np.nan, 4.25], np.float32),
    },
    "agg": {
        "key": np.array([2, 5, 9], np.int64),
        "sum_c0": np.array([-(1 << 62), 65536, 3], np.int64),
        "count": np.array([4, 1, 2], np.int64),
    },
    "empty": _rows_table(0),
}


@pytest.mark.parametrize("case", sorted(_PART_TABLES))
def test_part_file_roundtrip_is_bitwise_and_truncation_raises(tmp_path, case):
    """A part reads back bit for bit, in column order, as writable aligned
    arrays; a part file cut short, or not a part file, raises instead of
    reading wrong."""
    t = _PART_TABLES[case]
    store = DiskStore(tmp_path)
    store.write("mv", {"rid": np.zeros(1, np.int64)})
    store.append("mv", t)
    back = store.read_parts("mv", 1)  # the raw part, weights intact
    assert list(back) == list(t)
    for k, v in t.items():
        assert back[k].dtype == v.dtype and back[k].shape == v.shape
        assert back[k].tobytes() == v.tobytes()
        assert back[k].flags.writeable and back[k].flags.aligned
    path = store._path("mv", store._part_ids("mv")[-1])
    whole = path.read_bytes()
    for damaged in (whole[:-1], b"PK\x03\x04" + whole[4:]):
        path.write_bytes(damaged)
        with pytest.raises(ValueError):
            store.read_parts("mv", 1)


def test_diskstore_write_is_atomic(tmp_path):
    store = DiskStore(tmp_path)
    store.write("a", {"x": np.arange(4)})
    # a stray tmp file (simulated crash) must not appear in the manifest
    (tmp_path / "b.col.tmp").write_bytes(b"partial")
    assert not store.exists("b")


def test_catalog_clear_resets_peak_and_reset_stats():
    cat = MemoryCatalog(100.0)
    cat.put("a", object(), 80.0)
    cat.release("a")
    assert cat.peak_bytes == 80.0
    # restart path: a reused catalog must not report the stale peak
    cat.clear()
    assert cat.peak_bytes == 0.0 and cat.used_bytes == 0.0
    cat.put("b", object(), 30.0)
    cat.put("c", object(), 20.0)
    cat.release("c")
    cat.reset_stats()  # keeps residents, resets peak to current usage
    assert "b" in cat and cat.peak_bytes == 30.0


@settings(max_examples=10)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_catalog_peak_semantics_under_concurrent_put_release(seed):
    """Property: under racing try_put/release from several threads — with a
    mid-run ``clear()`` (the engine restart path) and ``reset_stats()``
    thrown in — byte accounting never corrupts: usage stays within
    [0, budget], peak never exceeds the budget (atomic admission), and at
    quiescence usage equals the sum of resident entries, ``reset_stats``
    re-bases the peak to exactly that, and ``clear`` zeroes everything."""
    rng = random.Random(seed)
    budget = 1000.0
    cat = MemoryCatalog(budget)
    n_threads, n_ops = 4, 60
    sizes = [
        [rng.uniform(1.0, 400.0) for _ in range(n_ops)]
        for _ in range(n_threads)
    ]
    start = threading.Barrier(n_threads + 1)

    def worker(tid):
        start.wait()
        for i, size in enumerate(sizes[tid]):
            name = f"t{tid}e{i}"
            if cat.try_put(name, object(), size) and i % 3 != 0:
                cat.release(name)
            if i % 17 == 0:
                cat.reset_stats()

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
    ]
    for th in threads:
        th.start()
    start.wait()
    cat.clear()  # restart mid-flight: must not break later accounting
    for th in threads:
        th.join()

    resident = cat.resident()
    assert cat.used_bytes == pytest.approx(sum(resident.values()))
    assert 0.0 <= cat.used_bytes <= budget + 1e-9
    assert cat.used_bytes <= cat.peak_bytes <= budget + 1e-9
    cat.reset_stats()
    assert cat.peak_bytes == pytest.approx(cat.used_bytes)
    cat.clear()
    assert cat.used_bytes == 0.0 and cat.peak_bytes == 0.0
    assert cat.resident() == {} and cat.fits(budget)


def test_diskstore_append_parts_roundtrip(tmp_path):
    store = DiskStore(tmp_path)
    t0 = {"key": np.arange(6, dtype=np.int64), "x": np.ones(6, np.float32)}
    d1 = {"key": np.arange(3, dtype=np.int64), "x": np.full(3, 2, np.float32)}
    d2 = {"key": np.arange(2, dtype=np.int64), "x": np.full(2, 3, np.float32)}
    store.write("mv", t0)
    store.append("mv", d1)
    store.append("mv", d2)
    assert store.parts("mv") == 3
    assert store.manifest()["mv"] == sum(map(table_nbytes, (t0, d1, d2)))
    full = store.read("mv")
    np.testing.assert_array_equal(
        full["x"], np.concatenate([t0["x"], d1["x"], d2["x"]])
    )
    # prefix = old content, suffix = the deltas
    np.testing.assert_array_equal(store.read_parts("mv", 0, 1)["x"], t0["x"])
    np.testing.assert_array_equal(
        store.read_parts("mv", 1)["x"], np.concatenate([d1["x"], d2["x"]])
    )
    # a full write replaces every part
    store.write("mv", t0)
    assert store.parts("mv") == 1
    assert store.manifest()["mv"] == table_nbytes(t0)
    np.testing.assert_array_equal(store.read("mv")["x"], t0["x"])


def test_diskstore_append_throttles_on_delta_bytes(tmp_path):
    # at 1 MB/s, charging total bytes (1 MiB + 4 KiB) would sleep >= 1.05s;
    # charging delta bytes sleeps ~4 ms (generous margin absorbs fsync noise)
    store = DiskStore(tmp_path, write_bw=1e6)
    big = {"x": np.zeros(1 << 18, np.float32)}   # 1 MiB
    small = {"x": np.zeros(1 << 10, np.float32)}  # 4 KiB
    store.write("mv", big)
    dt = store.append("mv", small)
    assert dt < 0.5, "append must be charged delta bytes, not total bytes"


def test_diskstore_rewrite_of_multipart_mv_is_crash_atomic(tmp_path):
    """A rewrite that crashes before the manifest commit must leave the old
    multi-part content fully intact (never new-part-0 + stale deltas)."""
    store = DiskStore(tmp_path)
    store.write("mv", {"x": np.arange(4)})
    store.append("mv", {"x": np.arange(4, 6)})
    # simulate a crashed write(): the new part lands on an id the manifest
    # does not reference, then the process dies before _record
    new_id = max(store._part_ids("mv")) + 1
    store._write_part("mv", new_id, {"x": np.full(3, 100)})
    np.testing.assert_array_equal(store.read("mv")["x"], np.arange(6))
    assert store.parts("mv") == 2
    # the next real write lands cleanly despite the orphan
    store.write("mv", {"x": np.full(3, 7)})
    np.testing.assert_array_equal(store.read("mv")["x"], np.full(3, 7))
    assert store.parts("mv") == 1


def _zset(rids, weight, **cols):
    t = {"rid": np.asarray(rids, np.int64),
         "weight": np.full(len(rids), weight, np.int64)}
    for k, v in cols.items():
        t[k] = np.asarray(v)
    return t


def test_diskstore_tombstone_append_retract_consolidate_roundtrip(tmp_path):
    """Z-set delta parts: updates splice at their old rid, deletes drop out,
    and reads consolidate — weight columns never reach the caller."""
    store = DiskStore(tmp_path)
    base = {"rid": np.arange(6, dtype=np.int64),
            "x": np.arange(6, dtype=np.float32)}
    store.write("mv", base)
    # round 1: update rid 1 (retract + reinsert), delete rid 4, insert rid 10
    d1 = {
        "rid": np.array([1, 4, 1, 10], np.int64),
        "weight": np.array([-1, -1, 1, 1], np.int64),
        "x": np.array([1.0, 4.0, 99.0, 10.0], np.float32),
    }
    store.append("mv", d1)
    assert store.parts("mv") == 2
    out = store.read("mv")
    assert "weight" not in out
    np.testing.assert_array_equal(out["rid"], [0, 1, 2, 3, 5, 10])
    np.testing.assert_array_equal(
        out["x"], np.array([0, 99, 2, 3, 5, 10], np.float32)
    )
    # round 2: delete the round-1 insert again
    store.append("mv", _zset([10], -1, x=np.array([10.0], np.float32)))
    out = store.read("mv")
    np.testing.assert_array_equal(out["rid"], [0, 1, 2, 3, 5])
    # prefix read = pre-round content; suffix read = the raw weighted delta
    np.testing.assert_array_equal(store.read_parts("mv", 0, 1)["x"], base["x"])
    suffix = store.read_parts("mv", 1, 2)
    assert "weight" in suffix and suffix["weight"].tolist() == [-1, -1, 1, 1]


def test_diskstore_consolidate_rewrites_single_live_part(tmp_path):
    store = DiskStore(tmp_path)
    base = {"rid": np.arange(8, dtype=np.int64),
            "x": np.ones(8, np.float32)}
    store.write("mv", base)
    store.append("mv", _zset([0, 1, 2], -1, x=np.ones(3, np.float32)))
    before = store.read("mv")
    bytes_with_tombstones = store.manifest()["mv"]
    dt = store.consolidate("mv")
    assert dt > 0.0
    assert store.parts("mv") == 1
    # manifest shrinks to live bytes; content is unchanged
    assert store.manifest()["mv"] == table_nbytes(before)
    assert store.manifest()["mv"] < bytes_with_tombstones
    after = store.read("mv")
    for k in before:
        np.testing.assert_array_equal(after[k], before[k])
    # idempotent no-op once single-part
    assert store.consolidate("mv") == 0.0


def test_diskstore_read_throttle_charges_tombstone_bytes(tmp_path):
    """Throttle pricing is keyed on the logical bytes read — retraction
    parts included — not on the (smaller) consolidated result: 2 MiB of
    parts at 10 MB/s must take >= ~0.2s even though nearly every row is
    retracted."""
    store = DiskStore(tmp_path, read_bw=10e6)
    n = 1 << 18
    base = {"rid": np.arange(n, dtype=np.int64),
            "x": np.zeros(n, np.float32)}   # ~3 MiB logical
    store.write("mv", base)
    kill = {"rid": base["rid"][:-16], "x": base["x"][:-16],
            "weight": np.full(n - 16, -1, np.int64)}
    store.append("mv", kill)
    store.reset_counters()
    out = store.read("mv")
    assert len(out["rid"]) == 16  # nearly everything retracted
    raw = table_nbytes(base) + table_nbytes(kill)
    assert store.read_seconds >= 0.9 * raw / 10e6


def test_diskstore_tombstone_crash_atomicity_and_stale_tmp_sweep(tmp_path):
    """A consolidation that crashes before the manifest commit leaves the
    tombstone parts authoritative; stale tmp files are ignored by readers
    and swept by delete."""
    store = DiskStore(tmp_path)
    store.write("mv", {"rid": np.arange(4, dtype=np.int64),
                       "x": np.arange(4, dtype=np.float32)})
    store.append("mv", _zset([0], -1, x=np.array([0.0], np.float32)))
    expect = store.read("mv")
    # simulated crash: the consolidated part lands on an unreferenced id and
    # a stale .tmp survives, but the process dies before _record
    new_id = max(store._part_ids("mv")) + 1
    store._write_part("mv", new_id, expect)
    (tmp_path / "mv.part99.col.tmp").write_bytes(b"partial")
    fresh = DiskStore(tmp_path)  # reader after restart
    got = fresh.read("mv")
    for k in expect:
        np.testing.assert_array_equal(got[k], expect[k])
    assert fresh.parts("mv") == 2  # manifest still references base + delta
    # a later real consolidation overwrites the orphan and commits cleanly
    fresh.consolidate("mv")
    assert fresh.parts("mv") == 1
    fresh.delete("mv")
    assert list(tmp_path.glob("mv.*")) == []


def test_diskstore_tombstone_debt_accounting(tmp_path):
    """Appends accumulate a tombstone-debt estimate (tombstone rows plus
    their victims); full rewrites — consolidation included — reset it."""
    store = DiskStore(tmp_path)
    base = {"rid": np.arange(16, dtype=np.int64),
            "x": np.arange(16, dtype=np.float32)}
    store.write("mv", base)
    assert store.tombstone_bytes("mv") == 0
    assert store.live_bytes("mv") == table_nbytes(base)
    # insert-only appends carry no debt
    store.append("mv", {"rid": np.arange(16, 20, dtype=np.int64),
                        "x": np.zeros(4, np.float32)})
    assert store.tombstone_bytes("mv") == 0
    kill = _zset([0, 1, 2, 3], -1, x=np.zeros(4, np.float32))
    store.append("mv", kill)
    debt = store.tombstone_bytes("mv")
    assert debt > table_nbytes(kill)  # tombstones + their victims
    assert store.live_bytes("mv") == store.manifest()["mv"] - debt
    assert store.tombstone_ratio("mv") > 0.0
    store.append("mv", _zset([4, 5], -1, x=np.zeros(2, np.float32)))
    assert store.tombstone_bytes("mv") > debt  # debt accumulates
    store.consolidate("mv")
    assert store.tombstone_bytes("mv") == 0
    assert store.tombstone_ratio("mv") == 0.0
    assert store.live_bytes("mv") == store.manifest()["mv"]


def test_diskstore_delete_removes_parts_and_tmp(tmp_path):
    store = DiskStore(tmp_path)
    t = {"x": np.arange(8)}
    store.write("mv", t)
    store.append("mv", t)
    (tmp_path / "mv.col.tmp").write_bytes(b"partial")  # crashed rewrite
    store.delete("mv")
    assert not store.exists("mv")
    assert list(tmp_path.glob("mv*.col*")) == []
