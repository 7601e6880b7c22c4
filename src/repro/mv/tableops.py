"""Columnar table operators — the SPJ units S/C schedules (paper §VI-A).

A *table* is a dict of equal-length 1-D arrays. Operators mirror the
select-project-join units the paper carves out of TPC-DS queries: SCAN,
FILTER, PROJECT, JOIN (equi), AGG (group-by sum/count). The array-level
inner loops — hash, compare, map expression, fixed-point segment
reduction, join probe — run through ``mv/dataplane.py``, which dispatches
between the numpy reference (default; bitwise contract) and jitted
JAX / Pallas paths (``SC_DATAPLANE`` / ``dataplane.use_impl``, DESIGN.md
§9); data-dependent compaction (filter/join output sizes) and splicing
happen on host, as they would in any vectorized engine.

These run the *real-execution* experiments: the Controller materializes their
outputs through the DiskStore / MemoryCatalog, and results must be bitwise
identical between serial, short-circuit, and incremental-refresh runs.

Incremental refresh (Z-set weighted-row deltas, DESIGN.md §5-6)
---------------------------------------------------------------
Base-table rows carry a ``rid`` column: a globally unique row id that is
monotone in the ingestion round (all rows inserted at round ``r`` sort after
every row from rounds ``< r``); updates keep their rid, so an updated row
stays at its original position in the canonical rid order. A *delta* is a
Z-set: a table with an integer ``weight`` meta column where positive rows
are insertions (``+w`` = w identical copies, for duplicate-row sources) and
negative rows are *retractions* carrying the exact payload of the stored
row(s) they cancel (an UPDATE is a retraction plus an insertion under the
same rid; a DELETE is a bare retraction; ``-w`` retracts w stored copies of
the rid). ``apply_delta``
consolidates a Z-set delta into the stored content: retracted rids are
removed, insertions are spliced in, and the result is kept in the canonical
stable rid order — which is exactly the row order a full recompute
produces, so incremental refresh stays bitwise comparable.

Per-operator delta rules:

* FILTER / PROJECT / MAP are per-row / per-column: the operator applied to
  the weighted delta IS the output delta (weights pass through; a
  retraction survives the filter iff its old payload did).
* JOIN is left-driven (output rows follow left input order; the right side
  is a PK-style first-occurrence index), and weights multiply through the
  PK join. ``zset_join_delta`` joins left retractions against the *old*
  right (exact old payloads) and left insertions against the new right;
  right-side deltas that change the first-occurrence mapping of a key —
  new keys, deleted keys, updated payloads — trigger a *partial fallback*
  that re-joins only the affected surviving old-left rows and splices the
  corrections by rid, instead of recomputing the whole node.
* UNION sorts its output by ``rid`` (when both inputs carry one), stably,
  so the copies of one rid stand in input order. Where no two inputs hold
  a rid, the union of Z-set deltas is the rid-consolidated concatenation
  of the input deltas. Inputs that descend from one scan can hold one rid
  with different payloads; ``zset_union_delta`` then retracts each touched
  shared rid's whole old group and inserts its new group in input order.
* AGG keeps *mergeable partial aggregates*: per-key ``sum_*`` columns are
  accumulated in fixed-point int64 (quantum ``1/AGG_QUANTUM``) so addition
  is exactly associative, and ``count`` is an exact int64. Weighted rows
  contribute ``weight * fixed_point(v)`` — retraction subtracts exactly
  what the original insertion added — hence ``merge_agg(agg(old), agg(Δ±))
  == agg(full)`` bitwise, with groups whose merged count reaches zero
  dropped (a full recompute never sees them). Floating-point segment sums
  do not commute with merging, which is why the sums are quantized.
"""
from __future__ import annotations

import weakref

import numpy as np

from . import dataplane

Table = dict[str, np.ndarray]

# Columns that are bookkeeping, not data: excluded from MAP inputs and AGG
# measures (they still group/join/sort like any other column). ``weight`` is
# the Z-set multiplicity of a delta row: a positive weight inserts that many
# identical copies, a negative weight retracts that many copies of its rid.
WEIGHT_COL = "weight"
META_COLS = ("key", "rid", WEIGHT_COL)

# Fixed-point quantum for AGG sums: values are accumulated as
# round(v * AGG_QUANTUM) in int64, so per-key sums are exactly associative
# (merge order cannot change the result) while keeping ~5 decimal digits.
AGG_QUANTUM = 2.0**16

# rid layout: round dominates (incremental deltas always sort after old
# rows), then the producing scan node, then the row offset within the batch.
_RID_NODE_SLOTS = 1 << 12
_RID_ROW_BITS = 32


def make_rid_base(round_idx: int, node_idx: int) -> int:
    """Start of the rid range for rows ingested by scan ``node_idx`` at
    ``round_idx`` — monotone in round across every table."""
    return (round_idx * _RID_NODE_SLOTS + node_idx) << _RID_ROW_BITS


def make_base_table(
    n_rows: int,
    n_cols: int,
    seed: int,
    key_mod: int | None = None,
    rid_base: int | None = None,
    key_probs: np.ndarray | None = None,
) -> Table:
    """Deterministic synthetic base table: an int64 ``key`` column, ``rid``
    row ids when ``rid_base`` is given, and ``n_cols - 1`` float32 value
    columns. Keys draw uniformly from ``[0, key_mod)`` unless ``key_probs``
    supplies an explicit per-key distribution (len == key range) — the hook
    ``realize_workload`` uses for Zipf-skewed key populations, which hash
    into uneven partition sizes downstream."""
    rng = np.random.default_rng(seed)
    kmod = key_mod or max(n_rows // 4, 4)
    if key_probs is not None:
        keys = rng.choice(len(key_probs), size=n_rows, p=key_probs)
        t: Table = {"key": keys.astype(np.int64)}
    else:
        t = {"key": rng.integers(0, kmod, n_rows).astype(np.int64)}
    if rid_base is not None:
        t["rid"] = rid_base + np.arange(n_rows, dtype=np.int64)
    for c in range(n_cols - 1):
        t[f"c{c}"] = rng.standard_normal(n_rows).astype(np.float32)
    return t


def data_cols(table: Table) -> list[str]:
    return [k for k in table if k not in META_COLS]


# ---------------------------------------------------------------------------
# Z-set (weighted-row) delta primitives
# ---------------------------------------------------------------------------

def n_rows(table: Table) -> int:
    return len(np.asarray(next(iter(table.values())))) if table else 0


# Memoized weight-column live-row sums: the catalog admission path sizes the
# same resident delta repeatedly (feasibility probes, try_put, append), and
# each ``weighted_nbytes`` call re-clipped and re-summed the weight column.
# Keyed by the weight array's id(), which CPython recycles: after the array
# is collected, a *different* array can be allocated at the same address
# before the weakref finalizer has evicted the entry. A hit is therefore
# only trusted when the stored weakref still resolves to the probing array
# AND its recorded shape/dtype match — identity alone is not enough, since
# the dead-ref window is exactly when id() lies. Stale entries found on
# probe are evicted eagerly.
_LIVE_ROWS_CACHE: dict[int, tuple[weakref.ref, tuple, np.dtype, int]] = {}
_LIVE_ROWS_CACHE_MAX = 4096


def _live_rows(table: Table) -> int:
    """Total positive Z-set multiplicity of a delta (cached per weight
    array)."""
    w = table[WEIGHT_COL]
    key = id(w)
    hit = _LIVE_ROWS_CACHE.get(key)
    if hit is not None:
        ref, shape, dtype, cached = hit
        if (
            ref() is w
            and getattr(w, "shape", None) == shape
            and getattr(w, "dtype", None) == dtype
        ):
            return cached
        _LIVE_ROWS_CACHE.pop(key, None)  # id recycled: drop the stale entry
    live = int(np.clip(weights_of(table), 0, None).sum())
    try:
        ref = weakref.ref(
            w, lambda _r, k=key: _LIVE_ROWS_CACHE.pop(k, None)
        )
    except TypeError:  # non-weakref-able column (plain list input)
        return live
    if len(_LIVE_ROWS_CACHE) >= _LIVE_ROWS_CACHE_MAX:
        _LIVE_ROWS_CACHE.clear()
    _LIVE_ROWS_CACHE[key] = (ref, w.shape, w.dtype, live)
    return live


def table_nbytes(table: Table) -> int:
    """Physical bytes of a table's columns (same accounting as
    ``storage.table_nbytes``; here so size probes need not import storage)."""
    return int(sum(np.asarray(v).nbytes for v in table.values()))


def table_sizes(table: Table) -> tuple[int, int]:
    """``(physical bytes, weighted live bytes)`` in one pass — what the
    catalog admission path charges (``max`` of the two for a Z-set delta).
    The weight-column sum is memoized per array, so repeated admission /
    feasibility probes of one published delta cost O(columns), not O(rows).
    The memo assumes the weight column is not mutated in place — true for
    every published part (the engine treats tables as immutable); callers
    that do mutate should use ``weighted_nbytes``, which never caches."""
    n = n_rows(table)
    w_bytes = (
        np.asarray(table[WEIGHT_COL]).nbytes if WEIGHT_COL in table else 0
    )
    phys_all = table_nbytes(table)
    phys = phys_all - w_bytes
    if WEIGHT_COL not in table or n == 0:
        return phys_all, phys
    return phys_all, int(round(phys * (_live_rows(table) / n)))


def weighted_nbytes(table: Table) -> int:
    """Bytes of live content a table expands to when materialized.

    Without a ``weight`` column this is the physical byte count. A Z-set
    delta with general integer weights represents ``w`` identical copies of
    each ``+w`` row (duplicate-row sources), so the content it expands to is
    the per-row payload bytes times the total *positive* multiplicity — the
    size model a Memory Catalog entry must be charged when the resident
    delta can be larger than its physical encoding. Retraction rows carry
    no live content. Always recomputed (mutation-safe); the admission path
    uses the memoized ``table_sizes``."""
    n = n_rows(table)
    phys = int(sum(
        np.asarray(v).nbytes for k, v in table.items() if k != WEIGHT_COL
    ))
    if WEIGHT_COL not in table or n == 0:
        return phys
    live_rows = int(np.clip(weights_of(table), 0, None).sum())
    return int(round(phys * (live_rows / n)))


def weights_of(table: Table) -> np.ndarray:
    """The Z-set weight vector of a delta (implicit all-+1 when absent)."""
    if WEIGHT_COL in table:
        return np.asarray(table[WEIGHT_COL], np.int64)
    return np.ones(n_rows(table), np.int64)


def with_weight(table: Table, weight: int = 1) -> Table:
    """Table with an explicit int64 weight column (existing one is kept only
    when ``weight`` is the default +1; otherwise it is overwritten)."""
    out = dict(table)
    if WEIGHT_COL not in out or weight != 1:
        out[WEIGHT_COL] = np.full(n_rows(table), weight, np.int64)
    return out


def strip_weight(table: Table) -> Table:
    return {k: v for k, v in table.items() if k != WEIGHT_COL}


def take_rows(table: Table, idx: np.ndarray) -> Table:
    return {k: np.asarray(v)[idx] for k, v in table.items()}


def _occurrence_index(values: np.ndarray) -> np.ndarray:
    """occ[i] = number of j < i with values[j] == values[i] (duplicate rank)."""
    order = np.argsort(values, kind="stable")
    srt = values[order]
    n = len(srt)
    if n == 0:
        return np.zeros(0, np.int64)
    run_start = np.zeros(n, np.int64)
    new_run = np.nonzero(np.r_[True, srt[1:] != srt[:-1]])[0]
    run_start[new_run] = new_run
    np.maximum.accumulate(run_start, out=run_start)
    occ = np.empty(n, np.int64)
    occ[order] = np.arange(n) - run_start
    return occ


def apply_delta(old: Table, delta: Table) -> Table:
    """Consolidate a Z-set delta into stored content.

    Rows of ``old`` whose rid carries a retraction are removed, positive
    rows are inserted, and the result is restored to the canonical stable
    rid order — updates land back at their original position, join
    corrections splice mid-stream, and pure appends (delta rids all larger)
    reduce to the plain concatenation of the insert-only model. ``old``
    carries no weight column (it is stored content); the returned table
    doesn't either. Retractions require a rid on both sides to match by.

    Weights are general integers (duplicate-row sources): a ``+w`` row
    inserts ``w`` identical copies; a ``-w`` row retracts ``w`` copies of
    its rid: the first ``w`` occurrences (in rid order) are dropped, clamped
    to the copies actually present. Copies under one rid may differ only in
    a UNION whose inputs share rids, and its delta retracts such a rid's
    whole group (``zset_union_delta``), so which copies go never matters.
    """
    if not delta or n_rows(delta) == 0:
        return dict(old)
    w = weights_of(delta)
    neg = w < 0
    pos_idx = np.nonzero(w > 0)[0]
    if pos_idx.size and (w[pos_idx] != 1).any():
        # general multiplicities: a +w row expands to w identical copies
        pos_idx = np.repeat(pos_idx, w[pos_idx])
    missing = [k for k in old if k not in delta]
    if missing:
        raise ValueError(f"delta lacks columns {missing} of the target table")
    if "rid" not in old:
        if neg.any():
            raise ValueError("retraction delta needs a rid column to match by")
        return {
            k: np.concatenate([np.asarray(old[k]), np.asarray(delta[k])[pos_idx]])
            for k in old
        }
    retracted = np.asarray(delta["rid"])[neg]
    old_rid = np.asarray(old["rid"])
    ins_rid = np.asarray(delta["rid"])[pos_idx]
    if not retracted.size and (
        not len(old_rid) or not ins_rid.size or ins_rid.min() > old_rid[-1]
    ):
        # pure append (round-monotone insert rids): the stable rid sort is a
        # no-op, skip it — this is the hot path of insert-only refresh
        return {
            k: np.concatenate([np.asarray(old[k]), np.asarray(delta[k])[pos_idx]])
            for k in old
        }
    if retracted.size:
        # per-rid retraction multiplicity (Σ -w over that rid's tombstones)
        uniq_r, inv_r = np.unique(retracted, return_inverse=True)
        counts = np.zeros(len(uniq_r), np.int64)
        np.add.at(counts, inv_r, -w[neg])
        pos_r = np.searchsorted(uniq_r, old_rid)
        pos_r = np.clip(pos_r, 0, max(len(uniq_r) - 1, 0))
        hit = uniq_r[pos_r] == old_rid if len(uniq_r) else np.zeros(
            len(old_rid), bool
        )
        if (counts == 1).all() and len(np.unique(old_rid)) == len(old_rid):
            keep = np.nonzero(~hit)[0]  # the unique-rid, weight-±1 hot path
        else:
            occ = _occurrence_index(old_rid)
            drop = hit & (occ < counts[pos_r])
            keep = np.nonzero(~drop)[0]
    else:
        keep = np.arange(len(old_rid))
    merged = {
        k: np.concatenate([np.asarray(old[k])[keep], np.asarray(delta[k])[pos_idx]])
        for k in old
    }
    order = np.argsort(merged["rid"], kind="stable")
    return {k: v[order] for k, v in merged.items()}


def materialize_delta(delta: Table) -> Table:
    """Live content of a Z-set delta standing alone (an MV whose first-ever
    part is a delta): applied onto an empty base, weight column stripped."""
    base = {k: np.asarray(v)[:0] for k, v in delta.items() if k != WEIGHT_COL}
    return apply_delta(base, delta)


def _row_bytes_equal(a: Table, ai: np.ndarray, b: Table, bi: np.ndarray,
                     cols: list[str]) -> np.ndarray:
    """Per-row bitwise equality of ``a[ai]`` vs ``b[bi]`` over ``cols``
    (value equality is not enough: -0.0 vs 0.0 must count as a change)."""
    eq = np.ones(len(ai), bool)
    for c in cols:
        va = np.ascontiguousarray(np.asarray(a[c])[ai])
        vb = np.ascontiguousarray(np.asarray(b[c])[bi])
        ba = va.view(np.uint8).reshape(len(ai), -1)
        bb = vb.view(np.uint8).reshape(len(bi), -1)
        eq &= (ba == bb).all(axis=1)
    return eq


def consolidate_zset(delta: Table) -> Table:
    """Net opposite-sign pairs in a Z-set delta: a retraction and an
    insertion under the same (unique-per-sign) rid with bitwise-identical
    payloads partially cancel — their weights sum, the fully-cancelled pair
    (net 0) drops out entirely, and a surviving net multiplicity stays on
    the row whose sign it matches (general integer weights: ``-2`` vs
    ``+3`` nets to a single ``+1`` insertion). Leaves everything else
    (order included) untouched."""
    if WEIGHT_COL not in delta or "rid" not in delta or n_rows(delta) == 0:
        return delta
    w = weights_of(delta)
    rid = np.asarray(delta["rid"])
    neg_idx, pos_idx = np.nonzero(w < 0)[0], np.nonzero(w > 0)[0]
    if not neg_idx.size or not pos_idx.size:
        return delta
    # only rids unique within each sign are safely cancellable
    def _unique_only(idx):
        r = rid[idx]
        uniq, counts = np.unique(r, return_counts=True)
        return idx[np.isin(r, uniq[counts == 1])]

    neg_u, pos_u = _unique_only(neg_idx), _unique_only(pos_idx)
    common, ni, pi = np.intersect1d(
        rid[neg_u], rid[pos_u], assume_unique=True, return_indices=True
    )
    if not common.size:
        return delta
    cols = [k for k in delta if k not in (WEIGHT_COL, "rid")]
    same = _row_bytes_equal(delta, neg_u[ni], delta, pos_u[pi], cols)
    if not same.any():
        return delta
    neg_s, pos_s = neg_u[ni][same], pos_u[pi][same]
    net = w[neg_s] + w[pos_s]
    new_w = w.copy()
    drop = [neg_s[net == 0], pos_s[net == 0]]
    pos_net = net > 0
    if pos_net.any():
        new_w[pos_s[pos_net]] = net[pos_net]
        drop.append(neg_s[pos_net])
    neg_net = net < 0
    if neg_net.any():
        new_w[neg_s[neg_net]] = net[neg_net]
        drop.append(pos_s[neg_net])
    keep = np.setdiff1d(np.arange(len(rid)), np.concatenate(drop))
    out = dict(delta)
    out[WEIGHT_COL] = new_w
    return take_rows(out, keep)


def op_filter(table: Table, col: str = "c0", threshold: float = 0.0) -> Table:
    if col not in table:
        col = next(iter(data_cols(table)), None)
        if col is None:  # meta-only table (e.g. a key-only aggregate upstream)
            return dict(table)
    mask = dataplane.filter_mask(np.asarray(table[col]), threshold)
    idx = np.nonzero(mask)[0]
    return {k: np.asarray(v)[idx] for k, v in table.items()}


def op_project(table: Table, keep_frac: float = 0.5) -> Table:
    # the weight column is delta bookkeeping: it always survives and never
    # counts toward the projection width, so a weighted delta keeps exactly
    # the columns the full-table projection keeps
    cols = [k for k in table if k != WEIGHT_COL]
    keep = max(1, int(round(len(cols) * keep_frac)))
    # meta columns always survive projection (key for joins/aggs, rid for the
    # incremental-union ordering); data columns fill the remaining width
    metas = [k for k in cols if k in META_COLS]
    data = [k for k in cols if k not in META_COLS]
    width = max(keep - len(metas), 0)
    kept = set(metas) | set(data[:width]) | {WEIGHT_COL}
    return {k: table[k] for k in table if k in kept}


def _softsign(x: np.ndarray) -> np.ndarray:
    return x / (np.float32(1.0) + np.abs(x))


def op_map(table: Table) -> Table:
    """Element-wise derived column (models expression evaluation).

    The expression must be bitwise independent of the batch shape (delta
    refresh evaluates it over chunks that a full recompute evaluates whole),
    so every impl evaluates it *unfused*: mul/add/div/abs are correctly
    rounded by IEEE-754, and ``dataplane.map_derived`` keeps the jitted
    paths in two separate kernels so XLA cannot contract the mul+add into
    an FMA (which would change the low bit vs the numpy reference).
    """
    out = dict(table)
    vals = [np.asarray(table[k]) for k in data_cols(table)]
    if len(vals) >= 2:
        out["derived"] = dataplane.map_derived(vals[0], vals[1])
    elif vals:
        out["derived"] = dataplane.map_derived(vals[0], None)
    return out


def _first_occurrence_index(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(sorted unique keys, row index of each key's first occurrence) — the
    PK-style probe index every right join side is reduced to."""
    return dataplane.first_occurrence(keys)


def op_join(left: Table, right: Table) -> Table:
    """Inner equi-join on 'key' (sort-merge, host index building + gather).

    Left-driven: output rows follow left input order, and the right side
    contributes its *first occurrence* per key (PK-style join). Stability of
    the first occurrence under right-side appends is what makes the
    incremental delta rule exact (module docstring). The right side's own
    meta columns are dropped — the output's rid (and Z-set weight, when the
    left is a weighted delta) are the left's.
    """
    lk, rk = np.asarray(left["key"]), np.asarray(right["key"])
    uniq, ridx_for = _first_occurrence_index(rk)
    matched, pos = dataplane.probe_sorted(uniq, lk)
    li = np.nonzero(matched)[0]
    ri = ridx_for[pos[matched]] if len(uniq) else np.array([], np.int64)
    out: Table = {}
    for k, v in left.items():
        out[k] = np.asarray(v)[li]
    for k, v in right.items():
        if k in META_COLS:
            continue
        out[f"r_{k}"] = np.asarray(v)[ri]
    return out


def join_delta_is_appendable(right_old_keys: np.ndarray, right_delta: Table) -> bool:
    """True iff appending ``right_delta`` cannot change existing join matches
    (insert-only, and no key in the delta is new) — equivalently, iff
    ``zset_join_delta`` will emit no corrections for it. The engine no
    longer gates on this predicate (the partial fallback handles every
    case); it remains the algebraic statement of the append-only rule."""
    dk = np.asarray(right_delta["key"])
    if dk.size == 0:
        return True
    if (weights_of(right_delta) < 0).any():
        return False
    return bool(np.isin(dk, np.asarray(right_old_keys)).all())


def _right_mapping_changes(
    right_old: Table, right_new: Table, candidates: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Candidate join keys whose PK first-occurrence mapping changed between
    the old and new right side: (keys needing retraction of old matches,
    keys needing insertion of new matches). A key appears in both when its
    match payload changed (UPDATE), in one when it appeared or vanished."""
    uo, io = _first_occurrence_index(np.asarray(right_old["key"]))
    un, inw = _first_occurrence_index(np.asarray(right_new["key"]))

    old_has, opos = dataplane.probe_sorted(uo, candidates)
    new_has, npos = dataplane.probe_sorted(un, candidates)
    both = old_has & new_has
    changed = np.zeros(len(candidates), bool)
    if both.any():
        cols = [k for k in right_old if k not in META_COLS]
        changed[both] = ~_row_bytes_equal(
            right_old, io[opos[both]], right_new, inw[npos[both]], cols
        )
    retract = candidates[(old_has & ~new_has) | changed]
    insert = candidates[(new_has & ~old_has) | changed]
    return retract, insert


def zset_join_delta(
    left_old, left_delta: Table, right_old: Table, right_delta: Table,
    stats: dict | None = None,
) -> tuple[Table, int]:
    """Weighted delta of ``op_join(left, right)`` given Z-set deltas of both
    sides; returns ``(delta, corrected_rows)``.

    When ``stats`` (a dict) is passed, it is filled with the observed
    partial-fallback profile of this call: ``affected_keys`` (candidate keys
    whose PK first-occurrence mapping changed), ``matched_keys`` (affected
    keys that actually matched surviving old-left rows — the corrections
    that cost real work), and ``corrected_rows``. The ratio
    ``matched_keys / affected_keys`` is the fallback rate the planner's
    correction-cost term can be calibrated with.

    Left retractions join the *old* right side (reproducing the exact old
    output payloads), left insertions join the new right side, and weights
    pass through the PK join. When the right delta changes a key's
    first-occurrence mapping — a new key matching old left rows, a deleted
    key unmatching them, or an updated match payload — the *partial
    fallback* re-joins only the affected old-left rows that survive this
    round's left retractions, emitting retract/insert corrections that
    ``apply_delta`` splices back by rid. ``corrected_rows`` counts those
    correction rows (0 = the pure delta rule sufficed).

    ``left_old`` may be a Table or a zero-arg callable returning one: the
    old left side is only needed (and a callable only invoked) when the
    right mapping actually changed — the pure delta rule never pays the
    historical left read.
    """
    lo_memo: list = [left_old if not callable(left_old) else None]

    def _left_old() -> Table:
        if lo_memo[0] is None:
            lo_memo[0] = left_old()
        return lo_memo[0]

    right_new = apply_delta(right_old, right_delta)
    w = weights_of(left_delta)
    parts: list[Table] = []
    neg_idx, pos_idx = np.nonzero(w < 0)[0], np.nonzero(w > 0)[0]
    if neg_idx.size:
        parts.append(op_join(take_rows(with_weight(left_delta), neg_idx), right_old))
    if pos_idx.size:
        parts.append(op_join(take_rows(with_weight(left_delta), pos_idx), right_new))
    corrected = 0
    affected = matched = 0
    cand = np.unique(np.asarray(right_delta["key"])) if (
        right_delta and n_rows(right_delta)
    ) else np.empty(0, np.int64)
    if cand.size:
        retract_keys, insert_keys = _right_mapping_changes(
            right_old, right_new, cand
        )
        affected = int(np.union1d(retract_keys, insert_keys).size)
        if retract_keys.size or insert_keys.size:
            # old-left rows still standing after this round's left retractions
            lo = _left_old()
            l_rid = np.asarray(lo["rid"])
            l_retracted = np.asarray(left_delta["rid"])[w < 0] if neg_idx.size \
                else np.empty(0, l_rid.dtype)
            rem = ~np.isin(l_rid, l_retracted) if l_retracted.size else \
                np.ones(len(l_rid), bool)
            l_keys = np.asarray(lo["key"])
            matched_keys: set[int] = set()
            if retract_keys.size:
                sub = np.nonzero(rem & np.isin(l_keys, retract_keys))[0]
                if sub.size:
                    matched_keys.update(np.unique(l_keys[sub]).tolist())
                    corr = op_join(
                        with_weight(take_rows(lo, sub), -1), right_old
                    )
                    corrected += n_rows(corr)
                    parts.append(corr)
            if insert_keys.size:
                sub = np.nonzero(rem & np.isin(l_keys, insert_keys))[0]
                if sub.size:
                    matched_keys.update(np.unique(l_keys[sub]).tolist())
                    corr = op_join(
                        with_weight(take_rows(lo, sub), +1), right_new
                    )
                    corrected += n_rows(corr)
                    parts.append(corr)
            matched = len(matched_keys)
    if stats is not None:
        stats["affected_keys"] = affected
        stats["matched_keys"] = matched
        stats["corrected_rows"] = corrected
    if not parts:
        # schema-only result: an empty slice of the left delta (same columns
        # as the left side) joined against the right — no left read needed
        empty_left = take_rows(with_weight(left_delta), np.empty(0, np.int64))
        return op_join(empty_left, right_old), 0
    out = concat_tables(parts)
    if "rid" in out:
        order = np.argsort(np.asarray(out["rid"]), kind="stable")
        out = {k: np.asarray(v)[order] for k, v in out.items()}
    return out, corrected


def _fixed_point(v: np.ndarray) -> np.ndarray:
    return np.rint(np.asarray(v, np.float64) * AGG_QUANTUM).astype(np.int64)


def op_agg(table: Table) -> Table:
    """Group-by key; fixed-point-exact sums + int64 count per group.

    Sums accumulate as int64 fixed-point (see ``AGG_QUANTUM``) and are stored
    back as float64 — a deterministic function of the exact integer sum, so
    aggregation is associative and ``merge_agg`` is bitwise-exact. ``count``
    is int64 (an int32 accumulator overflows past 2^31 rows).

    On a Z-set delta (a ``weight`` column present) every row contributes
    ``weight * fixed_point(v)`` to its group's sums and ``weight`` to its
    count: a retraction subtracts exactly the integer its insertion added,
    so the result is the signed partial aggregate ``merge_agg`` needs.
    Groups whose delta-local count nets to zero are kept — they may still
    carry sum corrections (an update that moved a value but not its key).

    ``stable=False`` is a declared contract, not an omission: every
    accumulation here is an exact int64 sum (mod 2^64 addition commutes), so
    the jitted path's grouping sort may legally be unstable — the perf path
    sc-lint baselines as the one sanctioned ``unstable-sort`` finding. Any
    future order-sensitive accumulation (floats, first/last, arg-extrema)
    must flip it to ``stable=True``.
    """
    keys = np.asarray(table["key"])
    w = weights_of(table) if WEIGHT_COL in table else None
    cols = {
        f"sum_{k}": (np.asarray(table[k]), "fixed")
        for k in data_cols(table)
        if np.issubdtype(np.asarray(table[k]).dtype, np.number)
    }
    uniq, sums, counts = dataplane.group_reduce(
        keys, cols, weights=w, stable=False
    )
    out: Table = {"key": uniq}
    for name, acc in sums.items():
        out[name] = acc.astype(np.float64) / AGG_QUANTUM
    out["count"] = counts
    return out


def merge_agg(old: Table, delta: Table) -> Table:
    """Merge two partial aggregates: ``merge_agg(agg(a), agg(b)) == agg(a++b)``
    bitwise (sums re-enter fixed-point, so addition is exact; counts are
    int64). ``delta`` may be a *signed* partial aggregate (``op_agg`` of a
    Z-set delta): groups whose merged count reaches zero have no surviving
    rows and are dropped, exactly as a full recompute would never emit
    them. Key order of the result is sorted-unique, matching ``op_agg``."""
    ok, dk = np.asarray(old["key"]), np.asarray(delta["key"])
    keys = np.concatenate([ok, dk])
    # one segment reduction over the concatenated partials: sums re-enter
    # fixed-point (kind "fixed"), counts add raw (kind "int"); per-key
    # integer addition is exact, so this is bitwise the old scatter-merge
    cols: dict[str, tuple[np.ndarray, str]] = {}
    for col in old:
        if col == "key":
            continue
        ov = np.asarray(old[col])
        dv = (
            np.asarray(delta[col])
            if col in delta
            else np.zeros(len(dk), ov.dtype)
        )
        cols[col] = (np.concatenate([ov, dv]),
                     "int" if col == "count" else "fixed")
    # stable=False: per-key integer addition is exact, order-insensitive
    # (the same declared contract as op_agg)
    uniq, sums, _counts = dataplane.group_reduce(
        keys, cols, weights=None, stable=False
    )
    out: Table = {"key": uniq}
    for col, acc in sums.items():
        if col == "count":
            out[col] = acc
        else:
            out[col] = acc.astype(np.float64) / AGG_QUANTUM
    live = out["count"] != 0
    if not live.all():
        out = {k: np.asarray(v)[live] for k, v in out.items()}
    return out


def op_union(left: Table, right: Table) -> Table:
    """Union of the common columns. When both sides carry a ``rid``, rows are
    ordered by it — the canonical order that makes incremental refresh a
    rid-spliced delta (pure inserts land after all old rids, so the
    insert-only case stays append-only). Weighted delta inputs consolidate:
    exact no-op retract/insert pairs cancel by rid."""
    common = [k for k in left if k in right]
    out = {k: np.concatenate([np.asarray(left[k]), np.asarray(right[k])]) for k in common}
    if "rid" in out:
        order = np.argsort(out["rid"], kind="stable")
        out = {k: v[order] for k, v in out.items()}
    if WEIGHT_COL in out:
        out = consolidate_zset(out)
    return out


def _rows_in(table: Table, rids: np.ndarray, inside: bool = True) -> Table:
    """Rows of ``table`` whose rid is (``inside``) or is not in ``rids``."""
    hit = np.isin(np.asarray(table["rid"]), rids)
    return take_rows(table, np.nonzero(hit if inside else ~hit)[0])


def _rid_counts(tables: list[Table], rids: np.ndarray) -> np.ndarray:
    """Copies of each of the sorted unique ``rids`` held across ``tables``
    (every row's rid is one of them)."""
    counts = np.zeros(len(rids), np.int64)
    for t in tables:
        r = np.asarray(t["rid"])
        if r.size:
            counts += np.bincount(np.searchsorted(rids, r),
                                  minlength=len(rids))
    return counts


def zset_union_delta(
    olds: list[Table | None], deltas: list[Table], union,
    stats: dict | None = None,
) -> Table:
    """Weighted delta of a UNION whose inputs may hold rows under one rid.

    ``olds[i]`` is input ``i``'s old content, or ``None`` for an input that
    shares no rid with another (its rows always pass through); ``deltas[i]``
    its Z-set delta; ``union(tables)`` the node's operator (``op_union``
    folded over the inputs). Copies of one rid can differ by input, so
    ``apply_delta``'s "retract the first w copies" would drop the wrong
    one, and a copy re-inserted from an earlier input would land after a
    later input's. So a touched rid that the sharing inputs held and hold
    two or more copies of (old count >= 1, old or new count >= 2) is
    *regrouped*: the delta retracts each old copy with its exact payload
    and inserts the new copies in input order — ``apply_delta`` then drops
    the whole old group and its stable rid sort puts the new group where
    ``union`` of the new inputs puts it. Every other rid passes through
    as ``union(deltas)``, which is the whole result when nothing needs a
    regroup. ``stats`` gets ``regroup_rows`` (retracted + inserted rows)
    and ``regroup_bytes`` (their payload bytes)."""
    share = [i for i, o in enumerate(olds) if o is not None]
    rids = [np.asarray(deltas[i]["rid"]) for i in share]
    touched = np.unique(np.concatenate(rids)) if rids else \
        np.empty(0, np.int64)
    regroup = touched[:0]
    if touched.size:
        old_t = {i: _rows_in(olds[i], touched) for i in share}
        new_t = {i: apply_delta(old_t[i], deltas[i]) for i in share}
        n_old = _rid_counts(list(old_t.values()), touched)
        n_new = _rid_counts(list(new_t.values()), touched)
        regroup = touched[(n_old >= 1) & (np.maximum(n_old, n_new) >= 2)]
    if stats is not None:
        stats.update(regroup_rows=0, regroup_bytes=0)
    if not regroup.size:
        return union(deltas)
    ws = [with_weight(d) for d in deltas]
    passed = union([_rows_in(w, regroup, inside=False) if i in old_t else w
                    for i, w in enumerate(ws)])

    def group(tables: dict[int, Table], weight: int) -> Table:
        return union([
            with_weight(_rows_in(tables[i], regroup), weight) if i in tables
            else take_rows(w, np.empty(0, np.int64))
            for i, w in enumerate(ws)
        ])

    retract, insert = group(old_t, -1), group(new_t, +1)
    if stats is not None:
        stats.update(
            regroup_rows=n_rows(retract) + n_rows(insert),
            regroup_bytes=table_nbytes(strip_weight(retract))
            + table_nbytes(strip_weight(insert)),
        )
    out = {k: np.concatenate([np.asarray(t[k])
                              for t in (passed, retract, insert)])
           for k in passed}
    order = np.argsort(out["rid"], kind="stable")
    return {k: v[order] for k, v in out.items()}


def empty_like(schema: dict[str, np.dtype]) -> Table:
    """A zero-row table with the given column schema (an empty delta)."""
    return {k: np.empty(0, dtype=dt) for k, dt in schema.items()}


def table_schema(table: Table) -> dict[str, np.dtype]:
    return {k: np.asarray(v).dtype for k, v in table.items()}


def assert_tables_bitwise(a: Table, b: Table, context: str = "") -> None:
    """Raise AssertionError (naming the first divergent column) unless two
    tables are bitwise identical: same column set, dtypes, shapes, bytes.
    The shared check behind every refresh-equivalence claim."""
    if set(a) != set(b):
        raise AssertionError(
            f"{context}: column sets differ {sorted(a)} != {sorted(b)}"
        )
    for col in a:
        va, vb = np.asarray(a[col]), np.asarray(b[col])
        if va.dtype != vb.dtype or va.shape != vb.shape or (
            va.tobytes() != vb.tobytes()
        ):
            raise AssertionError(
                f"{context}.{col}: not bitwise identical "
                f"({va.dtype}{va.shape} vs {vb.dtype}{vb.shape})"
            )


def concat_tables(parts: list[Table]) -> Table:
    """Column-wise concatenation of same-schema tables (store parts).

    When any part carries Z-set weights, every part is normalized to an
    explicit weight column and the result is consolidated by rid (exact
    no-op retract/insert pairs cancel) — concatenating weighted deltas
    yields one canonical weighted delta."""
    if not parts:
        raise ValueError("concat_tables needs at least one part")
    if len(parts) == 1:
        return dict(parts[0])
    weighted = any(WEIGHT_COL in p for p in parts)
    if weighted:
        parts = [with_weight(p) for p in parts]
    out = {
        k: np.concatenate([np.asarray(p[k]) for p in parts]) for k in parts[0]
    }
    return consolidate_zset(out) if weighted else out
