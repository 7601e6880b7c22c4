"""Plain reference: every MV recomputed in full, in numpy, from the live
base tables, then split into partitions and compared with the store.

It shares no code with the program's ``repro.mv`` (operators, data plane,
engine, catalog, partitioning or store). The operator definitions follow
the configuration file: the DAG, each FILTER's threshold and the PROJECT
width come from there; the rest is the repo's operator semantics, restated:

* SCAN: the live base table in rid order;
* FILTER: rows whose ``c0`` (else the first value column) is above the
  threshold, compared in the column's own float width;
* PROJECT: ``key``, ``rid`` and the first value columns up to
  ``round(keep_frac * columns)`` columns in all;
* MAP (and a JOIN or UNION of one input): adds ``derived = a * 1.0001f +
  b / (1 + |b|)`` over the first two value columns (``a / (1 + |a|)`` with
  one), each operation rounded once;
* JOIN: left-driven inner equi-join on ``key``; the right side contributes
  the first row of each key, its value columns renamed ``r_<name>``;
* UNION: the common columns, concatenated, stably sorted by ``rid``;
* AGG: per key, ``sum_<col>`` of every value column as an exact sum of
  ``rint(v * 2^16)`` in int64 stored as float64 over 2^16, and ``count``.

``precision="bfloat16"`` is the control: the same computation with every
float32 value rounded to bfloat16 on input and after each operation.
"""
from __future__ import annotations

import numpy as np

META = ("key", "rid", "weight")
_C1 = np.uint64(0xBF58476D1CE4E5B9)
_C2 = np.uint64(0x94D049BB133111EB)


def splitmix64(keys: np.ndarray) -> np.ndarray:
    x = np.asarray(keys).astype(np.uint64)
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint64(30))
        x = x * _C1
        x = x ^ (x >> np.uint64(27))
        x = x * _C2
        x = x ^ (x >> np.uint64(31))
    return x


def partitions(table: dict, n: int) -> list[dict]:
    """Rows of each partition ``splitmix64(key) % n``, in table order."""
    pid = splitmix64(table["key"]) % np.uint64(n)
    return [{k: v[pid == p] for k, v in table.items()} for p in range(n)]


def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), as float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + np.uint64(0x7FFF) + ((b >> np.uint64(16)) & np.uint64(1)))
    b = (b & np.uint64(0xFFFF0000)).astype(np.uint32)
    return b.view(np.float32)


class Reference:
    def __init__(self, config: dict, precision: str = "float32"):
        if precision not in ("float32", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.nodes = config["nodes"]
        self.keep_frac = float(config["project_keep_frac"])
        self.scale = np.float32(config["map_scale"])
        self.quantum = float(config["agg_quantum"])
        self.low = precision == "bfloat16"

    def _r(self, x: np.ndarray) -> np.ndarray:
        if self.low and x.dtype == np.float32:
            return _bf16(x)
        return x

    @staticmethod
    def _values(t: dict) -> list[str]:
        return [k for k in t if k not in META]

    def filter(self, t: dict, threshold: float) -> dict:
        cols = self._values(t)
        if not cols:
            return dict(t)
        col = "c0" if "c0" in t else cols[0]
        v = t[col]
        thr = v.dtype.type(threshold) if v.dtype.kind == "f" else \
            np.float64(threshold)
        keep = v > thr
        return {k: a[keep] for k, a in t.items()}

    def project(self, t: dict) -> dict:
        cols = list(t)
        width = max(1, int(round(len(cols) * self.keep_frac)))
        metas = [k for k in cols if k in META]
        vals = [k for k in cols if k not in META]
        kept = set(metas) | set(vals[:max(width - len(metas), 0)])
        return {k: t[k] for k in cols if k in kept}

    def map(self, t: dict) -> dict:
        out = dict(t)
        vals = [t[k] for k in self._values(t)]
        one = np.float32(1.0)
        if len(vals) >= 2:
            a, b = vals[0], vals[1]
            soft = self._r(b / self._r(one + self._r(np.abs(b))))
            out["derived"] = self._r(self._r(a * self.scale) + soft)
        elif vals:
            a = vals[0]
            out["derived"] = self._r(a / self._r(one + self._r(np.abs(a))))
        return out

    def join(self, left: dict, right: dict) -> dict:
        uniq, first = np.unique(right["key"], return_index=True)
        lk = left["key"]
        pos = np.searchsorted(uniq, lk)
        hit = np.zeros(len(lk), bool)
        if len(uniq):
            inside = pos < len(uniq)
            hit[inside] = uniq[pos[inside]] == lk[inside]
        rows = first[pos[hit]]
        out = {k: v[hit] for k, v in left.items()}
        for k, v in right.items():
            if k not in META:
                out[f"r_{k}"] = v[rows]
        return out

    def union(self, a: dict, b: dict) -> dict:
        out = {k: np.concatenate([a[k], b[k]]) for k in a if k in b}
        if "rid" in out:
            order = np.argsort(out["rid"], kind="stable")
            out = {k: v[order] for k, v in out.items()}
        return out

    def agg(self, t: dict) -> dict:
        uniq, inv = np.unique(t["key"], return_inverse=True)
        out = {"key": uniq}
        for k in self._values(t):
            v = self._r(t[k])
            fixed = np.rint(v.astype(np.float64) * self.quantum).astype(
                np.int64)
            acc = np.zeros(len(uniq), np.int64)
            np.add.at(acc, inv, fixed)
            out[f"sum_{k}"] = acc.astype(np.float64) / self.quantum
        out["count"] = np.bincount(inv, minlength=len(uniq)).astype(np.int64)
        return out

    def evaluate(self, bases: dict[int, dict]) -> list[dict]:
        """Every MV's full content, given each SCAN node's live table."""
        out: list[dict] = []
        for i, node in enumerate(self.nodes):
            op, ins = node["op"], [out[p] for p in node["parents"]]
            if op == "SCAN":
                t = {k: self._r(np.asarray(v)) for k, v in bases[i].items()}
            elif op in ("JOIN", "UNION") and len(ins) >= 2:
                t = ins[0]
                for other in ins[1:]:
                    t = self.join(t, other) if op == "JOIN" else \
                        self.union(t, other)
            elif op == "FILTER":
                t = self.filter(ins[0], float(node["threshold"]))
            elif op == "PROJECT":
                t = self.project(ins[0])
            elif op == "AGG":
                t = self.agg(ins[0])
            else:
                t = self.map(ins[0])
            out.append(t)
        return out


def _canonical(t: dict) -> dict:
    """Rows sorted stably by ``rid`` (by ``key`` where there is no rid),
    rows that tie there by their bytes: one order for a bag of rows (to
    tell a stored order apart from stored content in a report)."""
    n = len(next(iter(t.values()))) if t else 0
    if n < 2:
        return t
    cols = sorted(t)
    raw = np.concatenate(
        [np.ascontiguousarray(t[k]).view(np.uint8).reshape(n, -1)
         for k in cols], axis=1)
    rows = np.ascontiguousarray(raw).view(np.dtype((np.void, raw.shape[1])))
    by_bytes = np.argsort(rows.ravel(), kind="stable")
    primary = t["rid"] if "rid" in t else t.get("key")
    order = by_bytes
    if primary is not None:
        order = by_bytes[np.argsort(primary[by_bytes], kind="stable")]
    return {k: v[order] for k, v in t.items()}


def same_table(a: dict, b: dict) -> bool:
    """Same column set, and each column of the same dtype, shape and
    bytes."""
    if set(a) != set(b):
        return False
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        if x.dtype != y.dtype or x.shape != y.shape or \
                x.tobytes() != y.tobytes():
            return False
    return True


def compare(expected: list[dict], read_entry, names: list[str],
            n_partitions: int) -> dict:
    """Every partition of every MV against the reference, bit for bit and
    in the stored row order. Returns ``bad`` (entries that differ),
    ``total``, ``order_only`` (of the bad, those whose rows agree as a bag:
    only their order differs) and the first few bad names.
    ``read_entry(name, p)`` returns the stored partition, or raises
    ``KeyError`` when it is missing."""
    bad: list[str] = []
    order_only = 0
    total = 0
    for name, table in zip(names, expected):
        for p, part in enumerate(partitions(table, n_partitions)):
            total += 1
            try:
                got = read_entry(name, p)
            except (KeyError, FileNotFoundError):
                got = None
            if got is not None and same_table(part, got):
                continue
            bad.append(f"{name}@p{p}")
            if got is not None and same_table(_canonical(part),
                                              _canonical(got)):
                order_only += 1
    return {"bad": len(bad), "total": total, "order_only": order_only,
            "bad_names": bad[:8]}
