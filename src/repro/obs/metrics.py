"""Counter registry for the refresh engine (§12).

A minimal, thread-safe metrics surface the engine and store record into
when observability is on (the same ``SC_TRACE`` / ``obs.trace.enable``
switch gates both spans and metrics, so the disabled hot path pays one
predicate). Counters are cumulative across rounds until ``clear()``. What
a span already records (round walls, throttle stalls, catalog occupancy)
is read from the spans, not kept here twice.

Naming: a counter has a ``name`` and an optional ``entry`` label (the store
entry / MV name), so per-entry families — catalog hits/misses, bytes
read/written — aggregate naturally: the exported snapshot nests
``{"counters": {name: {entry: value}}}`` with the unlabeled series under
``""``.

Standard series recorded by the instrumented stack:

=====================================  =====================================
``bytes_read`` / ``bytes_written``     DiskStore logical I/O per entry
``catalog_hits`` / ``catalog_misses``  engine gather outcomes per entry
``join_fallbacks``                     JOIN partial-fallback rounds
``union_regroup_rows``                 rows a UNION's shared-rid regroup
                                       retracted and inserted, per entry
=====================================  =====================================
"""
from __future__ import annotations

import json
import threading
from pathlib import Path
from typing import Any

__all__ = ["MetricsRegistry", "METRICS"]


class MetricsRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, dict[str, float]] = {}

    # -- recording -----------------------------------------------------------
    def inc(self, name: str, value: float = 1.0, entry: str = "") -> None:
        with self._lock:
            fam = self._counters.setdefault(name, {})
            fam[entry] = fam.get(entry, 0.0) + value

    # -- reading -------------------------------------------------------------
    def counter_value(self, name: str, entry: str = "") -> float:
        with self._lock:
            return self._counters.get(name, {}).get(entry, 0.0)

    def counter_family(self, name: str) -> dict[str, float]:
        with self._lock:
            return dict(self._counters.get(name, {}))

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {
                "counters": {k: dict(v) for k, v in self._counters.items()},
            }

    def export_json(self, path: str | Path) -> Path:
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(self.snapshot(), indent=1, sort_keys=True))
        return p

    def clear(self) -> None:
        with self._lock:
            self._counters.clear()


#: Process-wide registry the instrumented stack records into.
METRICS = MetricsRegistry()
