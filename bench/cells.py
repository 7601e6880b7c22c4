"""Cells by name: ``BENCHMARK.json``'s entry, and the configuration,
traffic and store-tier files it leads to. Adding a cell, a configuration,
a traffic mix or a store tier takes new files and entries only."""
from __future__ import annotations

import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(ROOT / "BENCHMARK.json")


def load_cell(name: str) -> dict:
    """``{"workload", "config", "traffic", "store", "end_to_end",
    "per_layer"}`` of cell ``name``: the per-layer entries are those that
    list the cell (or list no cells)."""
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load(ROOT / configs[cell["config"]]["file"])
    return {
        "workload": cell,
        "config": config,
        "traffic": _load(BENCH_DIR / "traffic" / f"{cell['traffic']}.json"),
        "store": _load(BENCH_DIR / "stores" / f"{config['store']}.json"),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }
