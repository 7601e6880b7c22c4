"""What a traced run hands the per-layer metric readers.

Each per-layer metric named in ``BENCHMARK.json`` is read by its own file,
``bench/metrics/<name>.py``, whose ``read(obs: Observed)`` returns the
number, or ``None`` when the run holds nothing for it to read (the harness
then leaves the metric out of the result line).
"""
from __future__ import annotations

import bisect
import dataclasses
import importlib.util
from pathlib import Path

METRICS_DIR = Path(__file__).resolve().parent / "metrics"


@dataclasses.dataclass
class Observed:
    """One traced window. Times are ``time.perf_counter`` seconds."""

    rounds: dict[int, tuple[float, float]]  # round -> (start, end), commits
    # sc-trace spans of the window's rounds: (cat, round, start, dur, worker)
    spans: list[tuple[str, int, float, float, str]]
    counters: dict[str, float]  # METRICS counters summed over the window
    n_workers: int
    window: tuple[float, float]
    # profiler events inside the window, (name, start, dur); None untraced
    device_ops: list[tuple[str, float, float]] | None = None
    device_modules: list[tuple[str, float, float]] | None = None

    @property
    def n_rounds(self) -> int:
        return len(self.rounds)

    def span_seconds(self, cat: str) -> float:
        return sum(d for c, _, _, d, _ in self.spans if c == cat)

    def self_seconds(self, cat: str, child: str) -> float:
        """``cat`` span time less the ``child`` spans that the same worker
        ran inside it (a thread's spans nest, so a child starts inside its
        parent)."""
        starts: dict[str, list[float]] = {}
        ends: dict[str, list[float]] = {}
        for c, _, s, d, w in sorted(self.spans, key=lambda x: x[2]):
            if c == child:
                starts.setdefault(w, []).append(s)
                ends.setdefault(w, []).append(s + d)
        total = 0.0
        for c, _, s, d, w in self.spans:
            if c != cat:
                continue
            st, en = starts.get(w, []), ends.get(w, [])
            i = bisect.bisect_left(st, s)
            j = bisect.bisect_left(st, s + d)
            total += d - sum(min(en[k], s + d) - st[k] for k in range(i, j))
        return total

    def round_spans(self) -> dict[int, tuple[float, float]]:
        """The engine's ``round`` span of each window round."""
        return {r: (s, s + d) for c, r, s, d, _ in self.spans
                if c == "round" and r in self.rounds}


def load_reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
