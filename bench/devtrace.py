"""Device trace: the JAX profiler around the window, and its reduction.

The profiler writes an ``.xplane.pb``; ``device_events`` reads the device
planes' op events out of it and puts them on the host's
``time.perf_counter`` clock through one ``TraceAnnotation`` anchor taken
at a recorded ``perf_counter``. The reductions below (busy union, idle
gaps and their labels, the top ops) are plain arithmetic on
``(name, start_s, dur_s)`` tuples, so a test can check them on a recorded
trace.
"""
from __future__ import annotations

import bisect
import glob
import shutil
import time
from pathlib import Path

ANCHOR = "bench.clock_anchor"
# the device plane's line of individual ops (a TPU's "XLA Ops"); modules
# are read from "XLA Modules"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Profiler:
    """``jax.profiler`` trace into ``log_dir`` with the Python tracer off
    (it would slow every call of this Python-heavy host path)."""

    def __init__(self, log_dir: Path):
        self.log_dir = Path(log_dir)
        self.anchor: float | None = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self.anchor = time.perf_counter()
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass

    def stop(self) -> Path:
        import jax

        jax.profiler.stop_trace()
        found = sorted(glob.glob(str(self.log_dir / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no xplane.pb under {self.log_dir}")
        return Path(found[-1])


def device_events(xplane: Path, anchor: float, line: str = OPS_LINE,
                  plane_prefix: str = "/device:"
                  ) -> list[tuple[str, float, float]]:
    """``(name, start, dur)`` of every event on ``line`` of each plane
    named ``plane_prefix...`` (the devices), in seconds on the
    ``perf_counter`` clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    anchor_ns = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name == ANCHOR:
                    anchor_ns = ev.start_ns
                    break
    if anchor_ns is None:
        raise ValueError(f"{xplane}: no {ANCHOR} event on a host plane")
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for ln in plane.lines:
            if ln.name != line:
                continue
            for ev in ln.events:
                out.append((ev.name, anchor + (ev.start_ns - anchor_ns) / 1e9,
                            ev.duration_ns / 1e9))
    return out


def device_lines(xplane: Path) -> dict[str, int]:
    """``{"<plane> / <line>": events}`` of the device planes (a report of
    what the trace holds)."""
    from jax.profiler import ProfileData

    return {f"{pl.name} / {ln.name}": sum(1 for _ in ln.events)
            for pl in ProfileData.from_file(str(xplane)).planes
            if pl.name.startswith("/device:") for ln in pl.lines}


def clip(events, lo: float, hi: float) -> list[tuple[str, float, float]]:
    """Events cut to ``[lo, hi]``; events wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_intervals(events) -> list[tuple[float, float]]:
    """Union of the events' ``[start, end)`` intervals, sorted."""
    ivs = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: list[list[float]] = []
    for a, b in ivs:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(events) -> float:
    return sum(b - a for a, b in busy_intervals(events))


def idle_gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` in which no event runs."""
    gaps, t = [], lo
    for a, b in busy_intervals(clip(events, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def module_name(event_name: str) -> str:
    """``jit__hash(12)`` -> ``jit__hash``."""
    return event_name.split("(", 1)[0]


def name_ops(ops, modules) -> list[tuple[str, float, float]]:
    """Ops renamed ``<module>:<instruction>`` (``jit__probe:%while.4``)
    after the module event they start in; an XLA op's own name is its whole
    HLO text."""
    mods = sorted((s, s + d, module_name(n)) for n, s, d in modules)
    starts = [m[0] for m in mods]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        inst = name.split(" ", 1)[0]
        if i >= 0 and s < mods[i][1]:
            inst = f"{mods[i][2]}:{inst}"
        out.append((inst, s, d))
    return out


def top_ops(events, n: int = 10) -> list[list]:
    """``[[name, seconds], ...]``: the ops with the most device time."""
    total: dict[str, float] = {}
    for name, _, d in events:
        total[name] = total.get(name, 0.0) + d
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:n]]


def gap_label(mid: float, host_spans, plan_stretches) -> str:
    """What the host did at ``mid``: the innermost (shortest) open span of
    ``host_spans`` (``(cat, start, end)``), ``plan`` inside one of
    ``plan_stretches`` (from a round's last commit to the next round's
    ``round`` span), else ``host``."""
    best = None
    for cat, a, b in host_spans:
        if a <= mid < b and (best is None or b - a < best[2] - best[1]):
            best = (cat, a, b)
    if best is not None:
        return best[0]
    for a, b in plan_stretches:
        if a <= mid < b:
            return "plan"
    return "host"


def longest_gaps(gaps, host_spans, plan_stretches, n: int = 10) -> list:
    """``[[label, seconds], ...]`` of the ``n`` longest gaps."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    return [[gap_label((a + b) / 2, host_spans, plan_stretches), b - a]
            for a, b in longest]
