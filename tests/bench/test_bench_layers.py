"""The per-layer readers and the trace reduction, on made-up spans and
events, and on a CPU profiler trace made here."""
import time

import numpy as np
import pytest

from bench import devtrace
from bench.observe import Observed, load_reader

# two window rounds: 2 runs [10, 14) with its engine span [11, 14), and 3
# runs [14, 20) with its engine span [16, 20); two workers
SPANS = [
    ("round", 2, 11.0, 3.0, "main"), ("round", 3, 16.0, 4.0, "main"),
    ("task", 2, 11.0, 2.0, "w0"), ("task", 2, 11.5, 2.5, "w1"),
    ("task", 3, 16.0, 4.0, "w0"),
    ("compute", 2, 11.0, 2.0, "w0"), ("io.read", 2, 11.5, 0.5, "w0"),
    ("io.read", 2, 12.0, 0.25, "w1"),  # another worker's: not nested
    ("compute", 3, 16.0, 1.0, "w0"),
    ("write.sync", 3, 17.0, 0.75, "w0"),
]


def _obs(**kw):
    args = dict(rounds={2: (10.0, 14.0), 3: (14.0, 20.0)}, spans=SPANS,
                counters={"catalog_hits": 30.0, "catalog_misses": 10.0},
                n_workers=2, window=(10.0, 20.0))
    args.update(kw)
    return Observed(**args)


@pytest.mark.parametrize("name,want", [
    ("plan_s", ((11 - 10) + (16 - 14)) / 2),
    ("worker_busy", 100 * 8.5 / (2 * 7)),
    ("catalog_hit_ratio", 75.0),
    ("read_disk_s", 0.75 / 2),
    ("write_sync_s", 0.75 / 2),
    ("compute_s", (2.0 - 0.5 + 1.0) / 2),
])
def test_span_and_counter_readers(name, want):
    assert load_reader(name)(_obs()) == pytest.approx(want)


def test_device_readers():
    ops = [("fusion.1", 10.0, 1.0), ("fusion.2", 10.5, 1.0),
           ("copy", 15.0, 0.5)]
    modules = [("jit__hash(3)", 10.0, 1.5), ("jit__encode_w(9)", 15.0, 0.5),
               ("jit_other(1)", 18.0, 1.0)]
    obs = _obs(device_ops=ops, device_modules=modules)
    assert load_reader("device_idle")(obs) == pytest.approx(80.0)
    assert load_reader("dp_device_s")(obs) == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["device_idle", "dp_device_s",
                                  "catalog_hit_ratio"])
def test_readers_return_nothing_without_input(name):
    assert load_reader(name)(_obs(counters={})) is None


def test_ops_are_named_after_their_module():
    ops = [("%while.4 = (u32[]) while(...)", 10.0, 0.5),
           ("%fusion.1 = u32[8] fusion(...)", 10.6, 0.1),
           ("%copy = f32[8] copy(...)", 30.0, 0.1)]
    modules = [("jit__probe(7)", 9.9, 0.65), ("jit__pid(3)", 10.55, 0.2)]
    assert [n for n, _, _ in devtrace.name_ops(ops, modules)] == [
        "jit__probe:%while.4", "jit__pid:%fusion.1", "%copy"]


def test_busy_union_and_idle_gaps():
    ev = [("a", 1.0, 2.0), ("b", 2.0, 2.0), ("c", 7.0, 1.0),
          ("d", 9.5, 5.0)]
    assert devtrace.busy_intervals(ev) == [(1.0, 4.0), (7.0, 8.0),
                                           (9.5, 14.5)]
    assert devtrace.idle_gaps(ev, 0.0, 10.0) == [(0.0, 1.0), (4.0, 7.0),
                                                 (8.0, 9.5)]
    clipped = devtrace.clip(ev, 0.0, 10.0)
    assert devtrace.busy_seconds(clipped) == pytest.approx(4.5)
    assert devtrace.top_ops(ev + [("a", 20.0, 1.0)], 2) == [["d", 5.0],
                                                            ["a", 3.0]]
    host = [("compute", 3.5, 6.0), ("stall.read", 5.0, 5.5)]
    plan = [(8.0, 9.5)]
    assert devtrace.longest_gaps(devtrace.idle_gaps(ev, 0.0, 10.0), host,
                                 plan) == [["compute", 3.0], ["plan", 1.5],
                                           ["host", 1.0]]


def test_cpu_profiler_trace_reduction(tmp_path):
    """The xplane reading and the anchor: XLA:CPU's ops run on a host
    thread line, which stands in for a device plane here."""
    import jax
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: (x * 2.0 + 1.0).sum())
    x = np.arange(1 << 16, dtype=np.float32)
    f(x).block_until_ready()
    prof = devtrace.Profiler(tmp_path / "trace")
    prof.start()
    t0 = time.perf_counter()
    for _ in range(20):
        f(x).block_until_ready()
    t1 = time.perf_counter()
    xplane = prof.stop()
    lines = {ln.name for pl in ProfileData.from_file(str(xplane)).planes
             if pl.name.startswith("/host:CPU") for ln in pl.lines}
    xla = [n for n in lines if n.startswith("tf_XLA")]
    assert xla, lines
    ev = [e for name in xla for e in devtrace.device_events(
        xplane, prof.anchor, name, plane_prefix="/host:CPU")]
    fusions = [e for e in ev if "fusion" in e[0] and e[2] > 0]
    assert len(fusions) >= 20
    # on the perf_counter clock, the ops fall inside the calls' span
    assert all(t0 - 1e-3 <= s and s + d <= t1 + 1e-3 for _, s, d in fusions)
    busy = devtrace.busy_seconds(devtrace.clip(fusions, t0, t1))
    gaps = devtrace.idle_gaps(fusions, t0, t1)
    assert busy + sum(b - a for a, b in gaps) == pytest.approx(t1 - t0)
    assert 0 < busy < t1 - t0


def _excerpt():
    import json
    from pathlib import Path

    raw = json.loads((Path(__file__).parent / "data" /
                      "tpu_trace_excerpt.json").read_text())
    return ([(n, s / 1e9, d / 1e9) for n, s, d in raw["ops"]],
            [(n, s / 1e9, d / 1e9) for n, s, d in raw["modules"]])


def test_recorded_tpu_trace_reduction():
    ops, modules = _excerpt()
    named = devtrace.name_ops(ops, modules)
    kinds = {n.split(":")[0] for n, _, _ in named}
    assert kinds == {"jit__pid", "jit__map_mul", "jit__encode", "jit__probe"}
    # the union, against a sweep over integer nanoseconds
    ns = sorted((round(s * 1e9), round((s + d) * 1e9)) for _, s, d in ops)
    union, end = 0, None
    for a, b in ns:
        if end is None or a > end:
            union += b - a
            end = b
        elif b > end:
            union += b - end
            end = b
    assert devtrace.busy_seconds(ops) == pytest.approx(union / 1e9, abs=1e-9)
    lo = min(s for _, s, _ in modules)
    hi = max(s + d for _, s, d in modules)
    gaps = devtrace.idle_gaps(ops, lo, hi)
    assert devtrace.busy_seconds(ops) + sum(b - a for a, b in gaps) == \
        pytest.approx(hi - lo)
    # each module's device time holds its ops
    assert devtrace.busy_seconds(ops) <= sum(d for _, _, d in modules)
    obs = _obs(device_ops=ops, device_modules=modules)
    assert load_reader("dp_device_s")(obs) == pytest.approx(
        sum(d for _, _, d in modules) / 2)
