"""The result line keeps to the benchmark's contract, and ``bench/run.py``
prints no result where it cannot measure."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import cells, run

ROOT = cells.ROOT
TINY = {"bytes_per_root": 1 << 16, "n_partitions": 2}
QUIET = {"log": lambda *_: None, "on_chip": False}
DEVICE = {"platform", "kind", "count", "memory_peak_bytes"}


def _check_common(res, cell):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert set(res) <= {"correct", "attempted", "failed", "metrics",
                        "device", "breakdown", "checks"}
    assert res["correct"] is True and res["failed"] == 0
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
    json.loads(json.dumps(res))  # plain JSON: no NaN, no numpy types


def test_untraced_result_holds_the_end_to_end_metrics(tmp_path):
    cell = "io3.nfs.rf"
    res = run.run_cell(cell, 7, 1.0, False, overrides=TINY,
                       work_dir=tmp_path, **QUIET)
    _check_common(res, cell)
    assert set(res["device"]) == DEVICE
    want = {m["name"]: m["unit"] for m in cells.load_cell(cell)["end_to_end"]}
    assert {k: v["unit"] for k, v in res["metrics"].items()} == want
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_traced_result_holds_the_per_layer_metrics(tmp_path):
    cell = "compute2.local.rf"
    res = run.run_cell(cell, 8, 1.0, True, overrides=TINY,
                       work_dir=tmp_path, **QUIET)
    _check_common(res, cell)
    assert set(res["device"]) == DEVICE | {"busy_s", "window_s"}
    per_layer = {m["name"]: m["unit"]
                 for m in cells.load_cell(cell)["per_layer"]}
    # the CPU has no device plane: the device readers find nothing to read
    host_only = {k: u for k, u in per_layer.items()
                 if k not in ("dp_device_s", "device_idle")}
    assert {k: v["unit"] for k, v in res["metrics"].items()} == host_only
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in res["breakdown"].values())
    assert 0.0 <= res["checks"]["catalog_peak_share"]["value"] <= 1.0


def _run_script(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "io3.nfs.rf",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_run_exits_nonzero_without_a_tpu():
    proc = _run_script(ROOT, {})
    _no_result(proc)
    assert "TPU" in proc.stderr


def test_run_needs_the_program(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_script(tmp_path, {"PYTHONPATH": ""})
    _no_result(proc)
    assert "repro" in proc.stderr


@pytest.mark.parametrize("name", [m["name"] for m in
                                  cells.benchmark()["per_layer"]])
def test_every_per_layer_metric_has_a_reader(name):
    from bench.observe import load_reader

    assert callable(load_reader(name))
