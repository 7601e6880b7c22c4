"""CPU rehearsal of ``chip_smoke.py``: its scenario at a tiny size on the
jitted xla path, its refusal to run without a TPU, and where its compile
cache goes."""
import importlib.util
from pathlib import Path


def _chip_smoke():
    path = Path(__file__).resolve().parents[2] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refresh_check_is_bitwise_at_tiny_size_on_xla(tmp_path):
    lines = []
    out = _chip_smoke().refresh_check(1 << 14, tmp_path / "smoke",
                                      impl="xla", log=lines.append)
    assert out["impl"] == "xla" and lines[0] == "impl=xla"
    assert {"calibrate", "build", "round1", "round2", "reference",
            "verify"} <= set(out["times"])
    assert any(line.startswith("verify: P=8 xla") for line in lines)
    assert not (tmp_path / "smoke").exists()


def test_main_exits_nonzero_without_a_tpu(capsys):
    assert _chip_smoke().main([]) != 0
    assert capsys.readouterr().out == ""


def test_compile_cache_dir_from_env_else_fixed_checkout_path(
        monkeypatch, tmp_path):
    import os
    import subprocess
    import sys

    import jax

    from benchmarks.compile_cache import enable_compile_cache

    repo = Path(__file__).resolve().parents[2]
    prev = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
    # with the variable set, a compile lands in that directory (a fresh
    # process: the cache binds its directory at the first compile)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(repo), str(repo / "src")]))
    code = ("from benchmarks.compile_cache import enable_compile_cache; "
            "from repro.mv import dataplane as dp; import numpy as np; "
            "print(enable_compile_cache()); "
            "dp.hash64(np.arange(9, dtype=np.int64), impl='xla')")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())
