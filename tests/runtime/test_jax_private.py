"""The private JAX APIs of ``repro.runtime.jax_private`` still exist and
still mean what the repo relies on. These fail loudly on the JAX upgrade
that removes or renames one."""
import os
import subprocess
import sys
from pathlib import Path

from repro.runtime import jax_private

REPO = Path(__file__).resolve().parents[2]


def test_holds_tpu_is_false_on_cpu_and_initializes_no_backend():
    assert jax_private.holds_tpu() is False
    code = ("import jax; from jax._src import xla_bridge as xb; "
            "from repro.runtime import jax_private as jp; "
            "assert jp.holds_tpu() is False; "
            "assert not xb.backends_are_initialized(); "
            "jax.numpy.zeros(1).block_until_ready(); "
            "assert xb.backends_are_initialized(); "
            "assert 'cpu' in xb.backends() and jp.holds_tpu() is False")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)


def test_backend_compile_event_fires_once_per_compile():
    import jax
    import jax.monitoring
    import numpy as np

    event = jax_private.backend_compile_event()
    seen = []

    def listener(name, duration, **_):
        if name == event:
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        f = jax.jit(lambda x: x * 3 + 1)
        f(np.arange(5, dtype=np.float32)).block_until_ready()
        assert len(seen) == 1 and seen[0] >= 0
        f(np.arange(5, dtype=np.float32)).block_until_ready()  # cached
        assert len(seen) == 1
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
