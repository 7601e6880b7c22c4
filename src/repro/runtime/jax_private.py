"""The private JAX APIs this repo relies on, kept in one place.

JAX has no public way to ask whether a backend is initialized without
initializing one, nor a public name for the event it reports for a backend
compile. ``tests/runtime/test_jax_private.py`` fails as soon as a JAX
upgrade removes or renames either.
"""
from __future__ import annotations

import sys

__all__ = ["backend_compile_event", "holds_tpu"]


def holds_tpu() -> bool:
    """Whether this process has initialized a TPU backend, asked without
    initializing any backend (a process that never imported JAX stays
    JAX-free)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized() and (
        "tpu" in xla_bridge.backends()
    )


def backend_compile_event() -> str:
    """The ``jax.monitoring`` duration event JAX records for each backend
    compile (cache hits record none)."""
    from jax._src.dispatch import BACKEND_COMPILE_EVENT

    return BACKEND_COMPILE_EVENT
