"""Determinism lints: the two historical-bug fixtures MUST fire (and their
shipped fixes stay quiet), the repo scan reproduces exactly the checked-in
baseline, and each AST rule discriminates correctly on minimal snippets.
"""
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import fixtures, gating, load_baseline
from repro.analysis.determinism import (
    SIZE_LIKE_STATIC_ARGS,
    lint_dataplane_kernels,
    lint_jaxpr,
    lint_paths,
    lint_source,
)

REPO = Path(__file__).resolve().parents[2]


def rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# historical bug 2: _filter_mask static threshold (must-fire fixture)
# ---------------------------------------------------------------------------

def test_legacy_filter_mask_fires_static_arg_retrace():
    got = lint_source(fixtures.LEGACY_FILTER_MASK_SRC, "legacy")
    assert "static-arg-retrace" in rules(got)


def test_shipped_filter_mask_is_quiet():
    assert not gating(lint_source(fixtures.SHIPPED_FILTER_MASK_SRC, "ok"))


# ---------------------------------------------------------------------------
# historical bug 1: fused shape-specialized tanh (must-fire fixture)
# ---------------------------------------------------------------------------

def test_legacy_fused_map_fires_transcendental_and_fma():
    pytest.importorskip("jax")
    f32 = np.linspace(-1.0, 1.0, 8, dtype=np.float32)
    got = rules(lint_jaxpr(
        fixtures.legacy_fused_map(), f32, f32, symbol="legacy_fused_map"
    ))
    assert "transcendental-kernel" in got
    assert "fma-contraction" in got


def test_shipped_map_kernels_are_quiet():
    pytest.importorskip("jax")
    f32 = np.linspace(-1.0, 1.0, 8, dtype=np.float32)
    (mul,) = fixtures.shipped_map_kernels()
    assert not gating(lint_jaxpr(mul, f32, symbol="map_mul"))


# ---------------------------------------------------------------------------
# repo scan == baseline (the CI gate's ground truth)
# ---------------------------------------------------------------------------

def test_repo_scan_matches_checked_in_baseline():
    found = {f.fingerprint for f in gating(lint_paths(REPO))}
    baseline = load_baseline(REPO / "tools" / "sc_lint_baseline.json")
    assert found == baseline
    assert "unstable-sort:src/repro/mv/dataplane.py:group_reduce" in found


def test_shipped_dataplane_jaxprs_are_clean():
    pytest.importorskip("jax")
    assert not gating(lint_dataplane_kernels())


# ---------------------------------------------------------------------------
# rule discrimination on minimal snippets
# ---------------------------------------------------------------------------

def test_unstable_sort_rule():
    fires = lint_source("import numpy as np\no = np.argsort(k)\n")
    assert rules(fires) == {"unstable-sort"}
    quiet = lint_source(
        'import numpy as np\no = np.argsort(k, kind="stable")\n'
    )
    assert not quiet
    quiet2 = lint_source(
        'import numpy as np\no = np.argsort(k, kind="mergesort")\n'
    )
    assert not quiet2


def test_static_arg_allowlist():
    assert "P" in SIZE_LIKE_STATIC_ARGS
    quiet = lint_source(
        'import jax\nf = jax.jit(g, static_argnames="P")\n'
    )
    assert "static-arg-retrace" not in rules(quiet)
    fires = lint_source(
        'import jax\nf = jax.jit(g, static_argnames="threshold")\n'
    )
    assert "static-arg-retrace" in rules(fires)


def test_static_argnums_resolved_through_local_def():
    src = (
        "import jax\n"
        "def g(x, threshold):\n"
        "    return x > threshold\n"
        "f = jax.jit(g, static_argnums=(1,))\n"
    )
    assert "static-arg-retrace" in rules(lint_source(src))


def test_x64_leak_rule():
    leaky = (
        "import jax\n"
        "def enable():\n"
        '    jax.config.update("jax_enable_x64", True)\n'
        "    do_work()\n"
    )
    assert "x64-leak" in rules(lint_source(leaky))
    safe = (
        "import jax\n"
        "def scoped():\n"
        '    jax.config.update("jax_enable_x64", True)\n'
        "    try:\n"
        "        do_work()\n"
        "    finally:\n"
        '        jax.config.update("jax_enable_x64", False)\n'
    )
    assert "x64-leak" not in rules(lint_source(safe))
