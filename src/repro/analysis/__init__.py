"""Static analysis over the operator IR, source ASTs, and traced jaxprs
(sc-lint, DESIGN.md §10).

Three pass families, each importable on its own (this package root stays
lightweight so ``core.altopt`` can reuse ``plan_check`` without cycles):

* ``delta_safety``  — Z-set weight closure, rid stability of UNION/splice
  paths, AGG int64 fixed-point overflow bounds, JOIN partial-fallback
  reachability — typed over ``mv.ir.ViewIR``.
* ``determinism``   — AST lints (unstable sorts, value-like static jit
  arguments, x64-state leaks) and jaxpr lints (transcendentals / FMA
  contraction / silent f32 downcasts inside bitwise-contract kernels) for
  ``mv/dataplane.py``.
* ``plan_check``    — plan feasibility as a reusable analyzer: minimal
  counterexample interleavings and the shed-repair loop the hierarchical
  planner uses.

``tools/sc_lint.py`` drives all three against the repo baseline.
"""
from .findings import (
    Finding,
    GATING_LEVELS,
    LEVELS,
    format_findings,
    gating,
    load_baseline,
    new_findings,
    save_baseline,
    stale_entries,
    to_json,
)

__all__ = [
    "Finding",
    "LEVELS",
    "GATING_LEVELS",
    "gating",
    "load_baseline",
    "save_baseline",
    "new_findings",
    "stale_entries",
    "to_json",
    "format_findings",
]
