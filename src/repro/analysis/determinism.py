"""Determinism linting of the data plane and kernels (sc-lint pass family 2).

Two layers, both encoding hazards this repo actually shipped and fixed:

**Source (AST) lints** over ``mv/`` and ``kernels/``:

* ``unstable-sort`` — ``argsort`` without ``kind="stable"``. An unstable
  grouping sort feeding an order-sensitive consumer breaks bitwise
  equivalence across runs/impls. The one sanctioned unstable sort
  (``group_reduce``'s jitted-path grouping — exact integer sums commute)
  stays in the baseline rather than being silenced in code.
* ``static-arg-retrace`` — ``jax.jit(..., static_argnums=/static_argnames=)``
  marking a *value-like* parameter static: every distinct value recompiles
  (the historical ``_filter_mask`` bug jitted its float threshold static).
  Genuinely shape-like names (block sizes, partition counts, flags) are
  allowlisted.
* ``x64-leak`` — ``jax.config.update("jax_enable_x64", ...)`` in a function
  with no restoring update inside a ``finally``/``except`` handler: an
  error between enable and restore leaks global x64 state into unrelated
  f32 code.

**Jaxpr lints** over traced kernels (recursing into pjit/scan/cond
sub-jaxprs):

* ``transcendental-kernel`` — transcendental primitives inside a
  bitwise-contract kernel. XLA's transcendental approximations are
  fusion- and shape-dependent (the historical fused-``tanh`` kernel changed
  results with batch shape); only correctly-rounded IEEE ops are batch-
  invariant. The shipped map uses softsign (div/abs) for exactly this
  reason.
* ``fma-contraction`` — a float ``mul`` feeding an ``add``/``sub`` in the
  same jit unit: XLA:CPU may contract it into an FMA, changing the low bit
  vs the unfused reference (why ``map_derived`` jits the multiply alone).
* ``f32-downcast`` — a float64→float32 (or →f16) ``convert_element_type``:
  silent precision loss inside an x64 data path.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterable, Sequence

from .findings import Finding

__all__ = [
    "SIZE_LIKE_STATIC_ARGS",
    "lint_source",
    "lint_file",
    "lint_paths",
    "lint_jaxpr",
    "lint_dataplane_kernels",
    "DEFAULT_LINT_GLOBS",
]

# static jit arguments that are legitimately shape-like: few distinct values
# over a process lifetime, each changing the traced program's shapes/control
# flow. Anything else marked static is treated as value-like.
SIZE_LIKE_STATIC_ARGS = frozenset({
    "P", "n", "L", "steps", "chunk", "chunks", "axis", "ndim", "width",
    "depth", "block", "block_q", "block_k", "bq", "bk", "interpret",
    "causal", "heads", "dim", "n_partitions",
})

DEFAULT_LINT_GLOBS = ("src/repro/mv/*.py", "src/repro/kernels/*.py")

STABLE_KINDS = ("stable", "mergesort")

# jax primitives whose results depend on a platform/fusion-specific
# approximation rather than correct IEEE rounding. sqrt/div/abs/add/mul are
# correctly rounded and excluded; integer_pow lowers to exact multiplies.
TRANSCENDENTAL_PRIMS = frozenset({
    "tanh", "exp", "exp2", "expm1", "log", "log2", "log1p", "logistic",
    "erf", "erfc", "erf_inv", "sin", "cos", "tan", "asin", "acos", "atan",
    "atan2", "sinh", "cosh", "asinh", "acosh", "atanh", "pow", "rsqrt",
    "cbrt", "digamma", "lgamma",
})


# ---------------------------------------------------------------------------
# AST lints
# ---------------------------------------------------------------------------

def _const(node):
    return node.value if isinstance(node, ast.Constant) else None


def _call_name(func: ast.AST) -> str:
    """Dotted name of a call target, best effort ('jax.jit', 'np.argsort')."""
    parts: list[str] = []
    while isinstance(func, ast.Attribute):
        parts.append(func.attr)
        func = func.value
    if isinstance(func, ast.Name):
        parts.append(func.id)
    return ".".join(reversed(parts))


def _static_names(call: ast.Call, fn_params: list[str] | None) -> list[str]:
    """Parameter names a jax.jit call marks static (best effort)."""
    names: list[str] = []
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = _const(kw.value)
            if isinstance(v, str):
                names.append(v)
            elif isinstance(kw.value, (ast.Tuple, ast.List)):
                names.extend(
                    c for c in (_const(e) for e in kw.value.elts)
                    if isinstance(c, str)
                )
        elif kw.arg == "static_argnums" and fn_params is not None:
            idxs: list[int] = []
            v = _const(kw.value)
            if isinstance(v, int):
                idxs = [v]
            elif isinstance(kw.value, (ast.Tuple, ast.List)):
                idxs = [
                    c for c in (_const(e) for e in kw.value.elts)
                    if isinstance(c, int)
                ]
            for i in idxs:
                if 0 <= i < len(fn_params):
                    names.append(fn_params[i])
    return names


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str):
        self.path = path
        self.findings: list[Finding] = []
        self.fn_stack: list[str] = ["<module>"]
        self.restore_depth = 0  # inside a finally block / except handler
        # functions defined at any scope, for static_argnums resolution
        self.fn_defs: dict[str, ast.FunctionDef] = {}
        # per-function x64 bookkeeping: [(enable_call, in_restore)]
        self.x64_calls: dict[str, list[tuple[ast.Call, bool]]] = {}

    # -- scope tracking ----------------------------------------------------
    def _collect_defs(self, tree: ast.AST):
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.fn_defs.setdefault(node.name, node)

    def visit_FunctionDef(self, node: ast.FunctionDef):
        self.fn_stack.append(node.name)
        self.generic_visit(node)
        self.fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Try(self, node: ast.Try):
        for part in (node.body, node.orelse):
            for child in part:
                self.visit(child)
        self.restore_depth += 1
        for handler in node.handlers:
            for child in handler.body:
                self.visit(child)
        for child in node.finalbody:
            self.visit(child)
        self.restore_depth -= 1

    # -- rules -------------------------------------------------------------
    def visit_Call(self, node: ast.Call):
        name = _call_name(node.func)
        symbol = self.fn_stack[-1]

        if name.endswith("argsort"):
            kinds = [
                _const(kw.value) for kw in node.keywords if kw.arg == "kind"
            ]
            # positional kind: np.argsort(a, axis, kind)
            if len(node.args) >= 3:
                kinds.append(_const(node.args[2]))
            if not any(k in STABLE_KINDS for k in kinds):
                self.findings.append(Finding(
                    "unstable-sort", "warning", self.path, symbol,
                    "argsort without kind=\"stable\": ties reorder freely; "
                    "only order-insensitive consumers (exact integer sums) "
                    "may consume this permutation",
                    node.lineno,
                ))

        if name.endswith(".jit") or name == "jit":
            fn_params = None
            if node.args and isinstance(node.args[0], ast.Name):
                fndef = self.fn_defs.get(node.args[0].id)
                if fndef is not None:
                    fn_params = [a.arg for a in fndef.args.args]
            for pname in _static_names(node, fn_params):
                if pname not in SIZE_LIKE_STATIC_ARGS:
                    self.findings.append(Finding(
                        "static-arg-retrace", "warning", self.path,
                        symbol if symbol != "<module>" else (
                            node.args[0].id if node.args and
                            isinstance(node.args[0], ast.Name) else symbol
                        ),
                        f"static jit argument {pname!r} looks value-like: "
                        "every distinct value triggers a full retrace "
                        "(pass it traced, or allowlist a genuinely "
                        "shape-like name)",
                        node.lineno,
                    ))

        if name.endswith("config.update") and node.args and \
                _const(node.args[0]) == "jax_enable_x64":
            self.x64_calls.setdefault(symbol, []).append(
                (node, self.restore_depth > 0)
            )

        self.generic_visit(node)

    def finish(self):
        for symbol, calls in self.x64_calls.items():
            if any(in_restore for _, in_restore in calls):
                continue  # a restoring update exists in finally/except
            node = calls[0][0]
            self.findings.append(Finding(
                "x64-leak", "warning", self.path, symbol,
                "jax_enable_x64 flipped with no restoring update in a "
                "finally/except path: an error after the flip leaks global "
                "x64 state into unrelated code",
                node.lineno,
            ))


def lint_source(text: str, path: str = "<string>") -> list[Finding]:
    """AST-lint one source string (fixtures lint snippets this way)."""
    tree = ast.parse(text)
    linter = _Linter(path)
    linter._collect_defs(tree)
    linter.visit(tree)
    linter.finish()
    return linter.findings


def lint_file(path: str | Path, repo_root: str | Path | None = None
              ) -> list[Finding]:
    p = Path(path)
    rel = str(p.relative_to(repo_root)) if repo_root else str(p)
    return lint_source(p.read_text(), rel)


def lint_paths(
    repo_root: str | Path, globs: Sequence[str] = DEFAULT_LINT_GLOBS
) -> list[Finding]:
    root = Path(repo_root)
    out: list[Finding] = []
    for g in globs:
        for p in sorted(root.glob(g)):
            out.extend(lint_file(p, root))
    return out


# ---------------------------------------------------------------------------
# Jaxpr lints
# ---------------------------------------------------------------------------

def _subjaxprs(params: dict):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    kinds = (ClosedJaxpr, Jaxpr)
    for v in params.values():
        if isinstance(v, kinds):
            yield v
        elif isinstance(v, (tuple, list)):
            for e in v:
                if isinstance(e, kinds):
                    yield e


def _is_float(var) -> bool:
    dtype = getattr(getattr(var, "aval", None), "dtype", None)
    return dtype is not None and getattr(dtype, "kind", "") == "f"


def _walk_jaxpr(jaxpr, path: str, symbol: str, out: list[Finding]):
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    mul_outs: set = set()
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        for sub in _subjaxprs(eqn.params):
            _walk_jaxpr(sub, path, symbol, out)
        floaty = any(_is_float(v) for v in eqn.invars) or any(
            _is_float(v) for v in eqn.outvars
        )
        if prim in TRANSCENDENTAL_PRIMS and floaty:
            out.append(Finding(
                "transcendental-kernel", "warning", path, symbol,
                f"primitive '{prim}' in a bitwise-contract kernel: XLA's "
                "approximation is fusion/shape-dependent, breaking batch "
                "invariance — use correctly-rounded ops (the softsign "
                "split) or move it off the bitwise path",
            ))
        if prim == "mul" and eqn.outvars and _is_float(eqn.outvars[0]):
            mul_outs.add(id(eqn.outvars[0]))
        if prim in ("add", "sub") and floaty and any(
            id(v) in mul_outs for v in eqn.invars
        ):
            out.append(Finding(
                "fma-contraction", "warning", path, symbol,
                "float mul feeding add/sub in one jit unit: XLA may "
                "contract to an FMA, changing the low bit vs the unfused "
                "reference — split into separate jit units "
                "(dataplane.map_derived's two-kernel contract)",
            ))
        if prim == "convert_element_type" and eqn.invars:
            src = getattr(getattr(eqn.invars[0], "aval", None), "dtype", None)
            dst = eqn.params.get("new_dtype")
            if src is not None and dst is not None and \
                    getattr(src, "kind", "") == "f" and \
                    getattr(dst, "kind", "") == "f" and \
                    dst.itemsize < src.itemsize:
                out.append(Finding(
                    "f32-downcast", "warning", path, symbol,
                    f"silent {src}->{dst} downcast inside an x64 data "
                    "path: precision loss the table contract does not "
                    "declare",
                ))


def lint_jaxpr(
    fn, *args, symbol: str, path: str = "<jaxpr>",
    static_argnums=(), **kwargs
) -> list[Finding]:
    """Trace ``fn`` with sample ``args`` and lint the resulting jaxpr
    (recursively through pjit/scan/cond sub-jaxprs)."""
    import jax

    jaxpr = jax.make_jaxpr(fn, static_argnums=static_argnums)(*args, **kwargs)
    out: list[Finding] = []
    _walk_jaxpr(jaxpr, path, symbol, out)
    return out


def lint_dataplane_kernels() -> list[Finding]:
    """Trace every jitted XLA kernel of ``mv.dataplane`` with representative
    arguments and lint the jaxprs. Model kernels (``kernels/ops.py``) are
    out of scope: they carry no bitwise contract."""
    import numpy as np

    try:
        import jax  # noqa: F401
    except Exception as e:  # pragma: no cover - jax is a baked-in dep
        return [Finding(
            "lint-skipped", "info", "src/repro/mv/dataplane.py", "_jk",
            f"jax unavailable ({e}): jaxpr lints skipped",
        )]
    from ..mv import dataplane as dp

    path = "src/repro/mv/dataplane.py"
    i64 = np.arange(8, dtype=np.int64)
    f32 = np.linspace(-1.0, 1.0, 8, dtype=np.float32)
    samples: dict[str, tuple[tuple, tuple]] = {
        "hash": ((i64,), ()),
        "pid": ((i64, 4), (1,)),
        "map_mul": ((f32,), ()),
        "encode": ((f32,), ()),
        "encode_w": ((f32, i64), ()),
        "probe": ((i64, i64, 8), ()),
    }
    out: list[Finding] = []
    prev = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", True)
    try:
        kernels = dp._jk()
        for name, (args, static) in samples.items():
            if name not in kernels:
                out.append(Finding(
                    "lint-skipped", "info", path, f"_jk.{name}",
                    "kernel no longer exists; update lint_dataplane_kernels",
                ))
                continue
            out.extend(lint_jaxpr(
                kernels[name], *args, symbol=f"_jk.{name}", path=path,
                static_argnums=static,
            ))
    finally:
        jax.config.update("jax_enable_x64", prev)
    return out
