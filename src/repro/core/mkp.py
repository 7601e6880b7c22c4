"""S/C Opt Nodes — exact solution via multidimensional 0-1 knapsack (paper §V-A).

Implements the paper's Algorithm 1 (``SimplifiedMKP``):

1. exclude nodes with ``s_i > M`` or ``t_i == 0`` (never worth/feasible alone);
2. extract resident-set constraints ``V_i`` under the given execution order;
3. drop redundant constraints (non-maximal: ``V_i ⊊ V_j``; trivial:
   ``Σ_{j∈V_i} s_j ≤ M``);
4. solve the remaining binary MKP with branch-and-bound
   (``maximize Σ x_i t_i  s.t.  Σ_{j∈V_i} x_j s_j ≤ M  ∀i``);
5. nodes appearing in no constraint (and not excluded) are trivially flagged.

The paper uses the OR-Tools BnB solver; OR-Tools is not available offline, so
``branch_and_bound_mkp`` below is our own implementation (ratio-ordered DFS
with a per-constraint fractional-relaxation upper bound). It is exact up to a
node-expansion budget; tests validate it against brute force on small
instances. Selector baselines from §VI-A (Greedy / Random / Ratio [60]) live
here too, behind the common ``solve_nodes`` entry point.

Scores are rounded to the nearest integer inside the solver (paper
footnote 3); ties and the returned set use the original float scores.
"""
from __future__ import annotations

import dataclasses
import random
from bisect import bisect_left
from typing import Callable, Sequence

from .graph import MVGraph


# ---------------------------------------------------------------------------
# Constraint extraction (Algorithm 1, lines 1-7)
# ---------------------------------------------------------------------------

def excluded_nodes(graph: MVGraph, budget: float) -> frozenset[int]:
    """V_exclude = {v_i | s_i > M  or  t_i == 0}."""
    return frozenset(
        i
        for i in range(graph.n)
        if graph.sizes[i] > budget or graph.scores[i] <= 0.0
    )


def get_constraints(
    graph: MVGraph,
    budget: float,
    order: Sequence[int],
    exclude: frozenset[int],
    n_workers: int = 1,
) -> list[frozenset[int]]:
    """Maximal, non-trivial resident-set constraints (paper ``GetConstraints``).

    ``n_workers > 1`` widens each node's residency window by the engine's
    out-of-order completion slack, so the selected flag set stays feasible
    under every k-worker interleaving (DESIGN.md §2).
    """
    sets = graph.resident_sets(order, exclude, n_workers)
    # Deduplicate, drop trivial (cannot be violated even if all flagged).
    uniq: dict[frozenset[int], None] = {}
    for s in sets:
        if not s:
            continue
        if sum(graph.sizes[j] for j in s) <= budget + 1e-9:
            continue
        uniq.setdefault(s, None)
    cand = list(uniq)
    # Keep only maximal sets. Use int bitmasks for fast subset tests.
    masks = [_mask(s) for s in cand]
    keep: list[frozenset[int]] = []
    for i, (s, m) in enumerate(zip(cand, masks)):
        maximal = True
        for j, m2 in enumerate(masks):
            if i != j and m | m2 == m2 and m != m2:
                maximal = False
                break
            if i < j and m == m2:
                maximal = False  # duplicate safety (dict already dedupes)
                break
        if maximal:
            keep.append(s)
    return keep


def _mask(s: frozenset[int]) -> int:
    m = 0
    for i in s:
        m |= 1 << i
    return m


# ---------------------------------------------------------------------------
# Branch-and-bound binary MKP (our replacement for OR-Tools' BnB)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MKPResult:
    chosen: frozenset[int]
    objective: float
    optimal: bool  # False if the node-expansion budget was exhausted
    expansions: int = 0


def branch_and_bound_mkp(
    items: Sequence[int],
    profits: dict[int, float],
    weights: dict[int, float],
    constraints: Sequence[frozenset[int]],
    budget: float,
    max_expansions: int = 200_000,
) -> MKPResult:
    """Maximize Σ profits[i]·x_i  s.t. for every constraint C:
    Σ_{i∈C} weights[i]·x_i ≤ budget.

    DFS over items sorted by profit density, with an upper bound from the
    fractional relaxation of the single tightest constraint (dropping all
    other constraints only increases the optimum, so the bound is valid).

    The bound at position ``k`` of the density order costs what the
    tightest constraint costs, not a walk over every remaining item:

    * **Tightest constraint** — the first index of the smallest remaining
      capacity, ``caps.index(min(caps))`` (ties to the lowest index), taken
      afresh only after an include or undo changed ``caps``. ``caps`` moves
      by the same ``-= w`` / ``+= w`` steps as ever, so its floats, and the
      constraint chosen, are those of a per-visit scan.
    * **Relaxation from k** — precomputed once per call, each constraint
      holds its items' positions in the density order (ascending), their
      weights and a suffix sum of their integer profits. Every item outside
      the constraint counts whole, so the bound is ``cur + suffix[k] -
      csuf[j]`` plus the fractional part of item ``j``: the greedy fill walks
      the constraint's items from ``bisect_left(positions, k)`` and stops at
      the first ``j`` that does not fit. The generic ``cur + suffix[k]`` is
      tested first, since ``min(ub, generic) <= best`` holds iff either does.
    * **Exactness** — the integer part ``ub`` is an exact Python int and
      the fraction ``f`` the same float as a per-item walk computes. That
      walk adds ``f`` in the middle of the integer sum and rounds once per
      later addition (at most ``n + 1`` roundings), so its float lies
      within ``(n + 2)·(ub + |f| + 1)·2⁻⁵³`` of the exact ``ub + f``.
      Where ``ub + f - best`` lies farther than twice that from 0 (and
      every integer is below 2⁵², exact as a float), its sign decides
      ``bound <= best`` exactly as the walk's float would. Inside that
      band the walk's own order of addition is replayed, so the test never
      flips and the search visits the same nodes in the same order.
    """
    # Integer-round profits (paper footnote 3) for the search; keep >=1 for
    # any strictly positive score so rounding never erases a benefit.
    iprof = {
        i: max(1, round(profits[i])) if profits[i] > 0 else 0 for i in items
    }
    order = sorted(
        items, key=lambda i: (-(iprof[i] / max(weights[i], 1e-12)), weights[i])
    )
    n = len(order)
    cons = [tuple(sorted(c)) for c in constraints]
    item_cons: dict[int, list[int]] = {i: [] for i in items}
    for ci, c in enumerate(cons):
        for i in c:
            if i in item_cons:
                item_cons[i].append(ci)
    caps = [budget] * len(cons)

    # Per-position views of the density order.
    prof_at = [iprof[i] for i in order]
    w_at = [weights[i] for i in order]
    cons_at = [item_cons[i] for i in order]
    # Suffix profit sums for a cheap generic bound.
    suffix = [0] * (n + 1)
    for k in range(n - 1, -1, -1):
        suffix[k] = suffix[k + 1] + prof_at[k]
    # Per constraint: its items' positions in ``order`` (ascending), their
    # weights, and the suffix sums of their integer profits.
    cpos: list[list[int]] = [[] for _ in cons]
    for k in range(n):
        for ci in cons_at[k]:
            cpos[ci].append(k)
    cw = [[w_at[k] for k in ps] for ps in cpos]
    csuf = []
    for ps in cpos:
        s = [0] * (len(ps) + 1)
        for j in range(len(ps) - 1, -1, -1):
            s[j] = s[j + 1] + prof_at[ps[j]]
        csuf.append(s)
    # Fast comparison needs every integer below 2**52 (exact as a float).
    fast_ok = suffix[0] < 2**52
    tol_scale = (n + 2) * 2.0**-52

    best_set: list[int] = []
    best_val = 0
    expansions = 0
    exhausted = False
    tight = 0  # tightest constraint while caps is unchanged
    caps_dirty = True

    # Explicit-stack DFS (include branch explored first, matching the
    # recursive formulation bitwise): partition-expanded graphs can have
    # thousands of items, far past CPython's recursion limit. A frame is
    # ``(k, cur)`` to visit position k, or ``(-1, k)`` to undo the include
    # of position k (restore its capacity/chosen mutations).
    chosen: list[int] = []
    stack: list[tuple[int, int]] = [(0, 0)]
    while stack:
        k, cur = stack.pop()
        if k < 0:
            chosen.pop()
            w = w_at[cur]
            for ci in cons_at[cur]:
                caps[ci] += w
            caps_dirty = True
            continue
        expansions += 1
        if expansions > max_expansions:
            exhausted = True
            break  # best_val/best_set already hold the incumbent
        if cur > best_val:
            best_val = cur
            best_set = list(chosen)
        if k >= n:
            continue
        # Upper bound for completing from position k (see the docstring).
        gen = cur + suffix[k]
        if gen <= best_val:
            continue
        if cons:
            if caps_dirty:
                tight = caps.index(min(caps))
                caps_dirty = False
            cap = caps[tight]
            ps = cpos[tight]
            ws = cw[tight]
            m = len(ps)
            j = bisect_left(ps, k)
            while j < m and ws[j] <= cap:
                cap -= ws[j]
                j += 1
            if j < m:
                # ps[j] is the first item of the constraint that does not fit
                ub = gen - csuf[tight][j]
                w = ws[j]
                if w > 0:
                    p = ps[j]
                    f = prof_at[p] * (cap / w)
                    y = (ub - best_val) + f
                    if fast_ok and abs(y) > tol_scale * (ub + abs(f) + 1):
                        prune = y < 0
                    else:
                        # replay the per-item walk's order of addition
                        walk = (gen - suffix[p]) + f
                        j += 1
                        for idx in range(p + 1, n):
                            if j < m and ps[j] == idx:
                                j += 1
                            else:
                                walk += prof_at[idx]
                        prune = walk <= best_val
                    if prune:
                        continue
                elif ub <= best_val:
                    continue
        # LIFO: push the exclude branch first so the include branch (and
        # its undo) run before it, exactly like the recursive include-first
        stack.append((k + 1, cur))
        w = w_at[k]
        lim = w - 1e-9
        ks = cons_at[k]
        for ci in ks:
            if not caps[ci] >= lim:
                break
        else:
            for ci in ks:
                caps[ci] -= w
            caps_dirty = True
            chosen.append(order[k])
            stack.append((-1, k))
            stack.append((k + 1, cur + prof_at[k]))
    chosen = frozenset(best_set)
    return MKPResult(
        chosen=chosen,
        objective=sum(profits[i] for i in chosen),
        optimal=not exhausted,
        expansions=expansions,
    )


# ---------------------------------------------------------------------------
# Algorithm 1: SimplifiedMKP
# ---------------------------------------------------------------------------

def simplified_mkp(
    graph: MVGraph,
    budget: float,
    order: Sequence[int],
    max_expansions: int = 200_000,
    n_workers: int = 1,
    max_entry_bytes: float | None = None,
) -> frozenset[int]:
    """The paper's exact node-selection step (Algorithm 1).

    ``max_entry_bytes`` additionally excludes any single node larger than
    that cap — used when ``budget`` is an aggregate over cluster nodes but
    one entry must still fit a single node's catalog share.
    """
    cap = budget if max_entry_bytes is None else min(budget, max_entry_bytes)
    exclude = excluded_nodes(graph, cap)
    cons = get_constraints(graph, budget, order, exclude, n_workers)
    v_mkp: set[int] = set().union(*cons) if cons else set()
    if v_mkp:
        res = branch_and_bound_mkp(
            items=sorted(v_mkp),
            profits={i: graph.scores[i] for i in v_mkp},
            weights={i: graph.sizes[i] for i in v_mkp},
            constraints=cons,
            budget=budget,
            max_expansions=max_expansions,
        )
        chosen = set(res.chosen)
    else:
        chosen = set()
    # Line 9: nodes in no constraint (and not excluded) are trivially flagged.
    chosen |= set(range(graph.n)) - v_mkp - set(exclude)
    return frozenset(chosen)


# ---------------------------------------------------------------------------
# Selector baselines (paper §VI-A): Greedy / Random / Ratio-based [60]
# ---------------------------------------------------------------------------

def _flag_incrementally(
    graph: MVGraph,
    budget: float,
    order: Sequence[int],
    candidates: Sequence[int],
    n_workers: int = 1,
    max_entry_bytes: float | None = None,
) -> frozenset[int]:
    """Flag candidates one at a time if doing so keeps peak memory ≤ M."""
    pos_order = list(order)
    lc = graph.release_pos(pos_order, n_workers)
    from .graph import positions

    pos = positions(pos_order)
    cap = budget if max_entry_bytes is None else min(budget, max_entry_bytes)
    prof = [0.0] * graph.n
    chosen: set[int] = set()
    for i in candidates:
        if graph.sizes[i] > cap or graph.scores[i] <= 0:
            continue
        lo, hi = pos[i], lc[i]
        if max(prof[lo : hi + 1], default=0.0) + graph.sizes[i] <= budget + 1e-9:
            for k in range(lo, hi + 1):
                prof[k] += graph.sizes[i]
            chosen.add(i)
    return frozenset(chosen)


def greedy_select(
    graph: MVGraph,
    budget: float,
    order: Sequence[int],
    n_workers: int = 1,
    max_entry_bytes: float | None = None,
) -> frozenset[int]:
    """Iterate nodes in execution order; flag if feasible."""
    return _flag_incrementally(
        graph, budget, order, list(order), n_workers, max_entry_bytes
    )


def random_select(
    graph: MVGraph,
    budget: float,
    order: Sequence[int],
    seed: int = 0,
    n_workers: int = 1,
    max_entry_bytes: float | None = None,
) -> frozenset[int]:
    rng = random.Random(seed)
    cand = list(range(graph.n))
    rng.shuffle(cand)
    return _flag_incrementally(graph, budget, order, cand, n_workers, max_entry_bytes)


def ratio_select(
    graph: MVGraph,
    budget: float,
    order: Sequence[int],
    n_workers: int = 1,
    max_entry_bytes: float | None = None,
) -> frozenset[int]:
    """Ratio-based selection [60]: highest score/size first."""
    cand = sorted(
        range(graph.n),
        key=lambda i: -(graph.scores[i] / max(graph.sizes[i], 1e-12)),
    )
    return _flag_incrementally(graph, budget, order, cand, n_workers, max_entry_bytes)


# ---------------------------------------------------------------------------
# Hierarchical planning: the outer knapsack over per-MV partition columns
# ---------------------------------------------------------------------------

def greedy_column_select(
    curves: Sequence,
    budget: float,
    windows: Sequence[Sequence[tuple[int, int]]],
    n_steps: int,
    max_entry_bytes: float | None = None,
) -> list[list[int]]:
    """Select one partition column per MV under windowed residency budgets.

    The outer knapsack of the hierarchical partitioned planner (DESIGN.md
    §8). ``curves`` are per-MV ``BenefitCurve``s (density-ranked partitions
    with their sizes/scores); ``windows[v][p] = (enter, release)`` is the
    residency window — in plan steps, ``n_steps`` of them — that partition
    ``p`` of MV ``v`` would occupy if pinned under the current execution
    order (for the partition-major orders the hierarchical planner emits,
    these are the *exact* expanded k-worker windows of DESIGN.md §2).

    Because each curve's marginal densities are non-increasing, a single
    global density-ordered greedy scan selects a prefix of every MV's
    ranking — i.e. one "pin-the-top-j" column per MV — the Dantzig greedy
    for a multiple-choice knapsack with concave choice frontiers. A
    partition that no longer fits the step profile is skipped (not frozen):
    a later, smaller partition of the same MV may still fit, so a selection
    is a column with at most a few density-ordered gaps.

    Partitions larger than ``min(budget, max_entry_bytes)`` or with
    non-positive score are never selected. Returns the chosen partition ids
    per MV (subset of ``curves[v].parts``, in ranking order). The selection
    satisfies ``profile[step] <= budget`` at every step, each pinned
    partition charged over its own window.
    """
    import heapq

    cap = budget if max_entry_bytes is None else min(budget, max_entry_bytes)
    prof = [0.0] * max(n_steps, 1)
    chosen: list[list[int]] = [[] for _ in curves]

    def density(v: int, j: int) -> float:
        return curves[v].scores[j] / max(curves[v].sizes[j], 1e-12)

    heap: list[tuple[float, int, int]] = []
    for v, c in enumerate(curves):
        if c.parts:
            heap.append((-density(v, 0), v, 0))
    heapq.heapify(heap)
    while heap:
        _, v, j = heapq.heappop(heap)
        c = curves[v]
        if j + 1 < len(c.parts):
            heapq.heappush(heap, (-density(v, j + 1), v, j + 1))
        size, score = c.sizes[j], c.scores[j]
        if score <= 0.0 or size > cap:
            continue
        lo, hi = windows[v][c.parts[j]]
        if max(prof[lo : hi + 1], default=0.0) + size <= budget + 1e-9:
            for k in range(lo, hi + 1):
                prof[k] += size
            chosen[v].append(c.parts[j])
    return chosen


NodeSolver = Callable[[MVGraph, float, Sequence[int]], frozenset[int]]

NODE_SOLVERS: dict[str, NodeSolver] = {
    "mkp": simplified_mkp,
    "greedy": greedy_select,
    "random": random_select,
    "ratio": ratio_select,
}
