"""MKP solver correctness: Algorithm 1 pieces + brute-force validation."""
import itertools
import random
from typing import Sequence

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    MVGraph,
    branch_and_bound_mkp,
    excluded_nodes,
    get_constraints,
    greedy_select,
    ratio_select,
    simplified_mkp,
)
from repro.core.mkp import MKPResult


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def random_dag(rng_draw, max_n=10):
    n = rng_draw(st.integers(2, max_n))
    edges = []
    for j in range(1, n):
        for i in range(j):
            if rng_draw(st.booleans()) and rng_draw(st.booleans()):
                edges.append((i, j))
    sizes = [rng_draw(st.integers(1, 20)) for _ in range(n)]
    scores = [rng_draw(st.integers(0, 20)) for _ in range(n)]
    return MVGraph(n, tuple(edges), tuple(float(s) for s in sizes),
                   tuple(float(t) for t in scores))


def brute_force_best(graph: MVGraph, budget: float, order):
    """Exhaustive best feasible flag set under a fixed order."""
    best, best_score = frozenset(), 0.0
    nodes = [i for i in range(graph.n) if graph.scores[i] > 0]
    for r in range(len(nodes) + 1):
        for combo in itertools.combinations(nodes, r):
            if graph.peak_memory(combo, order) <= budget + 1e-9:
                sc = graph.total_score(combo)
                if sc > best_score:
                    best_score, best = sc, frozenset(combo)
    return best, best_score


# ---------------------------------------------------------------------------
# unit tests
# ---------------------------------------------------------------------------

def chain(sizes, scores):
    n = len(sizes)
    return MVGraph(n, tuple((i, i + 1) for i in range(n - 1)),
                   tuple(sizes), tuple(scores))


def test_excluded_nodes():
    g = chain([5.0, 50.0, 5.0], [1.0, 1.0, 0.0])
    ex = excluded_nodes(g, budget=10.0)
    assert ex == frozenset({1, 2})  # node1 too big, node2 zero score


def test_constraints_trivial_and_maximal_pruning():
    # chain 0->1->2, all size 4, budget 10: every resident set fits -> trivial
    g = chain([4.0, 4.0, 4.0], [1.0, 1.0, 1.0])
    assert get_constraints(g, 10.0, [0, 1, 2], frozenset()) == []
    # budget 5: {0,1} and {1,2} both violate-able and maximal
    cons = get_constraints(g, 5.0, [0, 1, 2], frozenset())
    assert frozenset({0, 1}) in cons and frozenset({1, 2}) in cons
    # subset {1} must have been pruned as non-maximal
    assert frozenset({1}) not in cons


def test_bnb_single_knapsack_exact():
    # classic knapsack: values 60,100,120 weights 10,20,30 cap 50 -> 220
    items = [0, 1, 2]
    res = branch_and_bound_mkp(
        items,
        profits={0: 60, 1: 100, 2: 120},
        weights={0: 10, 1: 20, 2: 30},
        constraints=[frozenset(items)],
        budget=50,
    )
    assert res.chosen == frozenset({1, 2})
    assert res.objective == 220
    assert res.optimal


def test_simplified_mkp_flags_unconstrained_nodes():
    # two independent childless nodes are only resident at their own step
    g = MVGraph(2, (), (8.0, 8.0), (3.0, 4.0))
    u = simplified_mkp(g, budget=10.0, order=[0, 1])
    assert u == frozenset({0, 1})  # childless: resident only at own step


def test_simplified_mkp_respects_budget():
    # 0->2, 1->2 ; flagging both 0 and 1 co-resident at step of 2 -> pick best
    g = MVGraph(3, ((0, 2), (1, 2)), (8.0, 8.0, 1.0), (3.0, 4.0, 1.0))
    u = simplified_mkp(g, budget=10.0, order=[0, 1, 2])
    assert g.peak_memory(u, [0, 1, 2]) <= 10.0
    assert u == frozenset({1, 2})  # node1 scores higher than node0


# ---------------------------------------------------------------------------
# paper Figure-7-style instance: execution order determines feasibility
# ---------------------------------------------------------------------------

def fig7_style():
    # 0:A(100)->2:B(5) ; 1:C(100)->3:D(5) ; 4:E(10) independent leaf
    # scores == sizes (paper's simplification)
    sizes = (100.0, 100.0, 5.0, 5.0, 10.0)
    return MVGraph(5, ((0, 2), (1, 3)), sizes, sizes)


def test_fig7_order_determines_flaggable_set():
    g = fig7_style()
    bad = [0, 1, 2, 3, 4]   # A C B D E : A and C co-resident
    good = [0, 2, 1, 3, 4]  # A B C D E : A released before C executes
    u_bad = simplified_mkp(g, 100.0, bad)
    u_good = simplified_mkp(g, 100.0, good)
    assert g.total_score(u_bad) == pytest.approx(115.0)  # one big + D + E
    assert g.total_score(u_good) == pytest.approx(210.0)  # both bigs + E
    assert {0, 1} <= set(u_good)
    # brute force agreement
    _, bf_bad = brute_force_best(g, 100.0, bad)
    _, bf_good = brute_force_best(g, 100.0, good)
    assert g.total_score(u_bad) == pytest.approx(bf_bad)
    assert g.total_score(u_good) == pytest.approx(bf_good)


# ---------------------------------------------------------------------------
# property tests: exactness vs brute force, feasibility, dominance
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(st.data())
def test_mkp_matches_brute_force(data):
    g = random_dag(data.draw, max_n=9)
    budget = float(data.draw(st.integers(5, 40)))
    order = g.topological_order()
    u = simplified_mkp(g, budget, order)
    assert g.peak_memory(u, order) <= budget + 1e-9
    _, bf = brute_force_best(g, budget, order)
    assert g.total_score(u) == pytest.approx(bf)


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_mkp_dominates_heuristics(data):
    g = random_dag(data.draw, max_n=10)
    budget = float(data.draw(st.integers(5, 40)))
    order = g.topological_order()
    u = simplified_mkp(g, budget, order)
    for heur in (greedy_select, ratio_select):
        uh = heur(g, budget, order)
        assert g.peak_memory(uh, order) <= budget + 1e-9
        assert g.total_score(u) >= g.total_score(uh) - 1e-9


# ---------------------------------------------------------------------------
# differential test: the bound's rewrite keeps the search bit for bit
# ---------------------------------------------------------------------------

# The branch-and-bound as it was before its bound walked per-constraint
# positions and suffix sums (a linear scan over every remaining item and
# every constraint per expansion), kept verbatim as the reference: the
# rewrite must return the same MKPResult, expansion count included.
def reference_branch_and_bound_mkp(
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
    """
    # Integer-round profits (paper footnote 3) for the search; keep >=1 for
    # any strictly positive score so rounding never erases a benefit.
    iprof = {
        i: max(1, round(profits[i])) if profits[i] > 0 else 0 for i in items
    }
    order = sorted(
        items, key=lambda i: (-(iprof[i] / max(weights[i], 1e-12)), weights[i])
    )
    cons = [tuple(sorted(c)) for c in constraints]
    item_cons: dict[int, list[int]] = {i: [] for i in items}
    for ci, c in enumerate(cons):
        for i in c:
            if i in item_cons:
                item_cons[i].append(ci)
    caps = [budget] * len(cons)

    best_set: list[int] = []
    best_val = 0
    expansions = 0
    exhausted = False

    # Suffix profit sums for a cheap generic bound.
    suffix = [0] * (len(order) + 1)
    for k in range(len(order) - 1, -1, -1):
        suffix[k] = suffix[k + 1] + iprof[order[k]]

    def bound(k: int, cur: int, caps_now: list[float]) -> float:
        """Upper bound for completing from item index k."""
        generic = cur + suffix[k]
        if not cons:
            return generic
        # Fractional knapsack on the tightest constraint only.
        ci = min(range(len(cons)), key=lambda c: caps_now[c])
        cap = caps_now[ci]
        in_c = set(cons[ci])
        ub = cur
        frac_done = False
        for idx in range(k, len(order)):
            i = order[idx]
            if i not in in_c:
                ub += iprof[i]  # unconstrained under this relaxation
            elif not frac_done:
                w = weights[i]
                if w <= cap:
                    cap -= w
                    ub += iprof[i]
                else:
                    if w > 0:
                        ub += iprof[i] * (cap / w)
                    frac_done = True  # constraint full; later in-c items add 0
        return min(ub, generic)

    # Explicit-stack DFS (include branch explored first, matching the
    # recursive formulation bitwise): partition-expanded graphs can have
    # thousands of items, far past CPython's recursion limit. "undo" frames
    # restore the capacity/chosen mutations when an include subtree is done.
    chosen: list[int] = []
    stack: list[tuple] = [("visit", 0, 0)]
    while stack:
        frame = stack.pop()
        if frame[0] == "undo":
            i = frame[1]
            chosen.pop()
            for ci in item_cons[i]:
                caps[ci] += weights[i]
            continue
        _, k, cur = frame
        expansions += 1
        if expansions > max_expansions:
            exhausted = True
            break  # best_val/best_set already hold the incumbent
        if cur > best_val:
            best_val = cur
            best_set = list(chosen)
        if k >= len(order):
            continue
        if bound(k, cur, caps) <= best_val:
            continue
        i = order[k]
        w = weights[i]
        # LIFO: push the exclude branch first so the include branch (and
        # its undo) run before it, exactly like the recursive include-first
        stack.append(("visit", k + 1, cur))
        if all(caps[ci] >= w - 1e-9 for ci in item_cons[i]):
            for ci in item_cons[i]:
                caps[ci] -= w
            chosen.append(i)
            stack.append(("undo", i))
            stack.append(("visit", k + 1, cur + iprof[i]))
    chosen = frozenset(best_set)
    return MKPResult(
        chosen=chosen,
        objective=sum(profits[i] for i in chosen),
        optimal=not exhausted,
        expansions=expansions,
    )




# (seed, items, constraints, style, max_expansions)
DIFF_CASES = [
    (1, 10, 4, "int", 200_000),
    (2, 12, 6, "int", 200_000),
    (3, 14, 8, "float", 200_000),
    (4, 12, 5, "float", 200_000),
    (5, 16, 10, "ties", 200_000),
    (6, 14, 7, "ties", 200_000),
    (7, 14, 6, "density", 200_000),
    (8, 12, 8, "density", 200_000),
    (9, 14, 6, "heavy", 200_000),
    (10, 12, 5, "heavy", 200_000),
    (11, 13, 9, "zero", 200_000),
    (12, 15, 7, "zero", 200_000),
    (13, 40, 25, "int", 3_000),
    (14, 40, 30, "float", 3_000),
    (15, 36, 20, "ties", 2_000),
    (16, 48, 36, "density", 4_000),
    (17, 32, 16, "heavy", 2_500),
    (18, 44, 28, "zero", 3_500),
    (19, 60, 45, "float", 5_000),
    (20, 24, 1, "int", 1_000),
]


def random_mkp(seed, n_items, n_cons, style):
    """A seeded MKP instance. Every constraint overlaps others; caps all
    start at the budget, so the tightest-constraint argmin ties at the root.

    * ``int`` — small integer weights and profits under an integer budget,
      so fractional terms often land exactly on an integer (the bound ties
      the incumbent and the exact-replay path runs);
    * ``float`` — real weights and profits, some rounding to 0 or 1;
    * ``ties`` — repeated constraints and equal weights (argmin ties that
      persist as capacities move);
    * ``density`` — profit proportional to weight (equal densities);
    * ``heavy`` — some weights above the budget;
    * ``zero`` — many zero-profit items.
    """
    rng = random.Random(seed)
    items = sorted(rng.sample(range(3 * n_items), n_items))
    budget = 10 if style == "int" else 10.0
    weights, profits = {}, {}
    for i in items:
        if style == "int":
            weights[i] = rng.randint(1, 6)
            profits[i] = rng.randint(0, 9)
        elif style == "ties":
            weights[i] = rng.choice((2.5, 5.0))
            profits[i] = float(rng.choice((3, 6)))
        elif style == "density":
            weights[i] = rng.uniform(0.5, 6.0)
            profits[i] = 2.0 * weights[i]
        elif style == "heavy":
            weights[i] = rng.uniform(1.0, 14.0)
            profits[i] = rng.uniform(0.0, 20.0)
        elif style == "zero":
            weights[i] = rng.uniform(0.5, 6.0)
            profits[i] = rng.uniform(0.0, 9.0) if rng.random() < 0.4 else 0.0
        else:
            weights[i] = rng.uniform(0.2, 7.0)
            profits[i] = rng.uniform(0.0, 12.0)
    constraints = []
    for _ in range(n_cons):
        size = rng.randint(2, max(2, n_items // 2))
        constraints.append(frozenset(rng.sample(items, size)))
    if style == "ties":
        constraints += constraints[: max(1, n_cons // 2)]
    return items, profits, weights, constraints, budget


@pytest.mark.parametrize(
    "seed,n_items,n_cons,style,max_exp", DIFF_CASES,
    ids=[f"{c[3]}-{c[1]}x{c[2]}-s{c[0]}" for c in DIFF_CASES],
)
def test_bnb_matches_reference_search(seed, n_items, n_cons, style, max_exp):
    items, profits, weights, constraints, budget = random_mkp(
        seed, n_items, n_cons, style
    )
    args = (items, profits, weights, constraints, budget, max_exp)
    got = branch_and_bound_mkp(*args)
    want = reference_branch_and_bound_mkp(*args)
    assert got.chosen == want.chosen
    assert got.objective == want.objective
    assert got.optimal == want.optimal
    assert got.expansions == want.expansions


def test_differential_cases_cover_exhausted_and_optimal_searches():
    """The differential cases hold both kinds of search: some stop at their
    expansion cap, the others prove optimality."""
    optimal = {
        branch_and_bound_mkp(*random_mkp(*c[:4]), c[4]).optimal
        for c in DIFF_CASES
    }
    assert optimal == {True, False}
