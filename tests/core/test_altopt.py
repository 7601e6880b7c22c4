"""Algorithm 2 (alternating optimization): convergence, feasibility, quality."""
import functools

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import repro.core.mkp as mkp
from repro.core import (
    CostModel,
    MVGraph,
    PAPER_COST_MODEL,
    score_graph,
    serial_plan,
    simplified_mkp,
    solve,
)
from repro.core.speedup import EFFECTIVE_NFS_COST_MODEL
from repro.mv import PAPER_WORKLOAD_SPECS, UpdateSpec, paper_workloads
from repro.mv.incremental import round_view
from repro.mv.partition import partition_workload
from test_mkp import reference_branch_and_bound_mkp


def random_dag(draw, max_n=12):
    n = draw(st.integers(2, max_n))
    edges = []
    for j in range(1, n):
        for i in range(j):
            if draw(st.booleans()) and draw(st.booleans()):
                edges.append((i, j))
    sizes = tuple(float(draw(st.integers(1, 30))) for _ in range(n))
    scores = tuple(float(draw(st.integers(0, 30))) for _ in range(n))
    return MVGraph(n, tuple(edges), sizes, scores)


def fig7_style_reordered():
    """Indexed so the initial Kahn order is the *bad* order: alternation must
    discover the order in which both 100GB nodes can be flagged (score 210)."""
    # 0:A(100)  1:C(100)  2:B(child of A)  3:D(child of C)  4:E(leaf)
    sizes = (100.0, 100.0, 5.0, 5.0, 10.0)
    return MVGraph(5, ((0, 2), (1, 3)), sizes, sizes)


def test_alternation_escapes_bad_initial_order():
    g = fig7_style_reordered()
    init = g.topological_order()
    assert init == [0, 1, 2, 3, 4]  # the bad interleaving
    u0 = simplified_mkp(g, 100.0, init)
    assert g.total_score(u0) == pytest.approx(115.0)  # one big + D + E
    plan = solve(g, budget=100.0)
    assert plan.score == pytest.approx(210.0)
    assert {0, 1} <= set(plan.flagged)
    assert plan.iterations >= 2
    assert g.is_feasible(plan.flagged, plan.order, 100.0)


def test_serial_plan_is_trivial():
    g = fig7_style_reordered()
    p = serial_plan(g)
    assert p.flagged == frozenset()
    assert p.score == 0.0
    assert g.is_topological(list(p.order))


def test_zero_budget_flags_nothing_expensive():
    g = fig7_style_reordered()
    plan = solve(g, budget=0.0)
    assert all(g.sizes[i] == 0 for i in plan.flagged)


# the solver-pairing graphs: fig7 at budget 100, and each Table III graph at
# 1.6% of its bytes (the paper's Memory Catalog share, the cells' budget)
PAIRING_GRAPHS = ("fig7", *(name for name, *_ in PAPER_WORKLOAD_SPECS))


@functools.lru_cache(maxsize=None)
def pairing_graph(name):
    """(graph, budget) for a solver-pairing case, built once per module."""
    if name == "fig7":
        return fig7_style_reordered(), 100.0
    wi = [n for n, *_ in PAPER_WORKLOAD_SPECS].index(name)
    g = paper_workloads(100.0)[wi].to_graph(CostModel())
    return g, 0.016 * sum(g.sizes)


@pytest.mark.parametrize("os_", ["madfs", "random_dfs", "sa", "separator"])
@pytest.mark.parametrize("ns", ["mkp", "greedy", "random", "ratio"])
@pytest.mark.parametrize("graph", PAIRING_GRAPHS)
def test_all_node_and_order_solvers_run(graph, ns, os_):
    g, budget = pairing_graph(graph)
    plan = solve(g, budget=budget, node_solver=ns, order_solver=os_)
    assert g.is_feasible(plan.flagged, plan.order, budget)
    assert g.is_topological(list(plan.order))
    again = solve(g, budget=budget, node_solver=ns, order_solver=os_)
    assert again.flagged == plan.flagged and again.order == plan.order


def test_mkp_madfs_scores_at_least_each_baseline():
    # MKP+MA-DFS is the paper's choice and must be at least as good here
    g = fig7_style_reordered()
    best = solve(g, budget=100.0).score
    for ns in ("greedy", "random", "ratio"):
        assert best >= solve(g, budget=100.0, node_solver=ns).score - 1e-9


@settings(max_examples=50, deadline=None)
@given(st.data())
def test_plan_always_feasible_and_improves_on_first_iteration(data):
    g = random_dag(data.draw)
    budget = float(data.draw(st.integers(0, 60)))
    plan = solve(g, budget=budget)
    # feasibility invariant (the paper's hard constraint)
    assert g.is_feasible(plan.flagged, plan.order, budget)
    assert g.is_topological(list(plan.order))
    # alternation can only improve on the first MKP pass
    first = g.total_score(simplified_mkp(g, budget, g.topological_order()))
    assert plan.score >= first - 1e-9


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_scores_from_cost_model_are_consistent(data):
    g = random_dag(data.draw, max_n=8)
    scored = score_graph(g.n, g.edges, g.sizes, PAPER_COST_MODEL)
    # childless nodes still get the write-overlap term
    for i in range(scored.n):
        assert scored.scores[i] >= 0.0
        if scored.sizes[i] > 0:
            assert scored.scores[i] > 0.0
    plan = solve(scored, budget=sum(scored.sizes) / 2)
    assert scored.is_feasible(plan.flagged, plan.order, sum(scored.sizes) / 2)


# ---------------------------------------------------------------------------
# the benchmark cells' planner instances: same plans with the fast bound
# ---------------------------------------------------------------------------

# io3 on the modeled NFS tier, compute2 on local disk; P=8, k=4 workers, a
# catalog of 1.6% of the dataset, 0.1% insert+delete refresh rounds.
CELL_STORES = {"io3": EFFECTIVE_NFS_COST_MODEL, "compute2": CostModel()}


def cell_instance(name, round_idx):
    """The flat planner instance of a benchmark cell, built from the
    workload's modeled sizes (no data): the P=8 expansion of
    ``paper_workloads(100.0)``'s ``name``, scored for round ``round_idx``
    as the scenario scores it (round 0 the build, later rounds the
    refresh view)."""
    cost_model = CELL_STORES[name]
    wl = next(w for w in paper_workloads(100.0)
              if w.name.split("@")[0] == name)
    pwl, _ = partition_workload(wl, 8)
    spec = UpdateSpec(mode="incremental", ingest_frac=0.001,
                      update_frac=0.0, delete_frac=0.001, n_rounds=1)
    view, _, _ = round_view(pwl, spec, cost_model, round_idx)
    budget = 0.016 * sum(n.size for n in pwl.nodes)
    return view.to_graph(cost_model), budget


@pytest.mark.parametrize("round_idx", [0, 1], ids=["build", "refresh"])
@pytest.mark.parametrize("name", sorted(CELL_STORES))
def test_cell_plan_same_with_reference_bnb(monkeypatch, name, round_idx):
    g, budget = cell_instance(name, round_idx)
    capped = []

    def solve_with(bnb):
        def recorded(*args, **kwargs):
            res = bnb(*args, **kwargs)
            capped.append(not res.optimal)
            return res

        monkeypatch.setattr(mkp, "branch_and_bound_mkp", recorded)
        return solve(g, budget, n_workers=4,
                     node_kwargs={"max_expansions": 20_000})

    got = solve_with(mkp.branch_and_bound_mkp)
    want = solve_with(reference_branch_and_bound_mkp)
    assert any(capped), "the instance no longer reaches the expansion cap"
    assert got.order == want.order
    assert got.flagged == want.flagged
    assert got.score == want.score
