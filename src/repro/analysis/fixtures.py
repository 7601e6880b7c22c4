"""Must-fire fixtures: the two historical bugs sc-lint exists to catch.

Both patterns shipped in this repo and were fixed at runtime cost; they are
kept here as executable regression anchors. ``tools/sc_lint.py --ci`` (and
``tests/analysis/test_determinism.py``) assert that the linter FIRES on each
legacy pattern and stays QUIET on the shipped fix — if a lint rule rots,
CI fails even though the repo itself is clean.

Bug 1 — fused shape-specialized tanh (batch invariance). The original MAP
kernel evaluated ``a*1.0001 + tanh(b)`` in one jit unit: XLA contracted the
mul+add into an FMA and picked shape-dependent tanh approximations, so a
chunked delta refresh disagreed with a whole-table recompute in the low
bit. Fix: softsign instead of tanh, and the multiply as its own jit unit
(``dataplane._jk``'s ``map_mul``) with the add and softsign on the host
(XLA:TPU's float32 division is not correctly rounded).

Bug 2 — ``_filter_mask`` static threshold. The filter compare was jitted
with its float threshold in ``static_argnums``: every distinct threshold
value (one per FILTER node) triggered a full retrace. Fix: the threshold
is traced, pinned to the column dtype on the host. (The compare has since
left the device: it runs on the host in every impl.)

Forged merge — the MQO hazard ``analysis.mqo_check`` exists to catch
(DESIGN.md §11): two views whose "shared" FILTER prefix differs only in a
captured threshold, with the merge provenance tampered to claim they are
one equivalence class. ``forged_threshold_merge`` hand-builds that
``MergedWorkload``; ``genuine_shared_prefix_merge`` is the quiet
counterpart (a real ``merge_workload`` result the pass must not flag).
"""
from __future__ import annotations

import textwrap

__all__ = [
    "LEGACY_FILTER_MASK_SRC",
    "SHIPPED_FILTER_MASK_SRC",
    "forged_threshold_merge",
    "genuine_shared_prefix_merge",
    "legacy_fused_map",
    "shipped_map_kernels",
]

LEGACY_FILTER_MASK_SRC = textwrap.dedent(
    '''
    import jax
    import jax.numpy as jnp


    def _filter_mask(col, threshold):
        return jnp.asarray(col) > threshold


    # BUG: threshold is a value, not a shape — one retrace per distinct
    # FILTER threshold in the workload
    filter_mask_jit = jax.jit(_filter_mask, static_argnums=1)
    '''
)

SHIPPED_FILTER_MASK_SRC = textwrap.dedent(
    '''
    import jax
    import jax.numpy as jnp


    def _filter_mask(col, threshold):
        return jnp.asarray(col) > threshold


    filter_mask_jit = jax.jit(_filter_mask)  # threshold traced: one trace
    '''
)


def legacy_fused_map():
    """The historical MAP kernel: one jit unit, tanh + contractable mul/add.
    Trace with two same-length float32 arrays."""
    import jax
    import jax.numpy as jnp

    def _map_fused(a, b):
        return a * jnp.float32(1.0001) + jnp.tanh(b)

    return jax.jit(_map_fused)


def shipped_map_kernels():
    """The shipped fix: the map's jitted kernels (the multiply alone)."""
    from ..mv.dataplane import _jk

    return (_jk()["map_mul"],)


def forged_threshold_merge():
    """A tampered ``MergedWorkload``: two FILTERs over the same scan whose
    captured thresholds differ (node indices 1 and 2 are not congruent
    mod 7, so ``filter_threshold`` gives each a distinct value), forged to
    claim a single equivalence class. ``mqo_check.check_merged`` must emit
    ``unsound-merge`` on it."""
    import dataclasses as dc

    from ..mv import ir as mvir
    from ..mv.mqo import MergedWorkload, node_fingerprints
    from ..mv.workloads import MVNode, Workload

    wl = Workload(name="forged_prefix", nodes=[
        MVNode("scan", (), "SCAN", 1e6, 0.0, base_read=1e6),
        MVNode("a_filter", (0,), "FILTER", 7e5, 1e-4),
        MVNode("b_filter", (0,), "FILTER", 7e5, 1e-4),
        MVNode("a_view", (1,), "MAP", 7e5, 1e-4),
        MVNode("b_view", (2,), "MAP", 7e5, 1e-4),
    ])
    ir = mvir.infer_schemas(mvir.lift_workload(wl))
    fps = list(node_fingerprints(ir))

    # The forgery: claim b_filter computes what a_filter computes and
    # rewire b_view onto the "shared" representative.
    fps[2] = fps[1]
    rep_of = (0, 1, 1, 3, 4)
    keep = (0, 1, 3, 4)
    new_index = {0: 0, 1: 1, 3: 2, 4: 3}
    nodes, ir_nodes = [], []
    for orig in keep:
        n = wl.nodes[orig]
        parents = tuple(new_index[rep_of[p]] for p in n.parents)
        nodes.append(dc.replace(n, parents=parents))
        ir_nodes.append(dc.replace(ir.nodes[orig], parents=parents))
    merged_wl = Workload(name="forged_prefix_mqo", nodes=nodes)
    merged_ir = dc.replace(
        ir, nodes=tuple(ir_nodes), name=merged_wl.name
    )
    return MergedWorkload(
        source=wl,
        workload=merged_wl,
        ir=merged_ir,
        fingerprints=tuple(fps),
        rep_of=rep_of,
        keep=keep,
        name_map={
            "scan": "scan", "a_filter": "a_filter",
            "b_filter": "a_filter", "a_view": "a_view",
            "b_view": "b_view",
        },
        shared=("a_filter",),
        classes={
            "scan": (0,), "a_filter": (1, 2),
            "a_view": (3,), "b_view": (4,),
        },
    )


def genuine_shared_prefix_merge():
    """The quiet counterpart: an honest ``merge_workload`` over the
    shared-prefix MQO workload. The soundness pass must report nothing."""
    from ..mv.mqo import merge_workload, shared_prefix_workload

    return merge_workload(shared_prefix_workload(n_views=2))
