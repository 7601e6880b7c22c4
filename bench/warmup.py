"""Set-up: the device programs the cell's refresh rounds will ask for.

The data plane's jitted kernels compile once per power-of-two size bucket
(per pair of buckets for the probe). The build (round 0) asks for the
largest buckets; refresh rounds ask for the small ones their deltas need,
and meet new ones as deltas and old sides vary. So at the start of the
warm round, set-up runs each kernel that the build compiled once at every
bucket from 8 rows up to the largest the build asked of it, the probe at
every index size the build used, and the weighted encode (deltas carry
weights, the build does not) over the unweighted one's buckets. Nothing
is run at a size the build did not reach, or at an index size it did not
use.
"""
from __future__ import annotations

import numpy as np

# jitted function (as JAX logs it) -> the data-plane call that runs it
_PID, _MAP, _ENC, _ENC_W, _PROBE = ("jit(_pid)", "jit(_map_mul)",
                                    "jit(_encode)", "jit(_encode_w)",
                                    "jit(_probe)")


def buckets(n: int) -> list[int]:
    """Powers of two from 8 up to the first one that holds ``n``."""
    out = [8]
    while out[-1] < n:
        out.append(out[-1] * 2)
    return out


def plan(programs) -> dict[str, set]:
    """``{function: {size, ...}}`` (``{(index, rows), ...}`` for the
    probe) to run, from the ``(function, ((dtype, shape), ...))`` programs
    the build compiled."""
    top: dict[str, int] = {}
    index_sizes: set[int] = set()
    for fn, avals in programs:
        if fn == _PROBE:
            index_sizes.add(avals[0][1][0])
            rows = avals[1][1][0]
        elif fn in (_PID, _MAP, _ENC, _ENC_W):
            rows = avals[0][1][0]
        else:
            continue
        top[fn] = max(top.get(fn, 0), rows)
    if _ENC in top:
        top[_ENC_W] = max(top.get(_ENC_W, 0), top[_ENC])
    out: dict[str, set] = {fn: set(buckets(n)) for fn, n in top.items()
                           if fn != _PROBE}
    if _PROBE in top:
        out[_PROBE] = {(k, n) for k in index_sizes
                       for n in buckets(top[_PROBE])}
    return out


def warm_data_plane(programs, n_partitions: int) -> int:
    """Run what ``plan(programs)`` lists through the data plane's public
    functions; returns the number of calls made."""
    from repro.mv import dataplane as dp

    calls = 0
    for fn, sizes in plan(programs).items():
        for s in sorted(sizes):
            if fn == _PROBE:
                k, n = s
                dp.probe_sorted(np.arange(k, dtype=np.int64),
                                np.arange(n, dtype=np.int64))
            elif fn == _PID:
                dp.partition_ids(np.arange(s, dtype=np.int64), n_partitions)
            elif fn == _MAP:
                v = np.ones(s, np.float32)
                dp.map_derived(v, v)
            elif fn == _ENC:
                dp.fixed_point_encode(np.ones(s, np.float32))
            else:
                dp.fixed_point_encode(np.ones(s, np.float32),
                                      np.ones(s, np.int64))
            calls += 1
    return calls
