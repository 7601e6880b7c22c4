"""The benchmark's traffic source yields ``realize_workload``'s rows."""
import numpy as np
import pytest

from bench import traffic
from repro.mv import UpdateSpec, generate_workload, realize_workload
from repro.mv import tableops as T

SEED = 4_000_000_007  # above 2**31, as the benchmark's seeds are


def _sources(n_bytes, skew, spec):
    wl = realize_workload(generate_workload(8, seed=3),
                          bytes_per_root=n_bytes, seed=SEED, key_skew=skew)
    scans = [i for i, n in enumerate(wl.nodes) if not n.parents]
    config = {"n_cols": 4, "bytes_per_root": n_bytes, "key_skew": skew}
    tfc = {"ingest_frac": spec.ingest_frac, "update_frac": spec.update_frac,
           "delete_frac": spec.delete_frac}
    return wl, traffic.make_sources(scans, config, tfc, SEED,
                                    traffic.Window(), key_order_seed=SEED)


def _same(a, b):
    assert list(a) == list(b)
    for k in a:
        assert a[k].dtype == b[k].dtype and a[k].tobytes() == b[k].tobytes()


@pytest.mark.parametrize("skew", [0.0, 0.99])
@pytest.mark.parametrize("fracs", [(0.001, 0.0, 0.001), (0.01, 0.005, 0.005),
                                   (0.02, 0.0, 0.0)])
def test_source_reproduces_realize_workload(skew, fracs):
    spec = UpdateSpec(ingest_frac=fracs[0], update_frac=fracs[1],
                      delete_frac=fracs[2], n_rounds=4)
    wl, sources = _sources(1 << 15, skew, spec)
    live = {}
    for i, src in sources.items():
        node = wl.nodes[i]
        _same(src.fn([]), node.fn([]))
        _same(src.delta_fn(0, spec), node.delta_fn(0, spec))
        live[i] = node.delta_fn(0, spec)
    for r in range(1, 5):
        for i, src in sources.items():
            want = wl.nodes[i].delta_fn(r, spec)
            _same(src.delta_fn(r, spec), want)
            _same(src.delta_fn(r, spec), want)  # a repeated request
            live[i] = T.apply_delta(live[i], want)
    for i, src in sources.items():
        _same(src.final(), live[i])


def test_source_refuses_rounds_out_of_order():
    spec = UpdateSpec(ingest_frac=0.01, n_rounds=3)
    _, sources = _sources(1 << 12, 0.0, spec)
    src = next(iter(sources.values()))
    with pytest.raises(RuntimeError):
        src.delta_fn(2, spec)


def test_window_stops_only_after_the_deadline():
    w = traffic.Window(first_window_round=2)
    ends = {}
    w.round_end = ends.get
    seen = []
    w.listeners.append(seen.append)
    for r in (1, 2):
        w.on_request(r)
        ends[r] = 10.0 * r
    w.deadline = 25.0
    w.on_request(3)  # starts at 20.0, before the deadline
    ends[3] = 30.0
    with pytest.raises(traffic.Stop):
        w.on_request(4)  # would start at 30.0
    assert w.served == 3 and seen == [1, 2, 3]
    w.on_request(3)  # a later request of a served round is a no-op
    assert np.isfinite(ends[3])
