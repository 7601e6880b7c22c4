"""The window ends at a round boundary: the store then holds exactly the
rounds the traffic source served, and the reference agrees with it."""
import numpy as np

from bench import run, traffic
from bench.reference import Reference, compare
from repro.mv import DiskStore

TINY = {"bytes_per_root": 1 << 16, "n_partitions": 2}


def test_window_stops_at_a_round_boundary(tmp_path):
    d = run.drive("io3.nfs.rf", 2_718_281_828, 1.0, False, on_chip=False,
                  overrides=TINY, work_dir=tmp_path, log=lambda *_: None)
    last = d.window.served
    assert not d.failed and last >= run.WARM_ROUND + 2
    assert max(c.round for c in d.store.commits) == last
    assert {c.round for c in d.store.commits} == set(range(last + 1))
    # every scan holds the rows inserted at the last round served, none of
    # the next round's, and its partitions agree with the source
    reader = DiskStore(tmp_path / "store")
    for i, src in d.sources.items():
        name = d.config["nodes"][i]["name"]
        rid = np.concatenate([reader.read(f"{name}@p{p}")["rid"]
                              for p in range(2)])
        assert (rid >= traffic.rid_base(last, i)).any()
        assert not (rid >= traffic.rid_base(last + 1, 0)).any()
        assert np.array_equal(np.sort(rid), src.final()["rid"])
    expected = Reference(d.config).evaluate(
        {i: s.final() for i, s in d.sources.items()})
    cmp = compare(expected, lambda mv, p: reader.read(f"{mv}@p{p}"),
                  [n["name"] for n in d.config["nodes"]], 2)
    assert cmp["bad"] == 0 and cmp["total"] == 2 * len(d.config["nodes"])


def test_round_that_fails_is_counted(tmp_path, monkeypatch):
    from repro.mv import incremental

    real = incremental.IncrementalEngine.configure_round

    def configure_round(self, round_idx, *a, **kw):
        if round_idx == run.WARM_ROUND + 2:
            raise OSError("disk lost")
        return real(self, round_idx, *a, **kw)

    monkeypatch.setattr(incremental.IncrementalEngine, "configure_round",
                        configure_round)
    res = run.run_cell("io3.nfs.rf", 5, 30.0, False, on_chip=False,
                       overrides=TINY, work_dir=tmp_path,
                       log=lambda *_: None)
    assert res["failed"] == 1 and not res["correct"]
    assert res["attempted"] == 2  # round 2 whole, round 3 failed
