"""What decides ``correct``: the reference agrees with the program at a
tiny size, and the control and each fault a cell can have fail it."""
import numpy as np
import pytest

from bench import run
from bench.check import control_reading

TINY = {"bytes_per_root": 1 << 16, "n_partitions": 2}
QUIET = {"log": lambda *_: None, "on_chip": False}


@pytest.mark.parametrize("cell", ["io3.nfs.rf", "compute2.local.rf"])
def test_reference_agrees_with_the_program(cell, tmp_path):
    res = run.run_cell(cell, 3_141_592_653, 1.0, False, overrides=TINY,
                       work_dir=tmp_path, **QUIET)
    assert res["correct"], res
    assert res["checks"]["bad_entries"] == {"value": 0, "limit": 0}
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("cell", ["io3.nfs.rf", "compute2.local.rf"])
def test_bfloat16_control_fails_the_comparison(cell, tmp_path):
    d = run.drive(cell, 1_618_033_988, 0.5, False, overrides=TINY,
                  work_dir=tmp_path, **QUIET)
    cmp = control_reading(d)
    assert cmp["bad"] >= cmp["total"] // 2 > 0


def _state_unchanged(monkeypatch):
    from repro.mv import incremental

    real = incremental.IncrementalEngine._skip_node

    def skip(self, v, resume):  # refresh rounds leave every MV as it was
        return self.round_idx > run.WARM_ROUND or real(self, v, resume)

    monkeypatch.setattr(incremental.IncrementalEngine, "_skip_node", skip)


def _half_batch(monkeypatch):
    from repro.mv import partition

    real = partition._ScanRouter.delta

    def delta(self, round_idx, spec):  # every other delta row is dropped
        return [{k: v[::2] for k, v in t.items()}
                for t in real(self, round_idx, spec)]

    monkeypatch.setattr(partition._ScanRouter, "delta", delta)


def _answer_altered(monkeypatch):
    from repro.mv import tableops

    real = tableops.op_map

    def op_map(table):  # the first derived value is one ulp off
        out = real(table)
        if "derived" in out and len(out["derived"]):
            d = np.array(out["derived"])
            d[0] = np.nextafter(d[0], np.float32(np.inf))
            out["derived"] = d
        return out

    monkeypatch.setattr(tableops, "op_map", op_map)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
@pytest.mark.parametrize("cell", ["io3.nfs.rf", "compute2.local.rf"])
def test_fault_makes_the_run_incorrect(cell, fault, tmp_path, monkeypatch):
    fault(monkeypatch)
    res = run.run_cell(cell, 1_414_213_562, 1.0, False, overrides=TINY,
                       work_dir=tmp_path, **QUIET)
    assert not res["correct"]
    assert res["checks"]["bad_entries"]["value"] > 0
    assert res["failed"] == 0
