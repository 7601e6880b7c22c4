"""The ``io1.local.rf`` cell on the CPU at a tiny size: io1's UNION holds
rows of two inputs under one rid, and the reference still agrees with the
program after many rounds; the control and each fault fail it; and the
``union_s`` reader of the regroup's spans."""
import pytest

from bench import run
from bench.check import control_reading
from bench.observe import Observed, load_reader
from tests.bench.test_bench_checks import (
    QUIET,
    TINY,
    _answer_altered,
    _half_batch,
    _state_unchanged,
)

CELL = "io1.local.rf"


@pytest.mark.parametrize("seed", [11, 22, 33])
def test_reference_agrees_with_the_program_on_io1(seed, tmp_path):
    res = run.run_cell(CELL, seed, 8.0, False, overrides=TINY,
                       work_dir=tmp_path, **QUIET)
    assert res["checks"]["bad_entries"] == {"value": 0, "limit": 0}
    assert res["correct"], res
    assert res["attempted"] >= 10 and res["failed"] == 0


def test_bfloat16_control_fails_io1(tmp_path):
    d = run.drive(CELL, 2_718_281_828, 0.5, False, overrides=TINY,
                  work_dir=tmp_path, **QUIET)
    cmp = control_reading(d)
    assert cmp["bad"] >= cmp["total"] // 2 > 0


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_fault_makes_io1_incorrect(fault, tmp_path, monkeypatch):
    fault(monkeypatch)
    res = run.run_cell(CELL, 1_732_050_807, 1.0, False, overrides=TINY,
                       work_dir=tmp_path, **QUIET)
    assert not res["correct"]
    assert res["checks"]["bad_entries"]["value"] > 0
    assert res["failed"] == 0


# two window rounds, two workers: UNION partitions regrouped on both
# workers in round 2, one in round 3, each inside its compute span
SPANS = [
    ("round", 2, 10.0, 3.0, "main"), ("round", 3, 14.0, 4.0, "main"),
    ("compute", 2, 10.0, 1.0, "w0"), ("union.splice", 2, 10.0, 0.5, "w0"),
    ("compute", 2, 10.5, 1.0, "w1"), ("union.splice", 2, 10.5, 0.25, "w1"),
    ("compute", 3, 14.0, 1.0, "w1"), ("union.splice", 3, 14.5, 0.25, "w1"),
]


def _obs(spans, rounds=None):
    return Observed(
        rounds={2: (10.0, 14.0), 3: (14.0, 18.0)} if rounds is None
        else rounds, spans=spans, counters={}, n_workers=2,
        window=(10.0, 18.0))


def test_union_s_reads_the_splice_spans():
    assert load_reader("union_s")(_obs(SPANS)) == pytest.approx(1.0 / 2)


def test_union_s_reads_nothing_without_splice_spans():
    read = load_reader("union_s")
    # a program without the span (or a graph without a UNION that shares
    # rids), and a window without rounds
    assert read(_obs([s for s in SPANS if s[0] != "union.splice"])) is None
    assert read(_obs([])) is None
    assert read(_obs(SPANS, rounds={})) is None
