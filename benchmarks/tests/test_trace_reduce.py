import json
import os

import pytest

from benchmarks import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_gaps_total():
    merged = tr.union([[0, 5], [3, 8], [10, 12], [12, 12]])
    assert merged == [[0, 8], [10, 12]]
    assert tr.total(merged) == 10
    assert tr.gaps(merged) == [[8, 10]]


def test_exposed_collective_overlapping_and_disjoint():
    ops = [["fusion.1", 0, 10], ["all-reduce.3", 5, 10], ["fusion.2", 20, 5],
           ["all-gather.1", 30, 4]]
    d = tr.reduce_device(ops)
    assert d["busy_s"] == pytest.approx(24e-9)          # [0,15] [20,25] [30,34]
    assert d["collective_s"] == pytest.approx(14e-9)    # [5,15] and [30,34]
    assert d["collective_exposed_s"] == pytest.approx(9e-9)  # [10,15] and [30,34]
    hidden = tr.reduce_device([["fusion", 0, 10], ["reduce-scatter", 2, 3]])
    assert hidden["collective_exposed_s"] == 0.0 and hidden["collective_s"] == pytest.approx(3e-9)
    none = tr.reduce_device([["fusion", 0, 10]])
    assert none["collective_s"] == 0.0


def test_op_totals_and_gap_names():
    ops = [["a", 0, 5], ["b", 5, 1], ["a", 10, 5]]
    assert tr.op_totals(ops, top=1) == [["a", 10e-9]]
    idle = tr.gaps(tr.reduce_device(ops)["busy"])
    spans = [["round_program dispatch", 0, 20, 3], ["ledger", 6, 10, 0]]
    assert tr.name_gaps(idle, spans) == [["ledger", 4e-9]]
    assert tr.name_gaps(idle, []) == [["unattributed", 4e-9]]


def test_recorded_trace():
    """A trace recorded on the chip (TPU v5 lite, tiny-bert, two fused
    dispatches), kept as the interval lists that load_xplane gives."""
    path = os.path.join(HERE, "data", "recorded_trace.json")
    rec = json.load(open(path))
    (name, ops), = list(rec["devices"].items())[:1]
    assert name.startswith(tr.DEVICE_PREFIX)
    d = tr.reduce_device(ops)
    assert d["busy_s"] == pytest.approx(rec["expected"]["busy_s"], rel=1e-9)
    span = (max(s + x for _, s, x in ops) - min(s for _, s, _ in ops)) / 1e9
    assert 0 < d["busy_s"] <= span
    assert tr.total(tr.gaps(d["busy"])) / 1e9 == pytest.approx(span - d["busy_s"], rel=1e-6)
    assert tr.op_totals(ops)[0][0] == rec["expected"]["top_op"]
