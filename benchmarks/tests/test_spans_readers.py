"""The span-tree readers (readers/spans.py) on hand-made contexts: a number
where the program's span tree is there, None where it is not (the parent
commit, a cell with no such span, a window of no rounds), never a raise. And
the ``--trace 1`` plumbing run reports the new names."""

import json

import pytest

from benchmarks import harness

NEW = ["engine.inputs_ms_per_round", "engine.enqueue_ms_per_round",
       "engine.device_wait_ms_per_round", "engine.fetch_ms_per_round",
       "engine.round_program_self_ms_per_round", "engine.dispatches_per_round",
       "ledger.fingerprint_ms_per_round"]


def reader(name):
    return harness.load_reader(harness.load_json("metrics", name + ".json")["reader"])


def span(total, count=1, **counts):
    return dict({"count": count, "total_s": total, "mean_s": total / count,
                 "p50_s": total / count, "p95_s": total / count}, **counts)


def tree(guarded):
    rp = dict(span(2.0, 8), self_s=0.004, children={
        "inputs": span(0.016, 16, h2d_bytes=512), "enqueue": span(0.024, 16, compiled=0),
        "wait": span(1.9, 16), "fetch": span(0.008, 8, d2h_bytes=96),
        "records": span(0.004, 8), "ledger": span(0.044, 8)})
    ledger = dict(span(0.044, 8), self_s=0.0, children={"chain": span(0.012, 8)})
    if guarded:
        ledger["children"]["fingerprint"] = span(0.032, 8, d2h_bytes=768)
    return {"round_program": rp, "ledger": ledger, "control_plane": span(0.002, 8)}


def test_present():
    ctx = {"phases": tree(True), "rounds": 8}
    got = {n: reader(n)(ctx) for n in NEW}
    assert got == pytest.approx({
        "engine.inputs_ms_per_round": 2.0, "engine.enqueue_ms_per_round": 3.0,
        "engine.device_wait_ms_per_round": 237.5, "engine.fetch_ms_per_round": 1.0,
        "engine.round_program_self_ms_per_round": 0.5,
        "engine.dispatches_per_round": 3.0, "ledger.fingerprint_ms_per_round": 4.0})


def test_fused_cell_has_no_fingerprint_span():
    ctx = {"phases": tree(False), "rounds": 8}
    assert reader("ledger.fingerprint_ms_per_round")(ctx) is None
    assert reader("engine.dispatches_per_round")(ctx) == 2.0


@pytest.mark.parametrize("ctx", [
    {"phases": None, "rounds": 8},
    {"phases": {}, "rounds": 8},
    # the parent commit's summary: the five fields and no children
    {"phases": {"round_program": span(2.0, 8), "ledger": span(0.04, 8)}, "rounds": 8},
    {"phases": tree(True), "rounds": 0},
], ids=["none", "empty", "parent", "zero-rounds"])
@pytest.mark.parametrize("name", NEW)
def test_absent_reads_none(name, ctx):
    assert reader(name)(ctx) is None


def test_new_entries_are_additions():
    b = harness.load_benchmark()
    names = [m["name"] for m in b["per_layer"]]
    assert names[8:8 + len(NEW)] == NEW  # after PR 24's eight; PR 27's five follow
    assert names[8 + len(NEW):] == [
        "step.forward_ms_per_round", "step.backward_ms_per_round", "step.optimizer_ms_per_round",
        "step.dropout_ms_per_round", "aggregate.device_ms_per_round"]
    by = {m["name"]: m for m in b["per_layer"]}
    assert by["ledger.fingerprint_ms_per_round"]["workloads"] == ["albert-base.guarded-s128"]
    assert all("workloads" not in by[n] for n in NEW[:-1])
    assert by["engine.dispatches_per_round"]["source"] == "program_counter"


@pytest.mark.parametrize("cell,fingerprint", [("albert-base.guarded-s128", True),
                                               ("albert-base.fedavg-s128", False)])
def test_traced_plumbing_run_reports_the_new_names(cell, fingerprint, tmp_path):
    r = harness.run_cell(cell, 2147483659, 1.0, True, plumbing=True, out_dir=str(tmp_path))
    want = set(NEW if fingerprint else NEW[:-1])
    assert want <= set(r["metrics"])
    assert ("ledger.fingerprint_ms_per_round" in r["metrics"]) == fingerprint
    assert r["metrics"]["engine.dispatches_per_round"]["value"] == (3.0 if fingerprint else 0.25)
    assert r["correct"] is True and json.dumps(r)  # the line stays one JSON object
