"""The set-up readers (readers/setup.py) on hand-made event streams: the
sums where the engine's stream has its construction's ``setup`` spans, None
where it has none (the parent commit) or there is no stream, never a raise.
And a ``--trace 1`` plumbing run reports all six."""

import json
import os

import pytest

from benchmarks import harness

NEW = ["setup.engine_s", "setup.data_s", "setup.model_init_s", "setup.lower_s",
       "setup.compile_s", "setup.cache_misses"]
UNITS = {"setup.engine_s": "s", "setup.data_s": "s", "setup.model_init_s": "s",
         "setup.lower_s": "s", "setup.compile_s": "s", "setup.cache_misses": "programs"}


def reader(name):
    return harness.load_reader(harness.load_json("metrics", name + ".json")["reader"])


def phase(name, wall_s, parent=None, **counts):
    return dict({"ev": "phase", "name": name, "wall_s": wall_s, "parent": parent,
                 "t0_ns": 1}, **counts)


SETUP = [
    phase("setup/data", 1.5, "setup"),
    phase("setup/model_init", 6.0, "setup", trace_us=200_000, lower_us=800_000,
          compile_us=2_000_000),
    phase("setup/remat_saved", 0.5, "setup", trace_us=400_000),
    phase("setup/eval_batches", 0.25, "setup"),
    phase("setup", 9.0, trace_us=10_000, lower_us=20_000, compile_us=30_000, cache_misses=2),
]
ROUNDS = [
    phase("round_program/inputs", 0.1, "round_program", trace_us=5_000, lower_us=6_000,
          compile_us=7_000, cache_misses=1),
    phase("round_program/enqueue", 12.0, "round_program", trace_us=3_000_000,
          lower_us=4_000_000, compile_us=5_000_000, cache_misses=1, compiled=1),
    phase("round_program/enqueue", 0.001, "round_program", compiled=0),
    phase("round_program", 14.0),
]


def ctx_for(tmp_path, events, torn=False):
    tele = tmp_path / "telemetry"
    tele.mkdir()
    lines = [json.dumps(e) for e in [{"ev": "run.start", "role": "engine"}] + events]
    text = "\n".join(lines) + "\n" + ('{"ev": "phase", "na' if torn else "")
    (tele / "events_engine.jsonl").write_text(text)
    return {"trace_dir": str(tmp_path / "trace")}


@pytest.mark.parametrize("torn", [False, True], ids=["whole", "torn-tail"])
def test_sums_over_the_stream(tmp_path, torn):
    ctx = ctx_for(tmp_path, SETUP + ROUNDS, torn)
    got = {n: reader(n)(ctx) for n in NEW}
    assert got == pytest.approx({
        "setup.engine_s": 9.0, "setup.data_s": 1.75, "setup.model_init_s": 6.0,
        "setup.lower_s": 0.2 + 0.8 + 0.4 + 0.01 + 0.02 + 0.005 + 0.006 + 3.0 + 4.0,
        "setup.compile_s": 2.0 + 0.03 + 0.007 + 5.0,
        "setup.cache_misses": 4})


def test_a_warm_run_reads_no_miss(tmp_path):
    ctx = ctx_for(tmp_path, SETUP[:-1] + [phase("setup", 9.0)] + ROUNDS[2:])
    assert reader("setup.cache_misses")(ctx) == 0
    assert reader("setup.compile_s")(ctx) == pytest.approx(2.0)


@pytest.mark.parametrize("events", [None, [], ROUNDS], ids=["no-stream", "empty", "parent"])
def test_none_without_setup_events(tmp_path, events):
    if events is None:
        ctx = {"trace_dir": str(tmp_path / "trace")}
    else:
        ctx = ctx_for(tmp_path, events)
    assert all(reader(n)(ctx) is None for n in NEW)


def test_entries_layer_units_and_reach():
    b = harness.load_benchmark()
    by = {m["name"]: m for m in b["per_layer"]}
    for n in NEW:
        m = by[n]
        assert m["layer"] == "set-up" and m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["unit"] == UNITS[n] and "workloads" not in m
    assert [m["name"] for m in b["per_layer"]][-len(NEW):] == NEW


def test_traced_plumbing_run_reports_them(tmp_path):
    r = harness.run_cell("albert-base.fedavg-s128", 2147483659, 1.0, True, plumbing=True,
                         out_dir=str(tmp_path))
    assert set(NEW) <= set(r["metrics"])
    for n in NEW:
        assert r["metrics"][n]["unit"] == UNITS[n] and r["metrics"][n]["value"] >= 0
    assert r["metrics"]["setup.engine_s"]["value"] >= r["metrics"]["setup.model_init_s"]["value"]
    assert r["correct"] is True and json.dumps(r)
    assert os.path.exists(os.path.join(str(tmp_path), "telemetry", "events_engine.jsonl"))
