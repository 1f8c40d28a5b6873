"""The round's span tree (OBSERVABILITY.md, "Round spans and device
scopes"): ``StepClock`` spans with a parent, a start and counts; the
engine's spans inside ``round_program``, ``ledger`` and ``control_plane``;
the ``phase`` events' new extras; and the ``fed.*`` named scopes of the round
programs, which change an operation's metadata and nothing else."""

import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bcfl_tpu import telemetry as T
from bcfl_tpu.config import FedConfig, LedgerConfig, PartitionConfig
from bcfl_tpu.compression import CompressionConfig
from bcfl_tpu.fed import client_step
from bcfl_tpu.fed.engine import FedEngine
from bcfl_tpu.metrics import StepClock
from bcfl_tpu.reputation import ReputationConfig

FIELDS = {"count", "total_s", "mean_s", "p50_s", "p95_s"}


# ------------------------------------------------------------- (a) StepClock

def test_phase_only_summary_is_the_legacy_view():
    clock = StepClock()
    for _ in range(3):
        with clock.phase("control_plane"):
            pass
        with clock.phase("round_program"):
            pass
    with clock.phase("eval"):
        pass
    s = clock.summary()
    assert set(s) == {"control_plane", "round_program", "eval"}
    for v in s.values():
        assert set(v) == FIELDS
    assert s["round_program"]["count"] == 3 and s["eval"]["count"] == 1
    assert s["round_program"]["p50_s"] <= s["round_program"]["p95_s"]


def test_span_paths_parents_and_counts():
    clock = StepClock()
    for i in range(2):
        with clock.phase("round_program"):
            with clock.span("inputs") as counts:
                counts["h2d_bytes"] = 10 + i
            with clock.span("enqueue", program="p", compiled=0) as counts:
                counts["compiled"] = 1 - i
            with clock.phase("ledger"):
                with clock.span("chain"):
                    pass
    s = clock.summary()
    assert set(s) == {"round_program", "ledger"}
    assert FIELDS < set(s["round_program"])
    kids = s["round_program"]["children"]
    assert set(kids) == {"inputs", "enqueue", "ledger"}
    assert kids["inputs"]["count"] == 2 and kids["inputs"]["h2d_bytes"] == 21
    assert kids["enqueue"]["compiled"] == 1
    # the nested phase sits under its parent AND at the top level, the same
    assert {k: kids["ledger"][k] for k in FIELDS} == \
        {k: s["ledger"][k] for k in FIELDS}
    assert set(s["ledger"]["children"]) == {"chain"}


def test_self_time_is_total_less_children():
    clock = StepClock()
    with clock.phase("round_program"):
        with clock.span("wait"):
            pass
        with clock.span("fetch"):
            pass
    rp = clock.summary()["round_program"]
    kids = sum(c["total_s"] for c in rp["children"].values())
    assert rp["self_s"] == pytest.approx(rp["total_s"] - kids)
    assert 0 <= rp["self_s"] <= rp["total_s"]
    for c in rp["children"].values():
        assert c["total_s"] <= rp["total_s"]


def test_root_level_span_is_absent_from_summary():
    clock = StepClock()
    with clock.phase("round_program"):
        pass
    with clock.span("post_round"):
        with clock.phase("eval"):
            pass
    with clock.span("on_round"):
        pass
    s = clock.summary()
    assert set(s) == {"round_program", "eval"}
    assert set(s["eval"]) == FIELDS and set(s["round_program"]) == FIELDS


def test_span_closes_on_an_exception():
    clock = StepClock()
    with pytest.raises(RuntimeError):
        with clock.phase("round_program"):
            with clock.span("enqueue"):
                raise RuntimeError("boom")
    with clock.phase("round_program"):
        with clock.span("inputs"):
            pass
    kids = clock.summary()["round_program"]["children"]
    assert set(kids) == {"enqueue", "inputs"}  # the stack unwound


def test_phase_events_carry_parent_start_and_counts(tmp_path):
    w = T.install(T.EventWriter(str(tmp_path / "events_engine.jsonl"),
                                peer=None, run="t"))
    try:
        clock = StepClock()
        clock.round = 7
        before = time.time_ns()
        with clock.phase("round_program"):
            with clock.span("enqueue", program="collapse") as counts:
                counts["compiled"] = 1
        with clock.span("post_round"):
            pass
    finally:
        T.uninstall()
    assert w.dropped == 0
    evs = [json.loads(x) for x in open(tmp_path / "events_engine.jsonl")]
    by = {e["name"]: e for e in evs if e["ev"] == "phase"}
    assert set(by) == {"round_program", "round_program/enqueue", "post_round"}
    assert by["round_program"]["parent"] is None
    assert by["post_round"]["parent"] is None
    child = by["round_program/enqueue"]
    assert child["parent"] == "round_program" and child["round"] == 7
    assert child["program"] == "collapse" and child["compiled"] == 1
    for e in by.values():
        assert e["v"] == T.SCHEMA_VERSION == 1
        assert before <= e["t0_ns"] <= time.time_ns()
        assert e["wall_s"] >= 0
    assert child["t0_ns"] >= by["round_program"]["t0_ns"]


def test_removed_tracing_functions_stay_removed():
    import bcfl_tpu.metrics as m
    from bcfl_tpu.metrics import tracing

    assert not hasattr(tracing, "annotate") and not hasattr(m, "annotate")
    assert not hasattr(StepClock, "record")


# ------------------------------------------------- (b), (c) the engine's tree

def _tiny(**kw):
    base = dict(
        dataset="synthetic", model="tiny-bert", num_clients=4, num_rounds=4,
        seq_len=16, batch_size=4, max_local_batches=2, eval_every=0,
        mode="server", ledger=LedgerConfig(enabled=True),
        partition=PartitionConfig(kind="iid", iid_samples=8),
    )
    base.update(kw)
    return FedConfig(**base)


def _run(tmp_path_factory, name, **kw):
    # fresh jit objects, so that every program's first call in this run is
    # its first call ever (``compiled`` = 1 exactly once)
    client_step.clear_program_cache()
    tdir = str(tmp_path_factory.mktemp(name))
    res = FedEngine(_tiny(name=name, telemetry_dir=tdir, **kw)).run()
    events = [json.loads(x)
              for x in open(os.path.join(tdir, "events_engine.jsonl"))]
    return res, events, tdir


@pytest.fixture(scope="module")
def fused(tmp_path_factory):
    return _run(tmp_path_factory, "spans_fused", rounds_per_dispatch=2)


@pytest.fixture(scope="module")
def per_round(tmp_path_factory):
    return _run(tmp_path_factory, "spans_per_round",
                reputation=ReputationConfig(enabled=True))


def _check_tree(phases):
    for parent in ("round_program", "ledger"):
        for child in phases[parent]["children"].values():
            assert child["count"] > 0
            assert child["total_s"] <= phases[parent]["total_s"]
        assert phases[parent]["self_s"] >= 0
    assert FIELDS < set(phases["round_program"])


def test_fused_run_has_every_child(fused):
    res, _, _ = fused
    phases = res.metrics.phases
    assert all(r.fused for r in res.metrics.rounds)
    assert set(phases) == {"round_program", "ledger"}
    assert set(phases["round_program"]["children"]) == {
        "inputs", "enqueue", "wait", "fetch", "records", "ledger"}
    # the fused path's fingerprints come out of the round program
    assert set(phases["ledger"]["children"]) == {"chain"}
    _check_tree(phases)
    rp = phases["round_program"]["children"]
    assert rp["enqueue"]["count"] == 2  # 4 rounds, 2 a dispatch
    assert rp["inputs"]["h2d_bytes"] > 0 and rp["fetch"]["d2h_bytes"] > 0


def test_per_round_ledger_run_has_every_child(per_round):
    res, _, _ = per_round
    phases = res.metrics.phases
    assert not any(r.fused for r in res.metrics.rounds)
    assert set(phases) == {"control_plane", "round_program", "ledger"}
    assert set(phases["round_program"]["children"]) == {
        "inputs", "enqueue", "wait", "fetch", "records", "ledger"}
    assert set(phases["ledger"]["children"]) == {"fingerprint", "chain"}
    assert set(phases["control_plane"]["children"]) == {"gate", "reputation"}
    _check_tree(phases)
    rounds = len(res.metrics.rounds)
    # client_updates and collapse each round, and one fingerprint program
    assert phases["round_program"]["children"]["enqueue"]["count"] == 2 * rounds
    assert phases["ledger"]["children"]["fingerprint"]["count"] == rounds
    assert phases["ledger"]["children"]["fingerprint"]["d2h_bytes"] > 0


@pytest.mark.parametrize("which", ["fused", "per_round"])
def test_compiled_is_one_on_a_programs_first_call_only(which, request):
    _, events, _ = request.getfixturevalue(which)
    enq = [e for e in events if e["ev"] == "phase"
           and e["name"] == "round_program/enqueue"]
    first = {}
    for e in enq:
        first.setdefault(e["program"], e)
    want = ({"server_rounds_static_fp"} if which == "fused"
            else {"client_updates", "collapse"})
    assert set(first) == want
    assert all(e["compiled"] == 1 for e in first.values())
    later = [e for e in enq if e is not first[e["program"]]]
    assert later and sum(e["compiled"] for e in later) == 0


@pytest.mark.parametrize("which", ["fused", "per_round"])
def test_stream_has_parents_starts_and_root_spans(which, request):
    res, events, tdir = request.getfixturevalue(which)
    spans = [e for e in events if e["ev"] == "phase"]
    assert all("parent" in e and e["t0_ns"] > 0 for e in spans)
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert {"post_round", "round_program", "ledger",
            "round_program/inputs", "ledger/chain"} <= set(by_name)
    assert all(e["parent"] is None for e in by_name["post_round"])
    assert all(e["parent"] == "round_program" for e in by_name["ledger"])
    assert all(e["parent"] == "ledger" for e in by_name["ledger/chain"])
    # a child's name is its path, so the collator's per-name rollup keeps
    # children of different parents apart; the run still collates clean
    col = T.collate_run(tdir)
    assert col["ok"], col["violations"]
    rolled = set().union(*(d.keys() for d in col["timeline"]["phases"].values()))
    assert {"round_program", "round_program/enqueue", "ledger/chain"} <= rolled
    # root-level spans reach the stream, not the summary
    assert "post_round" not in res.metrics.phases


def test_on_round_span_wraps_the_callback(tmp_path):
    seen = []
    tdir = str(tmp_path / "tel")
    res = FedEngine(_tiny(num_rounds=2, telemetry_dir=tdir)).run(
        on_round=seen.append)
    events = [json.loads(x)
              for x in open(os.path.join(tdir, "events_engine.jsonl"))]
    calls = [e for e in events
             if e["ev"] == "phase" and e["name"] == "on_round"]
    assert len(seen) == 2 and len(calls) == 2
    assert all(e["parent"] is None for e in calls)
    assert "on_round" not in res.metrics.phases


def test_telemetry_schema_lint_accepts_the_clock():
    from bcfl_tpu.analysis.core import run_lint

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = run_lint(
        [os.path.join(root, "bcfl_tpu", "metrics", "tracing.py"),
         os.path.join(root, "bcfl_tpu", "fed", "engine.py")],
        checker_ids_filter=["telemetry-schema"], use_baseline=False)
    assert not [f for f in findings if f.failing], findings


# ------------------------------------------------------ (d) the named scopes

def _programs(**kw):
    cfg = _tiny(num_rounds=2, rounds_per_dispatch=2, **kw)
    return FedEngine(cfg)


def _fused_args(e):
    static, batches, rrngs, _ = e._chunk_inputs(0, 2)
    assert static
    rw = e.mesh.shard_round_clients(jnp.ones((2, e.C), jnp.float32))
    carry = (e.trainable0 if e._comp is None
             else (e.trainable0, e.progs.ef_init(e.trainable0)))
    return carry, e.frozen, batches, rw, rrngs, e._chunk_corrupts(0, 2)


SCOPES_FUSED = ["fed.forward", "jvp(fed.forward)", "transpose(jvp(fed.forward))",
                "fed.loss", "fed.optimizer", "fed.optimizer_init",
                "fed.aggregate", "fed.transport", "fed.fingerprint"]


@pytest.mark.parametrize("kw,extra", [
    ({}, []),
    ({"compression": CompressionConfig(kind="int8+topk")},
     ["fed.codec.encode", "fed.codec.decode"]),
    ({"lora_rank": 2}, ["fed.lora_merge"]),
], ids=["plain", "codec", "lora"])
def test_fused_program_carries_its_scopes(kw, extra):
    e = _programs(**kw)
    text = e.progs.server_rounds_static_fp.lower(
        *_fused_args(e)).as_text(debug_info=True)
    for name in SCOPES_FUSED + extra:
        assert name in text, name
    # flax's module path reaches the name stack: dropout needs no scope
    assert "Dropout_0" in text


def test_per_round_programs_carry_their_scopes():
    e = _programs()
    batches, _ = e._round_batches(0)
    rngs = e._rngs(0)
    w = e.mesh.shard_clients(jnp.ones((e.C,), jnp.float32))
    text = e.progs.client_updates.lower(
        e.trainable0, e.frozen, batches, rngs).as_text(debug_info=True)
    stacked = jax.eval_shape(e.progs.client_updates, e.trainable0,
                             e.frozen, batches, rngs)[0]
    fp_text = e.progs.fingerprint.lower(stacked).as_text(debug_info=True)
    agg_text = e.progs.collapse.lower(
        stacked, w, e.trainable0).as_text(debug_info=True)
    for name in ("jvp(fed.forward)", "transpose(jvp(fed.forward))", "fed.loss",
                 "fed.optimizer", "fed.optimizer_init"):
        assert name in text, name
    assert "fed.fingerprint" in fp_text and "fed.aggregate" in agg_text


def test_scopes_change_metadata_and_nothing_else(monkeypatch):
    """The same programs traced without the ``fed.*`` names and with them:
    the same module but for its locations, and bit-identical outputs."""
    from jax._src import source_info_util as siu

    def build():
        client_step.clear_program_cache()
        e = _programs()
        args = _fused_args(e)
        prog = e.progs.server_rounds_static_fp
        text = prog.lower(*args).as_text()
        named = prog.lower(*args).as_text(debug_info=True)
        out = jax.device_get(prog(*args))
        batches, _ = e._round_batches(0)
        upd = jax.device_get(e.progs.client_updates(
            e.trainable0, e.frozen, batches, e._rngs(0)))
        return text, named, (out, upd)

    text_on, named_on, out_on = build()
    # the un-scoped twin: jax.named_scope's context manager (already bound
    # into the decorated stages at import) made to skip the fed.* names
    enter = siu.ExtendNameStackContextManager.__enter__

    def skip_fed(self):
        if not self.name.startswith("fed."):
            return enter(self)
        self.prev = siu._source_info_context.context
        return self.prev.name_stack

    with monkeypatch.context() as m:
        m.setattr(siu.ExtendNameStackContextManager, "__enter__", skip_fed)
        text_off, named_off, out_off = build()
    client_step.clear_program_cache()
    assert "fed.forward" in named_on and "fed." not in named_off
    assert text_on == text_off
    for a, b in zip(jax.tree.leaves(out_on), jax.tree.leaves(out_off)):
        np.testing.assert_array_equal(a, b)


def test_scopes_survive_the_program_cache():
    """Two engines with the same build arguments share one ``FedPrograms``
    (``client_step``'s program cache) and a second lowering reuses the first
    one's trace: the names are part of the program, whoever asks."""
    a, b = _programs(), _programs()
    assert a.progs is b.progs
    for e in (a, b):
        text = e.progs.server_rounds_static_fp.lower(
            *_fused_args(e)).as_text(debug_info=True)
        assert "transpose(jvp(fed.forward))" in text and "fed.optimizer" in text
