"""scope_split's arithmetic on hand-made events: an operation's scope from
its ``op_name``, the host spans flattened to the innermost one at every
instant, and idle gaps named by them."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness, scope_split as ss

STACK = "jit(body)/while/body/closed_call/vmap()/while/body/closed_call/"


@pytest.mark.parametrize("op_name,scope", [
    (STACK + "jvp(fed.forward)/TextClassifier/encoder/layer_0/attention/dot_general:", "forward"),
    (STACK + "transpose(jvp(fed.forward))/TextClassifier/pooler/transpose:", "backward"),
    ("jit(f)/vmap(transpose(jvp(fed.forward)))/TextClassifier/pooler/mul:", "backward"),
    (STACK + "jvp(fed.forward)/TextClassifier/Dropout_0/jit(_bernoulli)/jit(_uniform)/add:",
     "dropout_forward"),
    (STACK + "transpose(jvp(fed.forward))/TextClassifier/Dropout_0/select_n:", "dropout_backward"),
    ("jit(eval_one)/fed.forward/TextClassifier/embeddings/gather:", "forward"),
    (STACK + "jvp(fed.loss)/log_softmax:", "loss"),
    (STACK + "transpose(jvp(fed.loss))/mul:", "loss"),
    (STACK + "fed.optimizer/add:", "optimizer"),
    ("jit(body)/vmap(fed.optimizer_init)/broadcast_in_dim:", "optimizer"),
    ("jit(body)/while/body/closed_call/fed.aggregate/reduce_sum:", "aggregate"),
    ("jit(body)/fed.aggregate/fed.aggregate/div:", "aggregate"),
    ("jit(body)/while/body/closed_call/fed.fingerprint/dot_general:", "fingerprint"),
    ("jit(body)/while/body/closed_call/fed.fingerprint/fed.transport/add:", "transport"),
    ("jit(_enc)/fed.codec.encode/top_k:", "codec"),
    ("jit(_enc)/fed.codec.encode/fed.codec.decode/scatter-add:", "codec"),
    ("jit(f)/jvp(fed.forward)/fed.lora_merge/dot_general:", "lora_merge"),
    ("jit(body)/while/body/dynamic_slice:", "unscoped"),
    ("jit(_threefry_split)/slice:", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_classify(op_name, scope):
    assert ss.classify(op_name) == scope


def test_scope_totals_shares():
    ops = [[("%fusion.1 = f32[]", STACK + "jvp(fed.forward)/x:"), 0, 60.0],
           [("%fusion.2 = f32[]", STACK + "fed.optimizer/add:"), 60, 30.0],
           [("%copy.3 = f32[]", ""), 90, 10.0]]
    t = ss.scope_totals(ops)
    assert t["forward"]["share_pct"] == pytest.approx(60.0)
    assert t["optimizer"]["device_s"] == pytest.approx(30e-9)
    assert t["unscoped"]["share_pct"] == pytest.approx(10.0)
    assert t["forward"]["top_ops"] == [["fusion", pytest.approx(60e-9)]]
    assert t["unscoped"]["top_ops"] == [["copy", pytest.approx(10e-9)]]
    assert "backward" not in t
    assert ss.scope_totals([])["unscoped"]["share_pct"] == 0.0


HOST = [["fed.round_program", 0.0, 100.0], ["fed.round_program/inputs", 5.0, 20.0],
        ["fed.round_program/enqueue", 20.0, 30.0], ["fed.ledger", 60.0, 95.0],
        ["fed.ledger/chain", 62.0, 94.0], ["bench.mark#1", 1.0, 1.0],
        ["fed.post_round", 101.0, 110.0]]


def test_innermost_pieces_do_not_overlap_and_keep_the_latest_span():
    pieces = ss.innermost(HOST)
    assert [p[2] for p in pieces] == [
        "round_program", "round_program/inputs", "round_program/enqueue", "round_program",
        "ledger", "ledger/chain", "ledger", "round_program", "post_round"]
    assert all(a[1] <= b[0] for a, b in zip(pieces, pieces[1:]))
    assert sum(p[1] - p[0] for p in pieces) == pytest.approx(109.0)
    assert ss.innermost([]) == []


def test_gaps_are_named_by_the_innermost_span():
    fwd = STACK + "jvp(fed.forward)/x:"
    # busy [0,6] [18,28] [70,72] [120,121]; one program runs over [17,29]
    ops = [[("%a.1 = f32[]", fwd), 0, 6.0], [("%b.2 = f32[]", fwd), 18, 4.0],
           [("%while.9 = f32[]", ""), 17.5, 11.0],
           [("%c.3 = f32[]", ""), 23, 5.0], [("%d.4 = f32[]", fwd), 70, 2.0],
           [("%e.5 = f32[]", ""), 120, 1.0]]
    from benchmarks import trace_reduce as tr

    by_span, longest = ss.idle(tr.leaves(ops), [[17.0, 29.0]], HOST, top=3)
    assert [g["host_spans"][0][0] for g in longest] == [
        "ledger/chain", "round_program", "round_program/inputs"]
    # 72..120: the chain's 72..94 is the largest piece of it; the ledger's own 94..95 is
    # under a twentieth of the gap and is left out
    assert longest[0]["host_spans"] == [
        ["ledger/chain", round(22 / 48, 3)], ["unattributed", round(11 / 48, 3)],
        ["post_round", round(9 / 48, 3)], ["round_program", round(5 / 48, 3)]]
    assert longest[2]["host_spans"] == [["round_program/inputs", 1.0]]
    # 28..70: enqueue to 30, the phase's own time 30..60, the ledger's own 60..62, its chain
    mid = longest[1]
    assert mid["ms"] == pytest.approx(42e-6) and mid["kind"] == "between_dispatches"
    assert mid["op_before"] == {"op": "c", "scope": "unscoped"}
    assert mid["op_after"] == {"op": "d", "scope": "forward"}
    assert by_span["round_program"]["between_dispatches"] == pytest.approx(30e-6 + 5e-6)
    assert by_span["ledger/chain"]["between_dispatches"] == pytest.approx(8e-6 + 22e-6)
    # 22..23 lies inside the one program's execution
    assert by_span["round_program/enqueue"] == pytest.approx(
        {"in_program": 1e-6, "between_dispatches": 2e-6})
    # 100..101 and 110..120: no span is open
    assert by_span["unattributed"]["between_dispatches"] == pytest.approx(1e-6 + 10e-6)
    total = sum(v["in_program"] + v["between_dispatches"] for v in by_span.values())
    assert total == pytest.approx((12 + 1 + 42 + 48) * 1e-6)


def test_rehearsal_on_the_cpu_runs_to_its_end_and_finds_no_device(tmp_path):
    """The tool end to end at tiny size: the CPU's trace has no device
    plane, so there is nothing to split."""
    cell = harness.load_benchmark()["workloads"][0]["name"]
    r = ss.run(cell, 2147483659, 1.0, plumbing=True, out_dir=str(tmp_path))
    assert r["plumbing_only"] is True and r["split"] is None and r["bracket_rounds"] > 0
    assert json.load(open(tmp_path / "scope_split-2147483659.json")) == r


def test_no_accelerator_means_no_split():
    cell = harness.load_benchmark()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "benchmarks", "scope_split.py"),
         "--workload", cell, "--seed", "1"], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=harness.ROOT, timeout=300)
    assert p.returncode == harness.EXIT_NO_DEVICE and p.stdout.strip() == ""
