"""The scope reading (benchmarks/trace_reduce.py, the harness's ``--trace 1``
reduction) on hand-made events: an operation's scope from its ``op_name``
with no list of known scopes, the backward pass of a scope nested in
another, the table a round, the host spans flattened to the innermost one at
every instant, and idle gaps named by them."""

import pytest

from benchmarks import trace_reduce as tr

STACK = "jit(body)/while/body/closed_call/vmap()/while/body/closed_call/"


@pytest.mark.parametrize("op_name,scope", [
    (STACK + "jvp(fed.forward)/TextClassifier/encoder/layer_0/attention/dot_general:", "fed.forward"),
    (STACK + "transpose(jvp(fed.forward))/TextClassifier/pooler/transpose:", "transpose(fed.forward)"),
    ("jit(f)/vmap(transpose(jvp(fed.forward)))/TextClassifier/pooler/mul:", "transpose(fed.forward)"),
    (STACK + "jvp(fed.forward)/TextClassifier/Dropout_0/jit(_bernoulli)/jit(_uniform)/add:",
     "fed.forward"),
    (STACK + "transpose(jvp(fed.forward))/TextClassifier/Dropout_0/select_n:", "transpose(fed.forward)"),
    ("jit(eval_one)/fed.forward/TextClassifier/embeddings/gather:", "fed.forward"),
    (STACK + "jvp(fed.loss)/log_softmax:", "fed.loss"),
    (STACK + "transpose(jvp(fed.loss))/mul:", "transpose(fed.loss)"),
    (STACK + "fed.optimizer/add:", "fed.optimizer"),
    ("jit(body)/vmap(fed.optimizer_init)/broadcast_in_dim:", "fed.optimizer_init"),
    ("jit(body)/while/body/closed_call/fed.aggregate/reduce_sum:", "fed.aggregate"),
    ("jit(body)/fed.aggregate/fed.aggregate/div:", "fed.aggregate"),
    ("jit(body)/while/body/closed_call/fed.fingerprint/dot_general:", "fed.fingerprint"),
    ("jit(body)/while/body/closed_call/fed.fingerprint/fed.transport/add:", "fed.transport"),
    ("jit(_enc)/fed.codec.encode/top_k:", "fed.codec.encode"),
    ("jit(_enc)/fed.codec.encode/fed.codec.decode/scatter-add:", "fed.codec.decode"),
    ("jit(f)/jvp(fed.forward)/fed.lora_merge/dot_general:", "fed.lora_merge"),
    # JAX wraps the first scope it meets and none below it: a scope nested in
    # the forward pass is backward where ANY element above it is transposed
    (STACK + "transpose(jvp(fed.forward))/Model/fed.moe.route/mul:", "transpose(fed.moe.route)"),
    (STACK + "jvp(fed.forward)/Model/fed.moe.route/tanh:", "fed.moe.route"),
    ("jit(step)/fed.outer/vmap(transpose(jvp(fed.forward)))/fed.moe.route/add_any:",
     "transpose(fed.moe.route)"),
    ("jit(step)/fed.outer/vmap(transpose(jvp(fed.forward)))/M/fed.moe.route/fed.moe.route.topk/mul:",
     "transpose(fed.moe.route.topk)"),
    ("jit(f)/transpose(jvp(fed.forward))/fed.lora_merge/dot_general:", "transpose(fed.lora_merge)"),
    # a primitive called transpose below the innermost scope is no backward pass
    (STACK + "jvp(fed.forward)/Model/fed.moe.route/transpose:", "fed.moe.route"),
    (STACK + "jvp(fed.forward)/T/pooler/transpose(x):", "fed.forward"),
    # a scope no file here has heard of keeps its own name, dots and all
    (STACK + "jvp(fed.moe.route)/top_k:", "fed.moe.route"),
    (STACK + "transpose(jvp(fed.moe.route))/Router/dot_general:", "transpose(fed.moe.route)"),
    ("jit(f)/fed.x.y2/add:", "fed.x.y2"),
    ("jit(body)/while/body/dynamic_slice:", "unscoped"),
    ("jit(_threefry_split)/slice:", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_scope_of(op_name, scope):
    assert tr.scope_of(op_name) == scope


def test_scope_path_names_every_scope_around_an_operation():
    name = "jit(step)/fed.outer/vmap(transpose(jvp(fed.forward)))/M/fed.moe.route/mul:"
    assert tr.scope_path(name) == (("fed.outer", "fed.forward", "fed.moe.route"), True)
    assert tr.scope_path(STACK + "jvp(fed.forward)/M/fed.moe.route/mul:") == (
        ("fed.forward", "fed.moe.route"), False)
    assert tr.scope_path("jit(f)/copy:") == ((), False) and tr.scope_path(None) == ((), False)


def test_below_scope_finds_the_dropout_modules():
    name = STACK + "transpose(jvp(fed.forward))/TextClassifier/Dropout_0/select_n:"
    assert "Dropout_" in tr.below_scope(name)
    assert "Dropout_" not in tr.below_scope(STACK + "jvp(fed.forward)/TextClassifier/pooler/mul:")
    assert tr.below_scope("jit(f)/copy:") == "jit(f)/copy:" and tr.below_scope(None) == ""
    # below a NAMED scope: what a scope nested under the module still sits below
    nested = STACK + "jvp(fed.forward)/T/Dropout_0/fed.rng.mask/select_n:"
    assert tr.below_scope(nested) == "/select_n:"
    assert tr.below_scope(nested, "fed.forward") == ")/T/Dropout_0/fed.rng.mask/select_n:"
    assert tr.below_scope(nested, "fed.absent") == nested


def test_op_totals_by_scope_and_name():
    ops = [["%fusion.1 = f32[]", 0, 60.0, STACK + "jvp(fed.forward)/x:"],
           ["%fusion.2 = f32[]", 60, 30.0, STACK + "fed.optimizer/add:"],
           ["%copy.3 = f32[]", 90, 10.0, ""],
           ["%fusion.4 = f32[]", 100, 20.0, STACK + "transpose(jvp(fed.forward))/M/fed.moe.route/mul:"]]
    assert tr.op_totals(ops, by_scope=True) == [
        ["fed.forward:fusion", pytest.approx(60e-9)], ["fed.optimizer:fusion", pytest.approx(30e-9)],
        ["transpose(fed.moe.route):fusion", pytest.approx(20e-9)],
        ["unscoped:copy", pytest.approx(10e-9)]]
    assert tr.op_totals(ops, top=1) == [["fusion", pytest.approx(110e-9)]]
    assert tr.op_totals([], by_scope=True) == []


def test_scope_table_a_round_keeps_unknown_scopes_and_splits_the_passes():
    fwd, bwd = STACK + "jvp(fed.forward)/T/x:", STACK + "transpose(jvp(fed.forward))/T/x:"
    drop = STACK + "jvp(fed.forward)/T/Dropout_0/select_n:"
    ops = [["%a.1 = f32[]", 0, 4e6, fwd], ["%a.2 = f32[]", 5e6, 8e6, bwd],
           ["%d.3 = f32[]", 14e6, 2e6, drop], ["%r.4 = f32[]", 17e6, 1e6, STACK + "jvp(fed.moe.route)/top_k:"],
           ["%r.5 = f32[]", 19e6, 3e6, STACK + "transpose(jvp(fed.moe.route))/mul:"],
           ["%c.6 = f32[]", 23e6, 2e6], ["%o.7 = f32[]", 26e6, 6e6, "jit(f)/vmap(fed.optimizer_init)/b:"]]
    t = tr.scope_table(ops, rounds=2)
    assert t["scopes"] == pytest.approx({
        "fed.forward": 3.0, "transpose(fed.forward)": 4.0, "fed.moe.route": 0.5,
        "transpose(fed.moe.route)": 1.5, "unscoped": 1.0, "fed.optimizer_init": 3.0})
    assert sum(t["scopes"].values()) == pytest.approx(sum(op[2] for op in ops) / 2e6)
    assert t["op_names"][drop] == pytest.approx(1.0) and t["op_names"][""] == pytest.approx(1.0)
    assert tr.op_totals(ops, top=2, by_scope=True) == [
        ["transpose(fed.forward):a", pytest.approx(8e-3)], ["fed.optimizer_init:o", pytest.approx(6e-3)]]


# host events as load_xplane gives them: [name, start, duration]
HOST = [["fed.round_program", 0.0, 100.0], ["fed.round_program/inputs", 5.0, 15.0],
        ["fed.round_program/enqueue", 20.0, 10.0], ["fed.ledger", 60.0, 35.0],
        ["fed.ledger/chain", 62.0, 32.0], ["other.mark#1", 1.0, 0.0],
        ["fed.post_round", 101.0, 9.0]]


def test_innermost_pieces_do_not_overlap_and_keep_the_latest_span():
    pieces = tr.innermost(tr.host_spans(HOST))
    assert [p[2] for p in pieces] == [
        "round_program", "round_program/inputs", "round_program/enqueue", "round_program",
        "ledger", "ledger/chain", "ledger", "round_program", "post_round"]
    assert all(a[1] <= b[0] for a, b in zip(pieces, pieces[1:]))
    assert sum(p[1] - p[0] for p in pieces) == pytest.approx(109.0)
    assert tr.innermost([]) == []


def test_gaps_are_named_by_the_innermost_span():
    fwd = STACK + "jvp(fed.forward)/x:"
    # busy [0,6] [18,28] [70,72] [120,121]; a while spans its body's operations
    ops = [["%a.1 = f32[]", 0, 6.0, fwd], ["%b.2 = f32[]", 18, 4.0, fwd],
           ["%while.9 = f32[]", 17.5, 11.0, ""],
           ["%c.3 = f32[]", 23, 5.0, ""], ["%d.4 = f32[]", 70, 2.0, fwd],
           ["%e.5 = f32[]", 120, 1.0]]
    spans = tr.host_spans(HOST)
    busy = tr.union([[op[1], op[1] + op[2]] for op in tr.leaves(ops)])
    idle = tr.gaps(busy)
    assert idle == [[6.0, 18.0], [22.0, 23.0], [28.0, 70.0], [72.0, 120.0]]
    # the run's breakdown names each of the longest gaps by the innermost span
    assert tr.name_gaps(idle, spans, top=3) == [
        ["ledger/chain", pytest.approx(48e-9)], ["round_program", pytest.approx(42e-9)],
        ["round_program/inputs", pytest.approx(12e-9)]]
    pieces = tr.innermost(spans)
    starts = [p[0] for p in pieces]
    # 72..120: the chain's 72..94 is the largest piece of it, then the ledger's own 94..95,
    # the phase's 95..100, nothing over 100..101 and 110..120, post_round between
    assert tr.shares([72.0, 120.0], pieces, starts) == pytest.approx(
        {"ledger/chain": 22.0, "ledger": 1.0, "round_program": 5.0, "post_round": 9.0})
    # 28..70: enqueue to 30, the phase's own time 30..60, the ledger's own 60..62, its chain
    assert tr.shares([28.0, 70.0], pieces, starts) == pytest.approx(
        {"round_program/enqueue": 2.0, "round_program": 30.0, "ledger": 2.0, "ledger/chain": 8.0})
    assert tr.shares([6.0, 18.0], pieces, starts) == pytest.approx({"round_program/inputs": 12.0})
    # a gap that no span covers as much as is left uncovered
    assert tr.name_gaps([[100.0, 120.0]], spans) == [["unattributed", pytest.approx(20e-9)]]
