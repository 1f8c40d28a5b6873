"""The benchmark's own reader of the profiler's ``.xplane.pb`` (xplane.py)
and ``trace_reduce.load_xplane`` over it, on a file encoded by hand here
(protocol-buffer wire format, field numbers of xplane.proto): a device
operation's ``op_name`` is the ``tf_op`` stat of its METADATA, written as a
string or as a reference to a stat name; the engine's ``fed.*`` host events
are kept, others not; and the scope readers raise where no operation
carries the stat, return None where there is no device trace, never 0."""

import pytest

from benchmarks import harness, xplane
from benchmarks import trace_reduce as tr


def varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(no, value):
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(no << 3 | 2) + varint(len(value)) + value


def event(mid, off_ps, dur_ps):
    return field(1, mid) + field(2, off_ps) + field(3, dur_ps) + field(4, field(1, 9) + field(3, 7))


def line(name, ts_ns, events):
    return field(1, 1) + field(2, name) + field(3, ts_ns) + b"".join(field(4, e) for e in events)


def plane(name, lines, event_meta=(), stat_meta=()):
    body = field(1, 3) + field(2, name) + b"".join(field(3, ln) for ln in lines)
    for mid, mname, stats in event_meta:
        m = field(1, mid) + field(2, mname) + b"".join(field(5, st) for st in stats)
        body += field(4, field(1, mid) + field(2, m))
    for sid, sname in stat_meta:
        body += field(5, field(1, sid) + field(2, field(1, sid) + field(2, sname)))
    return field(1, body)


T0 = 1_790_000_000_000_000_000  # whole nanoseconds since 1970: no float holds them
FWD = "jit(body)/while/body/vmap(jvp(fed.forward))/T/dot_general:"
NEW = "jit(body)/while/body/transpose(jvp(fed.moe.route))/Router/mul:"


def write(path, op_name_stat="tf_op"):
    device = plane(
        "/device:TPU:0",
        [line("XLA Ops", T0, [event(7, 1_000_000, 5_000_000), event(8, 7_000_000, 1_000_000),
                              event(9, 9_000_000, 2_000_000)]),
         line("XLA Modules", T0, [event(7, 0, 20_000_000)]),
         line("Steps", T0, [event(7, 0, 20_000_000)])],
        event_meta=[(7, "%fusion.12 = f32[] fusion()", [field(1, 1) + field(7, 2)]),  # by reference
                    (8, "%copy.3 = f32[] copy()", [field(1, 3) + field(3, 55)]),   # no op_name
                    (9, "%mul.1 = f32[] multiply()", [field(1, 1) + field(5, NEW)])],  # as a string
        stat_meta=[(1, op_name_stat), (2, FWD), (3, "flops")])
    host = plane(
        "/host:CPU",
        [line("python", T0 - 1000, [event(1, 2_000_000, 30_000_000), event(2, 3_000_000, 4_000_000),
                                    event(3, 0, 1_000)])],
        event_meta=[(1, "fed.round_program", []), (2, "fed.round_program/inputs", []),
                    (3, "PjitFunction(f)", [])])
    with open(path, "wb") as f:
        f.write(host + device + field(4, "hostname"))
    return str(path)


def test_reads_names_times_and_the_metadata_stat(tmp_path):
    planes = xplane.read(write(tmp_path / "t.xplane.pb"))
    assert [p["name"] for p in planes] == ["/host:CPU", "/device:TPU:0"]
    dev = planes[1]
    assert dev["event_metadata"][7] == ("%fusion.12 = f32[] fusion()", {"tf_op": FWD})
    assert dev["event_metadata"][8] == ("%copy.3 = f32[] copy()", {})
    ops = next(ln for ln in dev["lines"] if ln["name"] == "XLA Ops")["events"]
    # nanoseconds from the file's earliest line (the host's, 1000 ns before)
    assert ops == [(7, 2000.0, 5000.0), (8, 8000.0, 1000.0), (9, 10000.0, 2000.0)]


def test_load_xplane_keeps_op_names_and_fed_spans(tmp_path):
    raw = tr.load_xplane(write(tmp_path / "t.xplane.pb"))
    assert raw["op_names"] is True
    assert raw["devices"]["/device:TPU:0"] == [
        ["%fusion.12 = f32[] fusion()", 2000.0, 5000.0, FWD],
        ["%copy.3 = f32[] copy()", 8000.0, 1000.0, ""],
        ["%mul.1 = f32[] multiply()", 10000.0, 2000.0, NEW]]
    assert set(raw) == {"devices", "host", "op_names"}  # no other line of the device plane
    assert raw["host"] == [["fed.round_program", 2000.0, 30000.0],
                           ["fed.round_program/inputs", 3000.0, 4000.0]]
    table = tr.scope_table(tr.leaves(raw["devices"]["/device:TPU:0"]), rounds=1)
    assert table["scopes"] == pytest.approx(
        {"fed.forward": 5e-3, "unscoped": 1e-3, "transpose(fed.moe.route)": 2e-3})
    busy = tr.reduce_device(raw["devices"]["/device:TPU:0"])["busy"]
    assert tr.name_gaps(tr.gaps(busy), tr.host_spans(raw["host"])) == [
        ["round_program", pytest.approx(1e-6)], ["round_program", pytest.approx(1e-6)]]


def test_not_a_trace_is_an_error_and_never_empty(tmp_path):
    bad = tmp_path / "bad.xplane.pb"
    bad.write_bytes(b"\x0a\xff\xff\xff\x0f not a message")
    with pytest.raises(ValueError):
        xplane.read(str(bad))
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    with pytest.raises(ValueError):
        xplane.read(str(empty))


def reader(name):
    return harness.load_reader(harness.load_json("metrics", name + ".json")["reader"])


SCOPED = ["step.forward_ms_per_round", "step.backward_ms_per_round", "step.optimizer_ms_per_round",
          "step.dropout_ms_per_round", "aggregate.device_ms_per_round"]


def test_scope_readers_on_a_hand_made_table():
    S = "jit(body)/while/body/"
    names = {S + "jvp(fed.forward)/T/layer_0/dot_general:": 50.0,
             S + "jvp(fed.forward)/T/Dropout_0/jit(_bernoulli)/add:": 13.0,
             S + "transpose(jvp(fed.forward))/T/layer_0/dot_general:": 119.0,
             S + "transpose(jvp(fed.forward))/T/Dropout_0/select_n:": 0.5,
             S + "fed.optimizer/add:": 17.0, S + "vmap(fed.optimizer_init)/b:": 0.5,
             S + "fed.aggregate/div:": 2.2, "": 12.0,
             # a mechanism's scope inside the model, both passes, and one below a Dropout module
             S + "jvp(fed.forward)/T/layer_0/fed.moe.route/top_k:": 2.0,
             S + "transpose(jvp(fed.forward))/T/layer_0/fed.moe.route/mul:": 4.0,
             S + "transpose(jvp(fed.forward))/T/Dropout_1/fed.rng.mask/select_n:": 0.25}
    scopes = {}
    for n, ms in names.items():
        scopes[tr.scope_of(n)] = scopes.get(tr.scope_of(n), 0.0) + ms
    ctx = {"trace": {"scopes": scopes, "op_names": names}}
    got = {n: reader(n)(ctx) for n in SCOPED}
    assert got == pytest.approx({
        "step.forward_ms_per_round": 52.0, "step.backward_ms_per_round": 123.0,
        "step.optimizer_ms_per_round": 17.5, "step.dropout_ms_per_round": 13.75,
        "aggregate.device_ms_per_round": 2.2})
    # the nested scope keeps its own key in the table, by pass
    assert scopes["fed.moe.route"] == 2.0 and scopes["transpose(fed.moe.route)"] == 4.0
    assert scopes["fed.forward"] == 63.0 and scopes["transpose(fed.forward)"] == 119.5
    # the scopes add up to the device's whole time
    assert sum(scopes.values()) == pytest.approx(sum(names.values()))


def test_scope_readers_find_nothing_or_raise_but_never_zero(tmp_path):
    for n in SCOPED:
        assert reader(n)({"trace": None}) is None          # a CPU rehearsal: no device trace
    # a program with no such scope: nothing to read
    ctx = {"trace": {"scopes": {"unscoped": 3.0}, "op_names": {"": 3.0}}}
    assert all(reader(n)(ctx) is None for n in SCOPED)
    # a device trace whose operations carry no op_name: the reader says so
    raw = tr.load_xplane(write(tmp_path / "t.xplane.pb", op_name_stat="hlo_op"))
    assert raw["op_names"] is False
    ctx = {"trace": {"scopes": None, "op_names": None, "scopes_error": "no 'tf_op' stat"}}
    for n in SCOPED:
        with pytest.raises(RuntimeError, match="tf_op"):
            reader(n)(ctx)
