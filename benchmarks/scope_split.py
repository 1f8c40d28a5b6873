#!/usr/bin/env python3
"""``python3 benchmarks/scope_split.py --workload <cell> --seed <n>``
(needs the ``tensorflow`` package installed, for the one generated file
``tsl/profiler/protobuf/xplane_pb2.py``; TensorFlow itself is not imported)

Where the device's time goes inside one cell's traced bracket, by the round
programs' named scopes (``fed.forward``, ``fed.optimizer``, ...; see
OBSERVABILITY.md, "Round spans and device scopes"), and what the host was
doing in each long idle gap.

It drives the harness's own ``setup_engine``, ``first_rounds`` and ``window``
with the trace on (the cell's own bracket), then reads the ``.xplane.pb``
itself: ``trace_reduce.load_xplane`` keeps an event's name only, and the
scope sits in the stats of the operation's metadata. ``--plumbing`` is the
harness's own switch for a rehearsal on the CPU at tiny size (there is no
device plane to split there). The engine's ``fed.*`` host spans are
``TraceAnnotation`` events in the same file, so gaps are named on the
profiler's clock with no shifting. It prints one JSON object; nothing here
decides ``correct`` and no metric of BENCHMARK.json reads it: PERF.md's
per-scope table comes from it, and a later ``benchmark`` PR folds it into
``breakdown``.
"""

import argparse
import bisect
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import trace_reduce as tr  # noqa: E402

SCOPES = ("forward", "backward", "dropout_forward", "dropout_backward", "loss",
          "optimizer", "aggregate", "fingerprint", "transport", "codec",
          "lora_merge", "unscoped")
# the stat of a device operation's metadata that carries its ``op_name`` (the
# JAX name stack with the scopes in it, then ":" and the primitive) on this
# libtpu (0.0.34; looked at by hand on the chip, PERF.md section 6, PR 25)
OP_NAME_STAT = "tf_op"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("fed.", "bench.")
_MARK = re.compile(r"fed\.[a-z_.]+")


def classify(op_name):
    """The scope of one device operation from its ``op_name``. Transforms
    wrap a scope's name (``vmap(jvp(fed.forward))``), so a scope is matched
    as a substring and the innermost (last) one names the operation; inside
    ``fed.forward`` a ``transpose(`` around the name is the backward pass
    and a flax ``Dropout_*`` module below it is dropout. No ``fed.`` name at
    all is ``unscoped``."""
    marks = list(_MARK.finditer(op_name or ""))
    if not marks:
        return "unscoped"
    last = marks[-1]
    stage = last.group()[len("fed."):].rstrip(".")
    if stage == "forward":
        part = op_name[op_name.rfind("/", 0, last.start()) + 1:last.start()]
        side = "backward" if "transpose(" in part else "forward"
        if "Dropout_" in op_name[last.end():]:
            return "dropout_" + side
        return side
    if stage.startswith("codec"):
        return "codec"
    if stage.startswith("optimizer"):
        return "optimizer"
    return stage if stage in SCOPES else "unscoped"


def xplane_pb2():
    """The profiler's own protobuf module. ``jax.profiler.ProfileData`` shows
    an event's own stats and not those of its metadata, where ``tf_op``
    sits, so the file is parsed as what it is. The generated module ships
    inside the installation's TensorFlow; it is loaded by its path, because
    importing ``tensorflow`` itself would reach for the chip that this
    process already holds."""
    import importlib.util

    spec = importlib.util.find_spec("tensorflow")
    if spec is None:
        raise RuntimeError("no xplane_pb2: this installation has no tensorflow package")
    path = os.path.join(list(spec.submodule_search_locations)[0],
                        "tsl", "profiler", "protobuf", "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load(path):
    """``{"ops": [[(hlo name, op_name), start_ns, dur_ns], ...],
    "modules": [[start, end], ...], "host": [[name, start, end], ...]}`` of
    the first device plane and of every host plane, all on the trace's one
    clock (a line's timestamp plus the event's offset)."""
    space = xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices, host = {}, []
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata

        def events(line):
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps / 1e3
                yield meta[ev.metadata_id], start, ev.duration_ps / 1e3

        if plane.name.startswith(tr.DEVICE_PREFIX):
            op_names = {}
            for mid, m in meta.items():
                for st in m.stats:
                    if stat_names.get(st.metadata_id) == OP_NAME_STAT:
                        # a string, or a reference to one kept once per plane
                        op_names[mid] = st.str_value or stat_names.get(st.ref_value, "")
            ops, modules = [], []
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops = [[(m.name, op_names.get(m.id, "")), s, d]
                           for m, s, d in events(line)]
                elif line.name == MODULES_LINE:
                    modules = [[s, s + d] for _, s, d in events(line)]
            devices[plane.name] = {"ops": ops, "modules": modules}
        else:
            for line in plane.lines:
                host += [[m.name, s, s + d] for m, s, d in events(line)
                         if m.name.startswith(HOST_PREFIXES)]
    if not devices:
        return None
    first = devices[sorted(devices)[0]]
    return {"ops": first["ops"], "modules": first["modules"], "host": host}


def scope_totals(leaf_ops, top=3):
    """Device seconds and share of the leaves' time per scope, with the
    scope's ``top`` operations by ``short_name`` (an anonymous ``fusion`` of
    the ledger's ``breakdown.device_ops`` is then seen in its scopes)."""
    acc = {k: {} for k in SCOPES}
    for (name, op_name), _, dur in leaf_ops:
        ops = acc[classify(op_name)]
        name = tr.short_name(name)
        ops[name] = ops.get(name, 0.0) + dur
    whole = sum(sum(ops.values()) for ops in acc.values())
    out = {}
    for k, ops in acc.items():
        ns = sum(ops.values())
        if ns or k == "unscoped":
            out[k] = {"device_s": ns / 1e9, "share_pct": 100.0 * ns / whole if whole else 0.0,
                      "top_ops": [[n, d / 1e9] for n, d in
                                  sorted(ops.items(), key=lambda kv: -kv[1])[:top]]}
    return out


def innermost(host):
    """The ``fed.*`` host spans (nested, one thread) as pieces that do not
    overlap, ``[[start, end, name], ...]`` in time order: at every instant
    the span that opened last."""
    spans = sorted((s, -e, name[len("fed."):]) for name, s, e in host
                   if name.startswith("fed."))
    out, stack, cur = [], [], 0.0

    def piece(end):
        if end > cur:
            out.append([cur, end, stack[-1][1]])
        return max(cur, end)

    for s, neg_e, name in spans:
        while stack and stack[-1][0] <= s:
            cur = piece(stack[-1][0])
            stack.pop()
        if stack:
            cur = piece(s)
        cur = s
        stack.append((-neg_e, name))
    while stack:
        cur = piece(stack[-1][0])
        stack.pop()
    return out


def shares(gap, pieces, starts):
    """``{name: ns}`` of one idle gap over the pieces of ``innermost``."""
    a, b = gap
    acc = {}
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(pieces) and pieces[i][0] < b:
        ov = min(b, pieces[i][1]) - max(a, pieces[i][0])
        if ov > 0:
            acc[pieces[i][2]] = acc.get(pieces[i][2], 0.0) + ov
        i += 1
    return acc


def inside(gap, modules, starts):
    """Does the gap lie within one XLA program's execution?"""
    i = bisect.bisect_right(starts, gap[0]) - 1
    return i >= 0 and gap[1] <= modules[i][1]


def idle(leaf_ops, modules, host, top=20):
    """Every idle gap between device operations, added up by the innermost
    host span over it and by whether it lies inside one XLA program's
    execution or between two; and the ``top`` longest gaps, each with the
    spans over it by their share of it and the operation on either side."""
    ordered = sorted(leaf_ops, key=lambda e: e[1])
    op_starts = [op[1] for op in ordered]
    busy = tr.union([[s, s + d] for _, s, d in ordered])
    pieces = innermost(host)
    piece_starts = [p[0] for p in pieces]
    modules = sorted(modules)
    module_starts = [m[0] for m in modules]
    by_span, rows = {}, []
    for gap in tr.gaps(busy):
        a, b = gap
        kind = "in_program" if inside(gap, modules, module_starts) else "between_dispatches"
        acc = shares(gap, pieces, piece_starts)
        acc["unattributed"] = (b - a) - sum(acc.values())
        for name, ns in acc.items():
            row = by_span.setdefault(name, {"in_program": 0.0, "between_dispatches": 0.0})
            row[kind] += ns / 1e6
        rows.append((b - a, a, b, kind, acc))
    longest = []
    for length, a, b, kind, acc in sorted(rows, key=lambda r: -r[0])[:top]:
        # a gap between two dispatches runs over the host's whole loop, so
        # it is named by every span that holds a twentieth of it or more
        parts = sorted(((n, ns / length) for n, ns in acc.items() if ns >= 0.05 * length),
                       key=lambda kv: -kv[1])
        i = bisect.bisect_left(op_starts, b)
        # the operation that ends where the gap starts: the latest end before it
        before = max((op for op in ordered[:i] if op[1] + op[2] <= a + 1),
                     key=lambda op: op[1] + op[2], default=None)
        longest.append({
            "ms": length / 1e6, "kind": kind,
            "host_spans": [[n, round(share, 3)] for n, share in parts],
            "op_before": _op_label(before), "op_after": _op_label(
                ordered[i] if i < len(ordered) else None),
        })
    return by_span, longest


def _op_label(op):
    if op is None:
        return None
    (name, op_name), _, _ = op
    return {"op": tr.short_name(name), "scope": classify(op_name)}


def split(path, rounds=None):
    """The whole reduction of one ``.xplane.pb``; None without a device plane."""
    raw = load(path)
    if raw is None or not raw["ops"]:
        return None
    leaf_ops = tr.leaves(raw["ops"])
    busy = tr.union([[s, s + d] for _, s, d in leaf_ops])
    by_span, longest = idle(leaf_ops, raw["modules"], raw["host"])
    scopes = scope_totals(leaf_ops)
    if rounds:
        for row in scopes.values():
            row["ms_per_round"] = 1e3 * row["device_s"] / rounds
    return {
        "busy_s": tr.total(busy) / 1e9,
        "first_to_last_op_s": (busy[-1][1] - busy[0][0]) / 1e9,
        "device_events": len(raw["ops"]), "leaf_events": len(leaf_ops),
        "programs_run": len(raw["modules"]), "host_spans": len(raw["host"]),
        "scopes": scopes,
        "idle_ms_by_host_span": by_span,
        "idle_gaps": longest,
    }


def run(workload, seed, seconds, plumbing=False, out_dir=None):
    """One traced window of the cell through the harness, reduced."""
    import jax

    from benchmarks import harness

    devs = jax.devices()
    cell, sizes = harness.load_cell(workload, plumbing)
    out_dir = out_dir or os.path.join(ROOT, "bench_out", workload)
    os.makedirs(out_dir, exist_ok=True)
    r = harness.Run(cell, sizes, seed, seconds, True, plumbing, out_dir, time.time())
    harness.setup_engine(r)
    harness.first_rounds(r)
    harness.window(r)
    result = {"workload": workload, "seed": seed,
              "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                         "count": len(devs)},
              "bracket_rounds": cell["trace"]["dispatches"] * r.k}
    if plumbing:
        result["plumbing_only"] = True
    result["split"] = split(tr.find_xplane(r.trace_dir), result["bracket_rounds"])
    with open(os.path.join(out_dir, f"scope_split-{seed}.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--plumbing", action="store_true")
    args = ap.parse_args()

    from benchmarks import harness

    harness.place_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if not args.plumbing and platform != "tpu":
        print(f"no accelerator: jax found {platform} (--plumbing rehearses on a CPU)",
              file=sys.stderr)
        return harness.EXIT_NO_DEVICE
    seconds = harness.load_benchmark()["run_seconds"]
    print(json.dumps(run(args.workload, args.seed, seconds, args.plumbing)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
