"""From a profiler trace to numbers: device busy time as the union of the
intervals in which an operation ran, the idle gaps between them, totals per
operation and per named scope of the round programs, and the collectives
with the part of them that nothing hides.

The arithmetic works on plain interval lists, so it is checked on a small
recorded trace and on hand-made intervals (benchmarks/tests). ``load_xplane``
turns the profiler's ``.xplane.pb`` into that form with the benchmark's own
reader of the file (benchmarks/xplane.py). A device operation is
``[name, start_ns, dur_ns]`` or ``[name, start_ns, dur_ns, op_name]``:
``op_name`` is the JAX name stack the operation was traced under, with the
``fed.*`` scopes in it (OBSERVABILITY.md, "Round spans and device scopes").
No list of known scopes exists anywhere: a scope that a later PR adds to the
program shows up under its own name."""

from __future__ import annotations

import bisect
import glob
import os
import re

COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
# the stat of a device operation's metadata that carries its ``op_name`` on
# this libtpu (0.0.34; looked at by hand on the chip, PERF.md section 6, PR 25)
OP_NAME_STAT = "tf_op"
HOST_PREFIXES = ("fed.",)
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"fed\.[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path, host_prefixes=HOST_PREFIXES):
    """``{"devices": {plane: [[op, start_ns, dur_ns, op_name], ...]},
    "host": [[name, start_ns, dur_ns], ...], "op_names": whether any device
    operation carried one}``: the device planes' operation lines with each
    operation's ``op_name``, and the host events whose name starts with one
    of ``host_prefixes`` (the engine's ``fed.*`` spans)."""
    from benchmarks import xplane

    prefixes = tuple(host_prefixes)

    def keep_line(plane, line):
        return not plane.startswith(DEVICE_PREFIX) or line == OPS_LINE

    def keep_event(plane, name):
        return plane.startswith(DEVICE_PREFIX) or name.startswith(prefixes)

    devices, host, named = {}, [], False
    for plane in xplane.read(path, keep_line, keep_event):
        meta = plane["event_metadata"]
        if plane["name"].startswith(DEVICE_PREFIX):
            devices.setdefault(plane["name"], [])
            for line in plane["lines"]:
                if line["name"] == OPS_LINE:
                    ops = []
                    for mid, start, dur in line["events"]:
                        name, stats = meta.get(mid, ("", {}))
                        ops.append([name, start, dur, stats.get(OP_NAME_STAT, "")])
                    named = named or any(op[3] for op in ops)
                    devices[plane["name"]] = ops
        else:
            for line in plane["lines"]:
                host += [[meta[mid][0], s, d] for mid, s, d in line["events"] if mid in meta]
    return {"devices": devices, "host": host, "op_names": named}


def union(intervals):
    """Merged ``[[start, end], ...]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(merged):
    return sum(e - s for s, e in merged)


def subtract(merged_a, merged_b):
    """The parts of ``merged_a`` that ``merged_b`` does not cover."""
    out, j = [], 0
    for s, e in merged_a:
        cur = s
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < e:
            if merged_b[k][0] > cur:
                out.append([cur, merged_b[k][0]])
            cur = max(cur, merged_b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def gaps(merged):
    """Idle intervals between consecutive busy intervals."""
    return [[a[1], b[0]] for a, b in zip(merged, merged[1:]) if b[0] > a[1]]


def is_collective(op_name):
    n = op_name.lower()
    return any(m in n for m in COLLECTIVE_MARKS)


def short_name(op_name):
    """The profiler names an operation by its whole HLO line: keep the
    instruction's name, without ``%`` and without its running number, so
    that the twelve layers' copies of one fusion add up."""
    n = op_name.split(" = ")[0].strip().lstrip("%")
    head, _, tail = n.rpartition(".")
    return head if head and tail.isdigit() else n


def leaves(ops):
    """The operations that contain no other: a ``while`` or a ``call`` spans
    its body's operations on the same line, and would count them twice."""
    out = []
    ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
    for i, op in enumerate(ordered):
        s, d = op[1], op[2]
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[1] < s + d and nxt[1] + nxt[2] <= s + d and d > 0:
            continue
        out.append(list(op))
    return out


def op_totals(ops, top=10, by_scope=False):
    """``[[name, seconds], ...]``: the leaf operations that took most time,
    added up by ``short_name``; with ``by_scope`` by scope and name
    (``transpose(fed.forward):multiply_add_fusion``), so that an anonymous
    ``fusion`` is seen in the scopes it serves."""
    acc = {}
    for op in leaves(ops):
        name = short_name(op[0])
        if by_scope:
            name = scope_of(op[3] if len(op) > 3 else "") + ":" + name
        acc[name] = acc.get(name, 0.0) + op[2]
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[n, d / 1e9] for n, d in rows]


def reduce_device(ops):
    """Busy seconds, the merged busy intervals, and the collectives' total
    and exposed seconds of one device's operation events."""
    busy = union([[op[1], op[1] + op[2]] for op in ops])
    coll = union([[op[1], op[1] + op[2]] for op in ops if is_collective(op[0])])
    other = union([[op[1], op[1] + op[2]] for op in ops if not is_collective(op[0])])
    return {
        "busy_s": total(busy) / 1e9,
        "busy": busy,
        "collective_s": total(coll) / 1e9,
        "collective_exposed_s": total(subtract(coll, other)) / 1e9,
    }


# ------------------------------------------------------------------- scopes

def scope_path(op_name):
    """``(names, backward)`` of one device operation's ``op_name``: every
    ``fed.<name>`` of the name stack, outermost first, dots and all
    (``fed.moe.route`` is a scope like any other), and whether the operation
    belongs to the backward pass. JAX writes a transform around the FIRST
    scope it meets and around none below it: the backward pass of a scope
    nested in the forward pass reads
    ``transpose(jvp(fed.forward))/Model/fed.moe.route/mul``. So an operation
    is backward where ANY element of the stack down to the innermost scope's
    own holds ``transpose(``. What follows the innermost scope (modules and
    the primitive, which may itself be called ``transpose``) plays no part."""
    marks = list(_SCOPE.finditer(op_name or ""))
    if not marks:
        return (), False
    return (tuple(m.group() for m in marks),
            "transpose(" in op_name[:marks[-1].start()])


def scope_of(op_name):
    """The key of one device operation in the scope table: the innermost
    (last) ``fed.<name>`` of its name stack, as ``transpose(fed.<name>)``
    where the operation belongs to the backward pass (``scope_path``). No
    ``fed.`` name at all is ``unscoped``: the compiler's own copies and
    slices carry no ``op_name``."""
    names, backward = scope_path(op_name)
    if not names:
        return UNSCOPED
    return f"transpose({names[-1]})" if backward else names[-1]


def below_scope(op_name, scope=None):
    """The part of the name stack below ``scope`` (its first occurrence; the
    innermost scope where none is named): the modules, the scopes nested in
    it and the primitive (``.../TextClassifier/Dropout_0/select_n:``). The
    whole name where the stack has no such scope."""
    marks = [m for m in _SCOPE.finditer(op_name or "") if scope in (None, m.group())]
    if not marks:
        return op_name or ""
    return op_name[(marks[0] if scope else marks[-1]).end():]


def scope_table(leaf_ops, rounds):
    """``{"scopes": {scope: ms a round}, "op_names": {op_name: ms a round}}``
    of one device's leaf operations over a bracket of ``rounds`` rounds. The
    scopes add up to the leaves' whole time; ``op_names`` keeps the time of
    every distinct name stack, for a reader that looks below a scope (a
    module's share of it)."""
    scopes, names = {UNSCOPED: 0.0}, {}
    per = 1e-6 / max(rounds, 1)
    for op in leaf_ops:
        op_name = op[3] if len(op) > 3 else ""
        ms = op[2] * per
        key = scope_of(op_name)
        scopes[key] = scopes.get(key, 0.0) + ms
        names[op_name or ""] = names.get(op_name or "", 0.0) + ms
    return {"scopes": scopes, "op_names": names}


# ---------------------------------------------------------------- idle gaps

def innermost(spans):
    """Nested spans (one thread) as pieces that do not overlap,
    ``[[start, end, name], ...]`` in time order: at every instant the span
    that opened last. ``spans`` are ``[name, start, end, ...]``."""
    ordered = sorted((sp[1], -sp[2], sp[0]) for sp in spans)
    out, stack, cur = [], [], 0.0

    def piece(end):
        if end > cur:
            out.append([cur, end, stack[-1][1]])
        return max(cur, end)

    for s, neg_e, name in ordered:
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


def name_gaps(idle, spans, top=10):
    """``[[name, seconds], ...]``: the longest idle gaps, each named by the
    INNERMOST host span that covers most of it: a child span names the gap it
    covers before its parent does. ``spans`` are ``[name, start, end, ...]``
    on the trace's clock. A gap of which no span covers as much as is left
    uncovered is ``unattributed``."""
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    rows = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        acc = shares([a, b], pieces, starts)
        acc["unattributed"] = (b - a) - sum(acc.values())
        rows.append([max(acc.items(), key=lambda kv: (kv[1], kv[0] != "unattributed"))[0],
                     (b - a) / 1e9])
    return rows


def host_spans(host, prefix="fed."):
    """The engine's spans among ``load_xplane``'s host events, as
    ``[name less the prefix, start, end]``."""
    return [[name[len(prefix):], s, s + d] for name, s, d in host if name.startswith(prefix)]
