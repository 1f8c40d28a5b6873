"""From a profiler trace to numbers: device busy time as the union of the
intervals in which an operation ran, the idle gaps between them, totals per
operation, and the collectives with the part of them that nothing hides.

The arithmetic works on plain interval lists, so it is checked on a small
recorded trace and on hand-made intervals (benchmarks/tests). ``load_xplane``
turns the profiler's ``.xplane.pb`` into that form with jax's own reader."""

from __future__ import annotations

import glob
import os

COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path, host_prefixes=("bench.",)):
    """``{"devices": {plane name: [[op, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``: the device planes' operation
    lines, and the host events whose name starts with one of
    ``host_prefixes`` (the harness's own annotations)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
            devices[plane.name] = ops
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tuple(host_prefixes)):
                        host.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
    return {"devices": devices, "host": host}


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
    for i, (name, s, d) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[1] < s + d and nxt[1] + nxt[2] <= s + d and d > 0:
            continue
        out.append([name, s, d])
    return out


def op_totals(ops, top=10):
    """``[[name, seconds], ...]``: the leaf operations that took most time,
    added up by ``short_name``."""
    acc = {}
    for name, _, dur in leaves(ops):
        name = short_name(name)
        acc[name] = acc.get(name, 0.0) + dur
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[n, d / 1e9] for n, d in rows]


def reduce_device(ops):
    """Busy seconds, the merged busy intervals, and the collectives' total
    and exposed seconds of one device's operation events."""
    busy = union([[s, s + d] for _, s, d in ops])
    coll = union([[s, s + d] for n, s, d in ops if is_collective(n)])
    other = union([[s, s + d] for n, s, d in ops if not is_collective(n)])
    return {
        "busy_s": total(busy) / 1e9,
        "busy": busy,
        "collective_s": total(coll) / 1e9,
        "collective_exposed_s": total(subtract(coll, other)) / 1e9,
    }


def name_gaps(idle, spans, top=10):
    """``[[name, seconds], ...]``: the longest idle gaps, each named by the
    host span that covers most of it. ``spans`` are ``[name, start, end,
    rank]`` on the trace's clock; of spans that cover a gap equally the
    lower rank (the inner span) names it. What no span covers is
    ``unattributed``."""
    rows = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        best, best_key = "unattributed", (0.0, 0)
        for name, s, e, rank in spans:
            ov = min(b, e) - max(a, s)
            if ov > 0 and (round(ov / (b - a), 2), -rank) > best_key:
                best, best_key = name, (round(ov / (b - a), 2), -rank)
        rows.append([best, (b - a) / 1e9])
    return rows
