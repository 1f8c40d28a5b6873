"""The profiler's ``.xplane.pb`` read as what it is: protocol-buffer wire
format, a handful of message types (tsl/profiler/protobuf/xplane.proto).

``jax.profiler.ProfileData`` shows an event's own stats and not those of its
metadata, where a device operation's ``op_name`` sits (stat ``tf_op``), and
the generated ``xplane_pb2`` ships only inside TensorFlow. So the few fields
the benchmark reads are decoded here, with nothing but the standard library:

  XSpace          planes = 1
  XPlane          name = 2, lines = 3, event_metadata = 4 (map), stat_metadata = 5 (map)
  XLine           name = 2, timestamp_ns = 3, events = 4
  XEvent          metadata_id = 1, offset_ps = 2, duration_ps = 3
  XEventMetadata  id = 1, name = 2, stats = 5
  XStatMetadata   id = 1, name = 2
  XStat           metadata_id = 1, str_value = 5, ref_value = 7

Everything else is skipped by its wire type. A file that is no such message
raises ``ValueError``; it is never read as empty.
"""

from __future__ import annotations


def _varint(buf, pos):
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7
        if shift > 70:
            raise ValueError("not a protocol-buffer varint")


def _fields(buf, pos, end):
    """``(field number, wire type, value, start, stop)`` of every field in
    ``buf[pos:end]``: the value of a varint field, else None with the
    payload's bounds."""
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield field, wire, value, pos, pos
        elif wire == 2:
            n, pos = _varint(buf, pos)
            if pos + n > end:
                raise ValueError("a length-delimited field runs past its message")
            yield field, wire, None, pos, pos + n
            pos += n
        elif wire == 1:
            yield field, wire, None, pos, pos + 8
            pos += 8
        elif wire == 5:
            yield field, wire, None, pos, pos + 4
            pos += 4
        else:
            raise ValueError(f"wire type {wire} is not in an xplane file")
    if pos != end:
        raise ValueError("a message ends inside a field")


def _text(buf, a, b):
    return bytes(buf[a:b]).decode("utf-8", "replace")


def _map_entry(buf, a, b):
    """A map field's entry: ``(key, (start, stop) of the value)``."""
    key, bounds = 0, (a, a)
    for f, w, v, s, e in _fields(buf, a, b):
        if f == 1 and w == 0:
            key = v
        elif f == 2 and w == 2:
            bounds = (s, e)
    return key, bounds


def _stat_metadata(buf, a, b):
    name = ""
    for f, w, _, s, e in _fields(buf, a, b):
        if f == 2 and w == 2:
            name = _text(buf, s, e)
    return name


def _event_metadata(buf, a, b, stat_names):
    """``(name, {stat name: string value})``: a string stat is kept as
    written, or as the stat name that its ``ref_value`` points at (the
    profiler keeps a repeated string once a plane)."""
    name, stats = "", {}
    for f, w, _, s, e in _fields(buf, a, b):
        if f == 2 and w == 2:
            name = _text(buf, s, e)
        elif f == 5 and w == 2:
            sid, text = 0, None
            for f2, w2, v2, s2, e2 in _fields(buf, s, e):
                if f2 == 1 and w2 == 0:
                    sid = v2
                elif f2 == 5 and w2 == 2:
                    text = _text(buf, s2, e2)
                elif f2 == 7 and w2 == 0:
                    text = stat_names.get(v2, "")
            if text is not None:
                stats[stat_names.get(sid, str(sid))] = text
    return name, stats


def _line(buf, a, b, keep):
    """``(name, timestamp_ns, [(metadata id, offset_ps, duration_ps), ...])``
    of a line, with the events whose metadata id is in ``keep`` (None keeps all). This loop
    sees every event of the trace, so it decodes the three varints it needs
    in place."""
    raw = []
    name, ts, pos = "", 0, a
    while pos < b:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 2:
            n, pos = _varint(buf, pos)
            end = pos + n
            if key >> 3 == 4:
                mid = off = dur = 0
                p = pos
                while p < end:
                    k = buf[p]
                    p += 1
                    if k == 0x08:
                        mid, p = _varint(buf, p)
                    elif k == 0x10:
                        off, p = _varint(buf, p)
                    elif k == 0x18:
                        dur, p = _varint(buf, p)
                    elif k & 7 == 2:
                        n2, p = _varint(buf, p)
                        p += n2
                    elif k & 7 == 0:
                        _, p = _varint(buf, p)
                    elif k & 7 == 1:
                        p += 8
                    elif k & 7 == 5:
                        p += 4
                    else:
                        raise ValueError("an XEvent holds an unknown wire type")
                if keep is None or mid in keep:
                    raw.append((mid, off, dur))
            elif key >> 3 == 2:
                name = _text(buf, pos, end)
            pos = end
        elif wire == 0:
            v, pos = _varint(buf, pos)
            if key >> 3 == 3:
                ts = v
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError("an XLine holds an unknown wire type")
    return name, ts, raw


def read(path, keep_line=None, keep_event=None):
    """The planes of ``path``::

        [{"name": plane name,
          "event_metadata": {id: (name, {stat name: string value})},
          "lines": [{"name": line name, "events": [(metadata id, start_ns, dur_ns), ...]}]}]

    ``keep_line(plane name, line name)`` says which lines are returned,
    ``keep_event(plane name, event name)`` which events of a line are (by the
    name of their metadata); None keeps everything.
    Times are on the trace's one clock, in nanoseconds from the earliest
    line of the file: a line's timestamp plus the event's offset."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for f, w, _, a, b in _fields(buf, 0, len(buf)):
        if f != 1 or w != 2:
            continue
        name, lines, stat_bounds, meta_bounds = "", [], [], []
        for f2, w2, _, s, e in _fields(buf, a, b):
            if f2 == 2 and w2 == 2:
                name = _text(buf, s, e)
            elif f2 == 3 and w2 == 2:
                lines.append((s, e))
            elif f2 == 4 and w2 == 2:
                meta_bounds.append(_map_entry(buf, s, e))
            elif f2 == 5 and w2 == 2:
                stat_bounds.append(_map_entry(buf, s, e))
        stat_names = {k: _stat_metadata(buf, *se) for k, se in stat_bounds}
        metadata = {k: _event_metadata(buf, *se, stat_names) for k, se in meta_bounds}
        keep = None
        if keep_event is not None:
            keep = {k for k, (n, _) in metadata.items() if keep_event(name, n)}
        out_lines = []
        for s, e in lines:
            line_name, ts, raw = _line(buf, s, e, keep)
            if keep_line is None or keep_line(name, line_name):
                out_lines.append({"name": line_name, "timestamp_ns": ts, "events": raw})
        planes.append({"name": name, "event_metadata": metadata, "lines": out_lines})
    if not planes:
        raise ValueError(f"{path} holds no XPlane: not a profiler trace")
    # whole nanoseconds since 1970 do not fit a float: count from the file's
    # earliest line
    base = min((ln["timestamp_ns"] for p in planes for ln in p["lines"] if ln["events"]),
               default=0)
    for p in planes:
        for ln in p["lines"]:
            ts = ln.pop("timestamp_ns") - base
            ln["events"] = [(mid, ts + off / 1e3, dur / 1e3) for mid, off, dur in ln["events"]]
    return planes
