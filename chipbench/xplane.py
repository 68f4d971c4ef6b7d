"""
A reader for the profiler's .xplane.pb that sees what `ProfileData` hides.

On a TPU the `op_name` of an HLO operation (the jax.named_scope path, stat
`tf_op`) is a stat of the event's METADATA, not of the event, and
`jax.profiler.ProfileData` exposes only the event's own stats (looked at by
hand, PR 23). The file is a protobuf (tsl/profiler/protobuf/xplane.proto);
this decodes the few fields the reduction needs straight from the wire
format, with nothing but the standard library. Field numbers are the
schema's and are checked against a recorded trace in chipbench/tests.
"""

import struct


def _varint(buf, pos):
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, pos
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; length-delimited
    values come back as memoryviews and are not copied."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = buf[pos:pos + size]
            pos += size
        elif wire == 1:
            value = struct.unpack_from("<d", buf, pos)[0]
            pos += 8
        elif wire == 5:
            value = struct.unpack_from("<f", buf, pos)[0]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, wire, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _signed(value):
    return value - (1 << 64) if value >= (1 << 63) else value


def _map_entry(buf):
    key = value = None
    for number, _, v in _fields(buf):
        if number == 1:
            key = _signed(v)
        elif number == 2:
            value = v
    return key, value


def _event_metadata(buf):
    """XEventMetadata -> (name, display_name, {stat metadata id: str}):
    its string stats only."""
    name = display = ""
    stats = {}
    for number, _, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 4:
            display = _text(v)
        elif number == 5:
            sid = text = None
            for n, _, sv in _fields(v):
                if n == 1:
                    sid = sv
                elif n == 5:
                    text = sv
            if text is not None:
                stats[sid] = _text(text)
    return name, display, stats


def read(path, line_filter=None):
    """[{"name", "lines": [{"name", "events": [(start_ps, end_ps, name,
    {stat name: str})]}]}] with the string stats of each event's metadata.
    `line_filter(plane name, line name)` says which lines to decode."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    planes = []
    for number, _, plane_buf in _fields(data):
        if number != 1:
            continue
        name, lines, emeta, smeta = "", [], [], {}
        for n, _, v in _fields(plane_buf):
            if n == 2:
                name = _text(v)
            elif n == 3:
                lines.append(v)
            elif n == 4:
                emeta.append(v)
            elif n == 5:
                key, value = _map_entry(v)
                for m, _, mv in _fields(value):
                    if m == 2:
                        smeta[key] = _text(mv)
        metadata = None     # decoded only if a line of this plane is read
        out_lines = []
        for line_buf in lines:
            line_name, timestamp_ns, events = "", 0, []
            for n, _, v in _fields(line_buf):
                if n == 2:
                    line_name = _text(v)
                elif n == 3:
                    timestamp_ns = _signed(v)
                elif n == 4:
                    events.append(v)
            if line_filter and not line_filter(name, line_name):
                continue
            if metadata is None:
                metadata = {}
                for entry in emeta:
                    key, value = _map_entry(entry)
                    ename, display, stats = _event_metadata(value)
                    metadata[key] = (ename, display,
                                     {smeta.get(k, str(k)): s
                                      for k, s in stats.items()})
            decoded = []
            for event_buf in events:
                mid = offset = duration = 0
                for n, _, v in _fields(event_buf):
                    if n == 1:
                        mid = _signed(v)
                    elif n == 2:
                        offset = _signed(v)
                    elif n == 3:
                        duration = _signed(v)
                start = timestamp_ns * 1000 + offset
                ename, display, stats = metadata.get(mid, ("", "", {}))
                decoded.append((start, start + duration, display or ename,
                                stats))
            out_lines.append({"name": line_name, "events": decoded})
        planes.append({"name": name, "lines": out_lines})
    return planes
