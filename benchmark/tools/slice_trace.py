"""Cut a profiler trace down to a file small enough to keep in the repository,
and write beside it what the reducer has to read from it.

    python3 benchmark/tools/slice_trace.py <in.xplane.pb> <out.xplane.pb> [events]

Keeps every plane and line, and of each line its first ``events`` events;
drops the host's metadata plane's payload (compiled programs' text). Reads
and writes the protobuf wire format directly (XSpace: planes=1; XPlane:
name=2, lines=3, event_metadata=4; XLine: name=2, timestamp_ns=3, events=4;
XEvent: metadata_id=1, offset_ps=2, duration_ps=3), so the expected numbers
written to ``<out>.expected.json`` come from another parser and another
interval sweep than ``harness/xtrace.py`` uses.
"""
import json
import sys


def varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7f) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def put_varint(value):
    out = bytearray()
    while True:
        byte = value & 0x7f
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def fields(buf):
    """(field number, wire type, value, raw bytes of the whole field)."""
    i = 0
    while i < len(buf):
        start = i
        tag, i = varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            n, i = varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError("wire type %d" % wire)
        yield tag >> 3, wire, value, buf[start:i]


def delimited(field, payload):
    return put_varint(field << 3 | 2) + put_varint(len(payload)) + payload


def first(buf, field, default=None):
    for f, _, value, _ in fields(buf):
        if f == field:
            return value
    return default


def slice_line(line, keep):
    out, kept = bytearray(), 0
    for f, _, _, raw in fields(line):
        if f == 4:
            kept += 1
            if kept > keep:
                continue
        out += raw
    return bytes(out)


def slice_plane(plane, keep):
    name = first(plane, 2, b"").decode()
    out = bytearray()
    for f, _, value, raw in fields(plane):
        if f == 3:
            out += delimited(3, slice_line(value, keep))
        elif f == 4 and name == "/host:metadata":
            continue
        else:
            out += raw
    return bytes(out)


def device_events(space):
    """{plane: [(start_ns, end_ns, name)]} of the 'XLA Ops' lines."""
    found = {}
    for f, _, plane, _ in fields(space):
        if f != 1:
            continue
        pname = first(plane, 2, b"").decode()
        if not pname.startswith("/device:TPU:"):
            continue
        names = {}
        for f2, _, entry, _ in fields(plane):
            if f2 == 4:
                meta = first(entry, 2, b"")
                names[first(entry, 1, 0)] = first(meta, 2, b"").decode()
        for f2, _, line, _ in fields(plane):
            if f2 != 3 or first(line, 2, b"").decode() != "XLA Ops":
                continue
            t0_ps = first(line, 3, 0) * 1000
            for f3, _, ev, _ in fields(line):
                if f3 == 4:
                    start = t0_ps + first(ev, 2, 0)
                    found.setdefault(pname, []).append(
                        (start / 1000.0, (start + first(ev, 3, 0)) / 1000.0,
                         names[first(ev, 1, 0)]))
    return found


def expected(space, containers):
    """Busy seconds and seconds per operation name, by a sweep over the
    sorted interval edges (not the merge that the reducer uses)."""
    from harness import xtrace
    planes = device_events(space)
    busy_ns, ops, n_events = 0.0, {}, 0
    for events in planes.values():
        edges = []
        for start, end, name in events:
            key = xtrace.base_name(name)
            if key in containers:
                continue
            n_events += 1
            ops[key] = ops.get(key, 0.0) + (end - start)
            edges += [(start, 1), (end, -1)]
        depth, since = 0, None
        for at, step in sorted(edges, key=lambda e: (e[0], -e[1])):
            if depth == 0 and step > 0:
                since = at
            depth += step
            if depth == 0:
                busy_ns += at - since
    n = max(len(planes), 1)
    return {"planes": len(planes), "events": n_events,
            "busy_s": busy_ns * 1e-9 / n,
            "op_seconds": {k: v * 1e-9 / n for k, v in sorted(ops.items())}}


def main(src, dst, keep=3000):
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from harness import xtrace
    with open(src, "rb") as f:
        space = f.read()
    out = bytearray()
    for f, _, value, raw in fields(space):
        out += delimited(1, slice_plane(value, keep)) if f == 1 else raw
    with open(dst, "wb") as f:
        f.write(out)
    want = expected(bytes(out), xtrace.CONTAINERS)
    with open(dst + ".expected.json", "w") as f:
        json.dump(want, f, indent=1)
    print("%s: %d bytes -> %s: %d bytes, %d device events counted, busy %.6fs"
          % (src, len(space), dst, len(out), want["events"], want["busy_s"]))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2],
         int(sys.argv[3]) if len(sys.argv) > 3 else 3000)
