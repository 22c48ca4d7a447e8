"""From a JAX profiler trace (.xplane.pb) to seconds: device busy time, time
per device operation, and the idle gaps with what the host was doing in them.
Reads the file with ``jax.profiler.ProfileData`` and nothing else.

The device plane's "XLA Ops" line nests: a ``while`` or ``conditional`` event
spans every operation of its body, which follow as events of their own. A
container would make the device look busy from end to end, so it is left out
by name (``CONTAINERS``) and only the operations that do work are counted.
"""
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call", "tuple", "get-tuple-element",
              "parameter", "constant", "bitcast")
HOST_MARK = "bench:"          # the benchmark's own TraceAnnotations


def find_xplane(logdir):
    found = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return found[-1] if found else None


def base_name(name):
    """'%fusion.12 = ...' / 'fusion.12' -> 'fusion'; 'seg_hist.3' -> 'seg_hist'."""
    name = name.split(" = ")[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def is_container(name):
    return base_name(name) in CONTAINERS


def load(path):
    """{"device": {plane name: [(start_ns, end_ns, name)]},
        "host": [(start_ns, end_ns, name)]} from an .xplane.pb file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = device.setdefault(plane.name, [])
                for ev in line.events:
                    if not is_container(ev.name):
                        start = int(ev.start_ns)
                        events.append((start, start + int(ev.duration_ns),
                                       ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_MARK):
                        start = int(ev.start_ns)
                        host.append((start, start + int(ev.duration_ns),
                                     ev.name[len(HOST_MARK):]))
    return {"device": device, "host": host}


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def reduce(trace, window_s, top=10):
    """busy_s (mean over device planes), per-operation seconds (by base name,
    summed over planes and divided by their number), and the longest idle
    gaps on the first plane, labelled by the host annotation that covers
    each gap's middle."""
    planes = sorted(trace["device"])
    if not planes:
        return None
    busy, ops = 0.0, {}
    for name in planes:
        events = trace["device"][name]
        busy += sum(e - s for s, e in merge((s, e) for s, e, _ in events))
        for s, e, op in events:
            key = base_name(op)
            ops[key] = ops.get(key, 0.0) + (e - s)
    n = len(planes)
    first = merge((s, e) for s, e, _ in trace["device"][planes[0]])
    gaps = {}
    for (_, a), (b, _) in zip(first, first[1:]):
        mid = (a + b) // 2
        label = "host:unmarked"
        for s, e, what in trace["host"]:
            if s <= mid <= e:
                label = what
                break
        gaps[label] = gaps.get(label, 0.0) + (b - a)
    traced = (first[-1][1] - first[0][0]) * 1e-9 if first else 0.0
    edge = max(window_s - traced, 0.0)
    if edge > 0:
        gaps["before_first_or_after_last_op"] = edge * 1e9
    rank = lambda d: sorted(((k, v * 1e-9) for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy * 1e-9 / n, "window_s": window_s,
            "op_seconds": {k: v * 1e-9 / n for k, v in ops.items()},
            "device_ops": [[k, v / n] for k, v in rank(ops)],
            "idle_gaps": [[k, v] for k, v in rank(gaps)]}


def kernel_seconds(reduced, patterns):
    """Summed seconds of the operations whose base name contains any pattern;
    None when no operation matches."""
    hit = [v for k, v in reduced["op_seconds"].items()
           if any(p in k for p in patterns)]
    return sum(hit) if hit else None
