"""Look at a profiler trace by hand: planes, lines, and the names that take
the most time on each line. ``python3 benchmark/tools/trace_summary.py <logdir
or .xplane.pb> [top]``."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import xtrace  # noqa: E402


def main(path, top=25):
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = xtrace.find_xplane(path)
    print("file", path, os.path.getsize(path), "bytes")
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("plane %r" % plane.name)
        for line in plane.lines:
            total, names, n = 0, {}, 0
            lo, hi = None, None
            for ev in line.events:
                n += 1
                d = int(ev.duration_ns)
                s = int(ev.start_ns)
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
                key = xtrace.base_name(ev.name)
                c = names.setdefault(key, [0, 0])
                c[0] += d
                c[1] += 1
                total += d
            if not n:
                continue
            print("  line %r: %d events, span %.3fs, summed %.3fs"
                  % (line.name, n, (hi - lo) * 1e-9, total * 1e-9))
            for key, (d, c) in sorted(names.items(),
                                      key=lambda kv: -kv[1][0])[:top]:
                print("    %-60s %10.4fs %8d" % (key[:60], d * 1e-9, c))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25)
