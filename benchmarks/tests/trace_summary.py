"""Print what a profiler trace holds, for a look by hand: its planes,
their lines, and the most frequent and longest event names per line.

    python benchmarks/tests/trace_summary.py <file.xplane.pb> [top]
"""

import collections
import sys


def main(path, top=12):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            acc = collections.defaultdict(lambda: [0, 0.0])
            t0, t1 = None, None
            for e in line.events:
                a = acc[e.name]
                a[0] += 1
                a[1] += e.duration_ns * 1e-9
                t0 = e.start_ns if t0 is None else min(t0, e.start_ns)
                t1 = max(t1 or 0, e.start_ns + e.duration_ns)
            n = sum(a[0] for a in acc.values())
            if not n:
                continue
            print(f"  line {line.name!r}: {n} events, "
                  f"extent {(t1 - t0) * 1e-9:.3f} s")
            for name, (k, s) in sorted(acc.items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                print(f"    {s:10.4f} s  x{k:<7d} {name[:110]}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)
