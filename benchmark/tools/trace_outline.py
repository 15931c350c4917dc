"""Prints what a profiler trace holds: planes, lines, and per line the
event names that took most time. For looking at one trace by hand before
writing a reduction against it.

    python benchmark/tools/trace_outline.py <dir or .xplane.pb> [top n]
"""

from __future__ import annotations

import collections
import glob
import os
import sys


def main(argv: list[str]) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData
    path = argv[0]
    top = int(argv[1]) if len(argv) > 1 else 25
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            total = collections.Counter()
            count = collections.Counter()
            first = last = None
            example = {}
            for e in line.events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
                first = e.start_ns if first is None else min(first,
                                                             e.start_ns)
                end = e.start_ns + e.duration_ns
                last = end if last is None else max(last, end)
                if e.name not in example:
                    example[e.name] = [(k, str(v)[:80]) for k, v in e.stats]
            n = sum(count.values())
            span = (last - first) / 1e6 if n else 0.0
            print(f"  LINE {line.name!r}: {n} events, {len(total)} names, "
                  f"span {span:.3f} ms, summed "
                  f"{sum(total.values()) / 1e6:.3f} ms")
            for name, ns in total.most_common(top):
                print(f"    {ns / 1e6:10.3f} ms  x{count[name]:<5d} {name}"
                      f"  {example[name][:6]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
