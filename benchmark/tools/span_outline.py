"""Prints a traced run's host spans beside the device's steps, in time
order on the trace's clock: what the loop's thread and the checkpoint
writer did while each step ran, and what they did while the device
waited. For reading one trace by hand (`trace_outline.py` shows what
the file holds; this shows the program's own story in it).

    python benchmark/tools/span_outline.py <profile dir> [min span ms]

`<profile dir>` is what the trainer got as `--profile`: the profiler's
`.xplane.pb` somewhere under it and `spans-<pid>.jsonl` in it.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.reduce import host_spans, xplane  # noqa: E402


def main(argv: list[str]) -> int:
    trace_dir = argv[0]
    least_ns = float(argv[1]) * 1e6 if len(argv) > 1 else 0.0
    path = xplane.newest_trace(trace_dir)
    if path is None:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 2
    recs = host_spans.records(trace_dir)
    spans = host_spans.spans_on_trace_clock(path, recs)
    rows = [(s["start"], s["end"], s["thread"] or "?", s["name"],
             s["attrs"], s["source"]) for s in spans
            if s["end"] - s["start"] >= least_ns]
    for plane in xplane.load(path).planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        for line in plane.lines if m else ():
            if line.name == xplane.MODULES_LINE:
                rows += [(e.start_ns, e.start_ns + e.duration_ns,
                          f"TPU:{m.group(1)}", e.name, {}, "device")
                         for e in line.events]
    if not rows:
        print("no span and no device step in this trace", file=sys.stderr)
        return 1
    rows.sort(key=lambda r: r[0])
    zero = min(r[0] for r in rows if r[5] != "record")
    print(f"{path}\n{len(recs)} span records, {len(spans)} spans on the "
          "trace's clock; ms from the first event of the trace; a span "
          "marked * is known from spans-<pid>.jsonl alone")
    for start, end, thread, name, attrs, source in rows:
        mark = "*" if source == "record" else " "
        extra = " ".join(f"{k}={v}" for k, v in attrs.items())
        print(f"{(start - zero) / 1e6:12.3f} {(end - start) / 1e6:11.3f} ms"
              f" {mark} {thread:<16s} {name} {extra}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
