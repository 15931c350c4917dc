"""The program's own spans, on the device's clock.

While its profiler runs, the trainer writes every span (`edl_tpu/obs/
trace.py`: `train.loader_wait`, `train.dispatch`, `train.log_fetch`,
`ckpt.snapshot` with `ckpt.d2h` and `ckpt.stage`, `ckpt.write`, ...) as
an event of the host plane (`/host:CPU`, one line a thread) of the same
`.xplane.pb` the device's lines are in: one clock, no alignment. The
same spans, and those from before the profiler started (start-up, the
restore, a write still running when it stopped), are records of
`spans-<pid>.jsonl` beside the profiler's file, stamped with the wall
clock. A `train.dispatch` is in both with its `step`: the median
difference of their starts moves the records onto the trace's clock,
and a record without an event (it began before the profiler, or ended
after it) is added to what the host plane holds.

The join: every idle stretch of a device inside the window of whole
steps is split among the spans of the loop's thread (the one that
dispatches the steps: what it does is why the device waits) that cover
it, the profiler's own step annotation left out. A stretch under nested
spans is labelled with all of them, outermost first
(`ckpt.snapshot > ckpt.stage`); one under none is `no span`.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import statistics

from benchmark.harness.procs import say
from benchmark.reduce import xplane

HOST_PLANE = "/host:CPU"
STEP_ANNOTATION = "train"   # StepTraceAnnotation("train", step_num=n)
DISPATCH = "train.dispatch"
NO_SPAN = "no span"
# an event and its record start within this of each other once the
# records are on the trace's clock (the offsets spread by under 0.1 ms)
PAIRING_NS = 2e6


def records(trace_dir: str, pid: int | None = None) -> list[dict]:
    """The spans one process wrote under ``trace_dir``: process ``pid``,
    or the one whose spans end last (a killed generation's file lies
    beside its successor's). Nothing where the program writes none."""
    if pid is not None:
        paths = [os.path.join(trace_dir, f"spans-{pid}.jsonl")]
    else:
        paths = glob.glob(os.path.join(trace_dir, "spans-*.jsonl"))
    newest: list[dict] = []
    for path in paths:
        if not os.path.exists(path):
            continue
        recs = []
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:      # a killed process tears a line
                    continue
                if isinstance(rec, dict) and "name" in rec and "t0" in rec:
                    rec.setdefault("dur", 0.0)
                    rec.setdefault("attrs", {})
                    recs.append(rec)
        if recs and (not newest or _end(recs) > _end(newest)):
            newest = recs
    return newest


def _end(recs: list[dict]) -> float:
    return max(r["t0"] + r["dur"] for r in recs)


def plane_events(path: str, names: set[str]) -> list[dict]:
    """The host plane's events that carry one of the program's span
    names: {name, start, end (ns), line, stats}."""
    out = []
    for plane in xplane.load(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in names:
                    out.append({"name": e.name, "start": e.start_ns,
                                "end": e.start_ns + e.duration_ns,
                                "line": i, "stats": dict(e.stats)})
    return sorted(out, key=lambda e: e["start"])


def clock_offset_ns(events: list[dict], recs: list[dict]) -> float | None:
    """Wall-clock ns minus trace ns: the median over the dispatches that
    both hold, told apart by their `step`."""
    by_step = {r["attrs"].get("step"): r for r in recs
               if r["name"] == DISPATCH}
    diffs = [by_step[e["stats"]["step"]]["t0"] * 1e9 - e["start"]
             for e in events if e["name"] == DISPATCH
             and e["stats"].get("step") in by_step]
    return statistics.median(diffs) if diffs else None


def spans_on_trace_clock(path: str, recs: list[dict]) -> list[dict]:
    """Every span as {name, start, end (ns of the trace), thread, attrs,
    source: "plane" | "record"}. An event takes thread and attributes
    from the record that starts where it does; one no record pairs with
    keeps its line's number for a thread."""
    events = plane_events(path, {r["name"] for r in recs}
                          | {STEP_ANNOTATION})
    offset = clock_offset_ns(events, recs)
    if offset is None:
        return []
    by_name = collections.defaultdict(list)
    for r in recs:
        by_name[r["name"]].append((r["t0"] * 1e9 - offset, r))
    out, paired, thread_of_line = [], set(), {}
    for e in events:
        r = None
        if by_name.get(e["name"]):
            start, near = min(by_name[e["name"]],
                              key=lambda sr: abs(sr[0] - e["start"]))
            if abs(start - e["start"]) <= PAIRING_NS:
                r = near
                paired.add(id(r))
                thread_of_line.setdefault(e["line"], r.get("thread"))
        out.append({"name": e["name"], "start": e["start"],
                    "end": e["end"], "line": e["line"],
                    "attrs": (r or {}).get("attrs", e["stats"]),
                    "source": "plane"})
    for s in out:
        s["thread"] = thread_of_line.get(s.pop("line")) or "?"
    for r in recs:   # began before the profiler, or ended after it
        if id(r) not in paired:
            start = r["t0"] * 1e9 - offset
            out.append({"name": r["name"], "start": start,
                        "end": start + r["dur"] * 1e9,
                        "thread": r.get("thread"),
                        "attrs": r.get("attrs", {}), "source": "record"})
    return sorted(out, key=lambda s: s["start"])


def loop_thread(spans: list[dict]) -> str | None:
    for s in spans:
        if s["name"] == DISPATCH:
            return s["thread"]
    return None


def idle_by_span(trace: dict, spans: list[dict]) -> dict[str, float]:
    """Idle device seconds inside the window of whole steps by the
    loop-thread spans that cover them, averaged over the chips."""
    thread = loop_thread(spans)
    mine = [s for s in spans
            if s["thread"] == thread and s["name"] != STEP_ANNOTATION]
    total = collections.Counter()
    n = len(trace["devices"])
    for d in trace["devices"].values():
        lo, hi = d["window_ns"]
        for a, b in xplane.subtract([(lo, hi)], d["busy"]):
            over = [s for s in mine if s["end"] > a and s["start"] < b]
            cuts = sorted({a, b, *(min(max(t, a), b) for s in over
                                   for t in (s["start"], s["end"]))})
            for x, y in zip(cuts, cuts[1:]):
                cover = [s for s in over
                         if s["start"] <= x and s["end"] >= y]
                cover.sort(key=lambda s: (s["start"], -s["end"]))
                label = " > ".join(s["name"] for s in cover) or NO_SPAN
                total[label] += (y - x) / 1e9 / n
    return dict(total)


def thread_seconds(trace: dict, spans: list[dict], name: str) -> float:
    """Seconds the loop's thread spent in spans called ``name`` inside
    the window of whole steps, averaged over the chips' windows."""
    thread = loop_thread(spans)
    total = 0.0
    for d in trace["devices"].values():
        lo, hi = d["window_ns"]
        total += xplane.length(xplane.clip(xplane.union(
            (s["start"], s["end"]) for s in spans
            if s["name"] == name and s["thread"] == thread), lo, hi))
    return total / len(trace["devices"]) / 1e9


def of(cell, ev: dict) -> dict | None:
    """This run's spans on its trace's clock and the idle time they
    explain, made once and printed as the table the readers share.
    Nothing where the program wrote no spans (a program older than
    them)."""
    trace = ev.get("trace")
    if not trace:
        return None
    if "host_spans" not in ev:
        recs = records(cell.trace_dir)
        spans = spans_on_trace_clock(trace["path"], recs) if recs else []
        if loop_thread(spans) is None:
            ev["host_spans"] = None
        else:
            idle = idle_by_span(trace, spans)
            say("idle device seconds of the traced window by the loop "
                "thread's spans: " + ", ".join(
                    f"{k} {v:.4f}" for k, v in
                    sorted(idle.items(), key=lambda kv: -kv[1])[:12]))
            ev["host_spans"] = {"spans": spans, "idle": idle}
    return ev["host_spans"]
