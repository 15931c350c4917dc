"""The measured window on the loop's own clock: its save periods (or log
windows), who on the host had each, and what the host's clock did.

The readers of `reduce/host_spans.py` see the four or five steps the
profiler saw. This one reads `spans-<pid>.jsonl` of the newest
generation over every step of the measured window (the steps of the
driver's log windows, found by the `step` attribute of `train.dispatch`
and `ckpt.snapshot`), on the records' own clock, no profiler needed:

- a *mark* is the moment the loop went on after a step: the start of
  the next step's `train.dispatch` (where a SIGKILL took that record:
  the end of the last thing the loop's thread finished);
- a *save period* runs from the mark after one save to the mark after
  the next, so it holds the steps between them and the second save,
  like the driver's periods between step lines; where the traffic saves
  nothing the rows are the log windows;
- a row gives its length, the self time of the loop thread's spans by
  name (a span's duration minus what its children cover; `no span` is
  the rest), the seconds of `host.clock_gap` records inside it with
  their cause column (`cpu_s`), the seconds of each phase of the checkpoint
  writer's thread that overlap it (with the versions its `ckpt.gc`
  removed), and how its save was handed over (`writer_inflight`,
  `superseded`, `queued_s`).

The table goes to stderr like the other readers' tables: every save
period, and in a cell without saves every log window more than 5 % over
the median.
"""

from __future__ import annotations

import collections
import statistics
import traceback

from benchmark.harness.procs import say
from benchmark.reduce import host_spans, xplane

GAP = "host.clock_gap"
SNAPSHOT = "ckpt.snapshot"
WRITE = "ckpt.write"
CUT_WRITE = "ckpt.write (cut by the kill)"
WRITER_PHASES = ("ckpt.clean", "ckpt.chunks", "ckpt.seal", "ckpt.mirror",
                 "ckpt.gc")
# the sampler's first record: without it a window without gaps cannot
# be told from a program that does not look
SAMPLER = "host.clock_sampler"
PROFILER = "train.profiler"
# a period of which the profiler took more than this share is the
# profiler's (starting it, and waiting for the device and writing its
# file as it stops, 3 s of the period it stops in): the save cell's
# readers leave it out, as `ckpt_stall_ms` leaves out the log windows
# the profiler touched
PROFILERS_SHARE = 0.01
LONG_WINDOW = 1.05   # x the median log window


def _end(r: dict) -> float:
    return r["t0"] + r["dur"]


def _inside(r: dict, a: float, b: float) -> float:
    return max(0.0, min(_end(r), b) - max(r["t0"], a))


def _covered(spans: list[dict], a: float, b: float) -> float:
    """Seconds of [a, b] under at least one of ``spans``."""
    return xplane.length(xplane.clip(xplane.union(
        (r["t0"], _end(r)) for r in spans), a, b))


def _self_times(mine: list[dict], kids: dict, a: float, b: float
                ) -> dict[str, float]:
    """Self seconds of the loop thread's spans inside [a, b] by name;
    ``kids`` are a span's children by its id."""
    out = collections.Counter()
    for r in mine:
        lo, hi = max(r["t0"], a), min(_end(r), b)
        if hi <= lo:
            continue
        out[r["name"]] += hi - lo - _covered(
            kids.get(r.get("sid"), ()), lo, hi)
    out[host_spans.NO_SPAN] = max(0.0, b - a - _covered(mine, a, b))
    return dict(out)


def _row(a_step, b_step, a, b, mine, kids, gaps, writer, snaps, writes
         ) -> dict:
    row = {"steps": (a_step, b_step), "length_s": b - a,
           "self_s": _self_times(mine, kids, a, b),
           "gap_s": sum(_inside(g, a, b) for g in gaps),
           # this process's CPU seconds over the gaps: about 0 when the
           # machine or the scheduler had them, about their length when
           # a thread of the trainer kept the interpreter lock
           "gap_cpu_s": sum(g["attrs"].get("cpu_s", 0.0) for g in gaps
                            if _inside(g, a, b)),
           "writer_s": {}, "gc_removed": 0}
    for r in writer:
        got = _inside(r, a, b)
        if got:
            row["writer_s"][r["name"]] = \
                row["writer_s"].get(r["name"], 0.0) + got
            row["gc_removed"] += r["attrs"].get("removed", 0)
    snap = snaps.get(b_step)
    if snap is not None:
        write = writes.get(b_step, {"attrs": {}})
        row["save"] = {
            "writer_inflight": snap["attrs"].get("writer_inflight"),
            "superseded": snap["attrs"].get("superseded"),
            "queued_s": write["attrs"].get("queued_s")}
    return row


def _unfinished_writes(recs: list[dict]) -> list[dict]:
    """A write the run's SIGKILL cut leaves no record, but its finished
    phases do (`ckpt.clean` at the least, milliseconds after it began).
    It began with the first of them and was still running when the
    process made its last record: whatever ended before that record did
    is in the file, and this write's end is not."""
    known = {r.get("sid") for r in recs}
    last = max(map(_end, recs))
    by_parent = collections.defaultdict(list)
    for r in recs:
        if r["name"] in WRITER_PHASES and r.get("parent") not in known:
            by_parent[r.get("parent")].append(r)
    return [{"name": CUT_WRITE, "t0": min(k["t0"] for k in kids),
             "dur": last - min(k["t0"] for k in kids), "attrs": {}}
            for kids in by_parent.values()]


def _write_overlap_s(snaps: dict, writes: list[dict], lo: int, hi: int
                     ) -> float:
    """Summed over the window's saves: seconds for which the write
    before a snapshot was still running after that snapshot began (of a
    write the kill cut: up to the process's last record)."""
    total = 0.0
    for step, snap in snaps.items():
        before = [w for w in writes if w["t0"] < snap["t0"]]
        if lo < step <= hi and before:
            last = max(before, key=lambda w: w["t0"])
            total += max(0.0, _end(last) - snap["t0"])
    return total


def _text(row: dict) -> str:
    def parts(d):
        return ", ".join(f"{k} {v:.3f}" for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1])
                         if v >= 0.0005) or "none"
    save = row.get("save")
    return (f"steps {row['steps'][0] + 1}-{row['steps'][1]}: "
            f"{row['length_s']:.3f} s | loop thread, self time: "
            f"{parts(row['self_s'])}"
            + ("" if quiet(row) else " (the profiler's period)")
            + f" | clock gaps {row['gap_s']:.3f} s"
            + (f" (cpu_s {row['gap_cpu_s']:.3f})" if row["gap_s"] else "")
            + f" | writer thread inside it: {parts(row['writer_s'])}"
            + (f" (versions removed {row['gc_removed']})"
               if "ckpt.gc" in row["writer_s"] else "")
            + (f" | its save: writer_inflight {save['writer_inflight']} "
               f"superseded {save['superseded']} queued_s "
               f"{save['queued_s']}" if save else ""))


def quiet(row: dict) -> bool:
    """Not the profiler's period."""
    return (row["self_s"].get(PROFILER, 0.0)
            <= PROFILERS_SHARE * row["length_s"])


def _reduce(cell, ev: dict) -> dict | None:
    recs = host_spans.records(cell.trace_dir)
    steps = [s for a, b in ev.get("quiet_windows", [])
             for s in (a[1], b[1])]
    dispatch = {r["attrs"].get("step"): r for r in recs
                if r["name"] == host_spans.DISPATCH}
    if not steps or not dispatch:
        return None
    lo, hi = min(steps), max(steps)
    thread = next(iter(dispatch.values())).get("thread")
    mine = [r for r in recs if r.get("thread") == thread]
    kids = collections.defaultdict(list)
    for r in mine:
        kids[r.get("parent")].append(r)
    cut = _unfinished_writes(recs)
    writer = [r for r in recs if r["name"] in (*WRITER_PHASES, WRITE)] + cut
    gaps = [r for r in recs if r["name"] == GAP]
    snaps, writes = ({r["attrs"]["step"]: r for r in recs
                      if r["name"] == name
                      and isinstance(r["attrs"].get("step"), int)}
                     for name in (SNAPSHOT, WRITE))

    def mark(step: int) -> float | None:
        if step + 1 in dispatch:
            return dispatch[step + 1]["t0"]
        if step not in dispatch:
            return None
        return max(_end(r) for r in mine if r["t0"] >= dispatch[step]["t0"])

    a, b = mark(lo), mark(hi)
    if a is None or b is None or b <= a:
        return None
    saves = sorted(s for s in snaps if lo <= s <= hi)
    log_every = cell.traffic.get("log_every")
    lines = list(range(lo, hi + 1, log_every)) if log_every \
        else sorted(set(steps))
    rows = {}
    for kind, marks in (("periods", saves), ("windows", lines)):
        at = [(s, mark(s)) for s in marks]
        rows[kind] = [_row(s0, s1, t0, t1, mine, kids, gaps, writer, snaps,
                           writes)
                      for (s0, t0), (s1, t1) in zip(at, at[1:])
                      if t0 is not None and t1 is not None and t1 > t0]
    out = {
        "window_s": b - a,
        "sampled": any(r["name"] == SAMPLER for r in recs),
        "gap_s": sum(_inside(g, a, b) for g in gaps),
        "covered_s": _covered(mine, a, b),
        "periods": rows["periods"], "windows": rows["windows"],
        "quiet_periods": [r for r in rows["periods"] if quiet(r)],
        "write_overlap_s": _write_overlap_s(
            snaps, [*writes.values(), *cut], lo, hi)}
    shown = out["periods"]
    if not shown and out["windows"]:
        limit = LONG_WINDOW * statistics.median(
            r["length_s"] for r in out["windows"])
        shown = [r for r in out["windows"] if r["length_s"] > limit]
    say(f"the measured window on the loop's clock, steps {lo + 1}-{hi}: "
        f"{out['window_s']:.3f} s, {100 * out['covered_s'] / out['window_s']:.3f}"
        f" % of the loop's thread under its own spans, clock gaps "
        f"{out['gap_s']:.3f} s" + ("" if out["sampled"] else
                                   " (the program has no clock sampler)")
        + f", write still running into the next snapshot "
        f"{out['write_overlap_s']:.3f} s; "
        + (f"{len(out['periods'])} save periods:" if out["periods"] else
           f"{len(shown)} of {len(out['windows'])} log windows over "
           f"{LONG_WINDOW} x their median:"))
    for row in shown:
        say("  " + _text(row))
    return out


def of(cell, ev: dict) -> dict | None:
    """The reduction of this run's measured window, made once and
    printed as the table the readers share. Nothing where the program
    wrote no record of the window's steps (a program older than its
    spans, a run without a profile directory)."""
    if "loop_periods" not in ev:
        try:
            ev["loop_periods"] = _reduce(cell, ev)
        except Exception:  # noqa: BLE001 — a reader never fails the run
            say("loop_periods could not read this run's records:\n"
                + traceback.format_exc())
            ev["loop_periods"] = None
    return ev["loop_periods"]
