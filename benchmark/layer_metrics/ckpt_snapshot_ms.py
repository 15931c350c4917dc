"""Loop + checkpoints: the loop's own `ckpt.snapshot` span (the device ->
host fetch, `ckpt.d2h`, inside `save_async`; since PR 25 the fetched
arrays go to the writer as they are, with no second copy): the median over the saves of the measured window that the trainer's
span file holds. `ckpt_stall_ms` times the same stall from outside, as
the difference between log windows with and without a save."""

from statistics import median

from benchmark.reduce import host_spans


def read(cell, ev):
    steps = [s for a, b in ev.get("quiet_windows", [])
             for s in (a[1], b[1])]
    spans = [r for r in host_spans.records(cell.trace_dir)
             if r["name"] == "ckpt.snapshot"
             and (not steps or min(steps) < r["attrs"].get("step", 0)
                  <= max(steps))]
    return median(r["dur"] for r in spans) * 1e3 if spans else None
