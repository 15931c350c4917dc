"""Entry points: the trainer's `train.startup` span, process start (the
kernel's, from /proc) -> `TrainLoop.run` entered: imports, runtime
start, mesh and model, parameter and optimizer init. Of the newest
generation, which in a cell with a kill is the resumed one, so that
`resume_s` = `respawn_s` + this + `ckpt_restore_s` + `first_step_s` +
a residual. From `spans-<pid>.jsonl` in the profile directory."""

from benchmark.reduce import host_spans


def read(cell, ev):
    pid = ev.get("resume", {}).get("pid")
    spans = [r for r in host_spans.records(cell.trace_dir, pid)
             if r["name"] == "train.startup"]
    return spans[-1]["dur"] if spans else None
