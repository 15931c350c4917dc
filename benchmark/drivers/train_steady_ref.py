"""`train_steady` for a configuration that brings its own reference: an
elastic training job left alone (launcher -> trainer on seeded shards,
no checkpoint directory), measured from the first step line after
warm-up for `--seconds`, throughput over the whole window. It starts,
windows, rates and counts failures through the same functions of
`harness/cell.py`; what differs is the child that decides `correct`,
which is the module the configuration's `reference.checker` names, and
the step lines' own counters (`name=value` after `loss=`), which are
kept as evidence for the per-layer readers."""

from __future__ import annotations

import re
import time

from benchmark.harness import cell as cl
from benchmark.harness import logs
from benchmark.harness.procs import BenchFailure, alive, say

_COUNTER = re.compile(r"(\w+)=(-?[\d.]+(?:e-?\d+)?)(?=\s)")


def step_counters(lines: logs.Lines) -> list[dict]:
    """{step, name: value, ...} of every step line."""
    out = []
    for _, text in lines:
        head = re.search(r"epoch \d+ step (\d+): (.*)", text)
        if head:
            out.append({"step": int(head.group(1)),
                        **{k: float(v) for k, v in
                           _COUNTER.findall(head.group(2))}})
    return out


def reference_check(cell: cl.Cell, step: int, logged_loss: float) -> dict:
    """`cell.reference_check` with the configuration's own checker: the
    trainer's logged loss at ``step`` against the plain float32
    reference on the same parameters and batch, and the program's
    forward pass against it token by token, both computed by a child
    that gets the chip after the trainer has ended and keeps what it
    compiles in `cell.REF_CACHE`: it compiles once a checkout."""
    limits = cell.config["reference"]
    ref = cl.reference_child(cell, limits["checker"], step,
                             cell.reference_env(), 600)
    diff = abs(ref["loss"] - logged_loss)
    say(f"reference on {ref['platform']}: {ref}; trainer logged "
        f"{logged_loss:.4f} at step {step}: |diff| {diff:.5f} (tolerance "
        f"{limits['loss_tolerance']}); token by token rms "
        f"{ref['token_loss_rms_diff']:.5f} (tolerance "
        f"{limits['token_loss_rms_tolerance']})")
    return {"ok": diff <= limits["loss_tolerance"]
            and ref["token_loss_rms_diff"]
            <= limits["token_loss_rms_tolerance"], "diff": diff, **ref}


def run(cell: cl.Cell) -> dict:
    job, pid, device = cl.start_training(cell, [])
    try:
        job.wait_line(pid, logs.first_step_complete, 900,
                      "the first step")
        # warm-up ends at the first step line after the first step: every
        # program the loop uses has run by then
        first = job.wait_line(pid, logs.steps, 300, "a step line")[0]
        t_start = first[0]
        setup_s = t_start - cell.t0
        say(f"window opens at step {first[1]} (set-up {setup_s:.2f}s)")
        while time.monotonic() < t_start + cell.seconds:
            if not alive(pid):
                raise BenchFailure("the trainer died inside the window:\n"
                                   + job.worker_tail.text())
            time.sleep(0.05)
        t_end = t_start + cell.seconds
        inside = [(t, ln) for t, ln in job.lines(pid)
                  if t_start <= t <= t_end]
        lines = logs.steps(inside)
        if len(lines) < 2:
            raise BenchFailure("fewer than two step lines in the window")
        profiled = None
        if cell.trace:  # the profiler's window may still be flushing
            written = job.wait_line(pid, logs.trace_written, 120,
                                    "the profiler to write its trace")
            profiled = (cell.traffic["profile"]["start_step"], written)
        quiet = cl.windows(lines, profiled)
        spans = sorted(b[0] - a[0] for a, b in quiet)
        rate = cl.rate_over([(lines[0], lines[-1])], cell.tokens_per_step)
        say(f"{rate:.1f} tokens/s over the whole window; {len(spans)} log "
            f"windows the profiler did not touch: shortest {spans[0]:.4f}s,"
            f" median {spans[len(spans) // 2]:.4f}s, longest "
            f"{spans[-1]:.4f}s")
        peak = job.memory_peak_bytes()
        gen = logs.first_step_wall(job.lines(pid)) or {}
    finally:
        job.kill(graceful=True)
    counters = step_counters(inside)
    ref = reference_check(cell, lines[0][1], lines[0][2])
    # a dropless model that dropped a token is as wrong as a wrong loss
    dropped = sum(c.get("moe_dropped", 0.0) > 0 for c in counters)
    failed = cl.bad_steps(lines, cell.traffic["log_every"]) \
        + cell.traffic["log_every"] * dropped
    return {
        "correct": bool(ref["ok"] and failed == 0),
        "attempted": lines[-1][1] - lines[0][1], "failed": failed,
        "device": {**device, "memory_peak_bytes": peak},
        "values": {cell.traffic["throughput_metric"]: rate,
                   "setup_s": setup_s},
        "evidence": {"quiet_windows": quiet, "newest_generation": gen,
                     "step_counters": counters, "reference": ref},
    }
