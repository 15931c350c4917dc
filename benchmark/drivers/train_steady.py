"""An elastic training job left alone: launcher -> trainer on seeded
shards, no checkpoint directory, measured from the first step line after
warm-up for `--seconds`. Throughput is that of the whole window: the
steps between its first and its last step line over the time between
the two, every stall inside it included."""

from __future__ import annotations

import time

from benchmark.harness import cell as cl
from benchmark.harness import logs
from benchmark.harness.procs import BenchFailure, alive, say


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
        lines = [s for s in logs.steps(job.lines(pid))
                 if t_start <= s[0] <= t_end]
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
    ref = cl.reference_check(cell, lines[0][1], lines[0][2])
    failed = cl.bad_steps(lines, cell.traffic["log_every"])
    return {
        "correct": bool(ref["ok"] and failed == 0),
        "attempted": lines[-1][1] - lines[0][1], "failed": failed,
        "device": {**device, "memory_peak_bytes": peak},
        "values": {cell.traffic["throughput_metric"]: rate,
                   "setup_s": setup_s},
        "evidence": {"quiet_windows": quiet, "newest_generation": gen},
    }
