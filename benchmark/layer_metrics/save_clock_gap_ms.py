"""Device: the milliseconds of `host.clock_gap` spans (the trainer's
clock sampler, `obs/trace.py`) inside the whole save periods of the
measured window (the profiler's left out, `loop_periods.quiet`, as in
`save_period_spread_ms`): time in which the host's clock ran on and the
sampler's thread did not, so the machine stood still, the process was
off the cores, or a thread of the trainer kept the interpreter lock
(the gap's `cpu_s` tells the last from the first two; the table of
`reduce/loop_periods.py` shows it a period). 0 in a quiet run. Nothing
for a program without the sampler."""

from benchmark.reduce import loop_periods


def read(cell, ev):
    got = loop_periods.of(cell, ev)
    if not got or not got["sampled"]:
        return None
    return 1e3 * sum(row["gap_s"] for row in got["quiet_periods"])
