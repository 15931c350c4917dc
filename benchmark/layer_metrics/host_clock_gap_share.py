"""Device: the share of the measured window in which the host's clock
ran on without the trainer's clock sampler (`obs/trace.py`: a thread
that sleeps 50 ms at a time and leaves a `host.clock_gap` span when a
tick comes back more than four ticks late): the seconds of those spans
inside the window over the window, both on the records' own clock, over
every step of the window and not only the profiler's. 0 in a quiet run;
what it reads is lost from `train_tokens_per_s` one for one where the
gap stood between two dispatches. The gap's `cpu_s` (in the table of
`reduce/loop_periods.py`) says whether the machine or the scheduler had
it (about 0) or one of the trainer's own threads (about the gap).
Nothing for a program without the sampler."""

from benchmark.reduce import loop_periods


def read(cell, ev):
    got = loop_periods.of(cell, ev)
    if not got or not got["sampled"]:
        return None
    return 100.0 * got["gap_s"] / got["window_s"]
