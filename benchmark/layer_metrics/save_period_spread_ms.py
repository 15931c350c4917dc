"""Loop + checkpoints: the longest minus the shortest whole save period
of the measured window, a period taken on the loop's own clock from the
start of the `train.dispatch` that follows one save to the start of the
one that follows the next. The profiler's period is left out
(`loop_periods.quiet`), here as in `save_clock_gap_ms`. `ckpt_stall_ms`
compares log windows from outside and is two-valued (2.2 s or 4-7 s,
ledger PRs 32-37); this says by how much the window's periods differ,
and the table of `reduce/loop_periods.py` says which span, gap or
writer phase the long one holds. 0 where one period is left."""

from benchmark.reduce import loop_periods


def read(cell, ev):
    got = loop_periods.of(cell, ev)
    if not got:
        return None
    lengths = [row["length_s"] for row in got["quiet_periods"]]
    return 1e3 * (max(lengths) - min(lengths)) if lengths else 0.0
