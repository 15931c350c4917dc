"""Loop + checkpoints: the share of the loop thread's time inside the
measured window (first to last mark of `reduce/loop_periods.py`) that
lies under one of its own spans. Under 99 % a span is missing where the
loop spends time, and a long period can hide there: the table's
`no span` column is that time, a period."""

from benchmark.reduce import loop_periods


def read(cell, ev):
    got = loop_periods.of(cell, ev)
    return 100.0 * got["covered_s"] / got["window_s"] if got else None
