"""Loop + checkpoints: the share of the window's time that its median
log window does not account for: 1 - (number of log windows x their
median length) / (their summed length). Near 0 when every window is like
the median; a late loader, an eval or a host hiccup shows here, and
lowers `train_tokens_per_s` by the same share. In a traced run the
windows the profiler touched are left out (`harness.cell.windows`), so
that this reads the loop and not the profiler."""

from statistics import median


def read(cell, ev):
    lengths = [b[0] - a[0] for a, b in ev.get("quiet_windows", [])]
    if len(lengths) < 2:
        return None
    return 100.0 * (1.0 - len(lengths) * median(lengths) / sum(lengths))
