"""Loop + checkpoints: what one save takes out of the loop. From the log
windows inside the measured window (without those the profiler touched):
the median length of those that hold a save (they end on a multiple of
`ckpt_steps`) minus the median of those that hold none. The `ckpt
plane` line is no source: it is written when `run()` returns, which a
SIGKILL prevents."""

from statistics import median


def read(cell, ev):
    every = cell.traffic.get("ckpt_steps")
    if not every:
        return None
    pairs = ev.get("quiet_windows", [])
    saving = [b[0] - a[0] for a, b in pairs if b[1] % every == 0]
    plain = [b[0] - a[0] for a, b in pairs if b[1] % every]
    if not saving or not plain:
        return None
    return (median(saving) - median(plain)) * 1e3
