"""Loop + checkpoints: the share of the traced window that the loop's
thread spent in `train.loader_wait` (the next batch from the loader and
its placement on the device), from the host plane of the trace. Near 0
while the loader keeps ahead of the step; what an input-bound job would
show here is lost from `train_tokens_per_s` one for one once it
exceeds the step's own slack."""

from benchmark.reduce import host_spans


def read(cell, ev):
    got = host_spans.of(cell, ev)
    if not got or not ev["trace"]["window_s"]:
        return None
    return 100.0 * host_spans.thread_seconds(
        ev["trace"], got["spans"], "train.loader_wait") \
        / ev["trace"]["window_s"]
