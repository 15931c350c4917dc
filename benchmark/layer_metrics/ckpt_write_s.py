"""Loop + checkpoints: `ckpt.write` on the writer thread (serialize,
write, seal of one snapshot, in the background): the median over the
writes that had finished when the trainer last flushed its spans. It
moves the rate only when it outlasts a save period: the next save then
waits for it (12.6-16 s stalls, PERF.md §6)."""

from statistics import median

from benchmark.reduce import host_spans


def read(cell, ev):
    spans = [r for r in host_spans.records(cell.trace_dir)
             if r["name"] == "ckpt.write"]
    return median(r["dur"] for r in spans) if spans else None
