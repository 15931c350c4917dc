"""Loop + checkpoints: summed over the saves of the measured window,
the seconds for which the `ckpt.write` before a save was still running
on the writer's thread after that save's `ckpt.snapshot` began: the
device-to-host fetch and the serialisation of 7 GB then share the host.
0 while the writer's margin holds (a write has a period less the
snapshot, ROADMAP S3); `writer_inflight` on the snapshot says the same
as a flag. A write the run's SIGKILL cut leaves no record: it counts from
its first finished phase (`ckpt.clean`) to the process's last record,
when it was still running."""

from benchmark.reduce import loop_periods


def read(cell, ev):
    got = loop_periods.of(cell, ev)
    return got["write_overlap_s"] if got else None
