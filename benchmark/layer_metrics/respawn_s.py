"""Elastic control: SIGKILL of the trainer -> the first log line of the
first trainer the launcher started in its place that wrote one, on the
benchmark's clock: the launcher's part of a resume, whether or not that
trainer went on to a first step (`resume_s` spans the generations lost
after it)."""


def read(cell, ev):
    return ev.get("resume", {}).get("respawn_s")
