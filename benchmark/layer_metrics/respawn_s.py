"""Elastic control: SIGKILL of the trainer -> the first log line of the
trainer the launcher started in its place, on the benchmark's clock."""


def read(cell, ev):
    return ev.get("resume", {}).get("respawn_s")
