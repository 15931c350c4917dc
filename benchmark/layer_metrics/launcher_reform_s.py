"""Elastic control: the launcher's `launch.reform`, the trainer's exit
seen -> the next trainer spawned (release and rejoin wait, barrier,
spawn), from the line the launcher writes once a respawn:

    reform: exit_seen->spawn 5.310s (rejoin_wait 3.262s, barrier 2.021s, spawn 0.003s)

The newest such line of the job's launcher log."""

import glob
import os
import re

LINE = re.compile(r"reform: exit_seen\S+spawn ([\d.]+)s \((.*)\)")


def read(cell, ev):
    found = []
    for path in glob.glob(os.path.join(cell.work, "**", "launcher.log"),
                          recursive=True):
        with open(path, errors="replace") as f:
            found += LINE.findall(f.read())
    return float(found[-1][0]) if found else None
