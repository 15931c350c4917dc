"""Parsers of the program's own log lines (the sound ones, PERF.md §6).

Every line of the trainer carries its process id
("... edl_tpu.train.loop [1234] ..."), so each generation of the trainer
is read apart from the others. A parser takes (stamp, text) pairs as
`LogTail` keeps them and returns what it found, or nothing.
"""

from __future__ import annotations

import ast
import re

Lines = list[tuple[float, str]]

_STEP = re.compile(r"epoch (\d+) step (\d+): .*?loss=(\S+)")
_DEVICE = re.compile(r"device: platform=(\S+) kind='([^']*)' count=(\d+) "
                     r"attention=(\S+)")
_FIRST = re.compile(r"first-step-complete global_step=(\d+) restore_s=(\S+)")
_WALL = re.compile(r"first-step wall \(trace\+compile\+run\) ([\d.]+)s, "
                   r"persistent compile cache (\{.*\})")
_SEALED = re.compile(r"saved sharded checkpoint .*step=(\d+)\)")
_RESTORED = re.compile(r"restored checkpoint .*step=(\d+)\) in ([\d.]+)s")
_STARTED = re.compile(r"started trainer rank=0 pid=(\d+)")


def of_pid(lines: Lines, pid: int) -> Lines:
    tag = f"[{pid}]"
    return [(t, ln) for t, ln in lines if tag in ln]


def steps(lines: Lines) -> list[tuple[float, int, float]]:
    """(stamp, global step, loss) of every step line."""
    out = []
    for t, ln in lines:
        m = _STEP.search(ln)
        if m:
            out.append((t, int(m.group(2)), float(m.group(3))))
    return out


def _first(pattern, lines: Lines):
    for t, ln in lines:
        m = pattern.search(ln)
        if m:
            return t, m
    return None


def device(lines: Lines) -> dict | None:
    got = _first(_DEVICE, lines)
    if got is None:
        return None
    m = got[1]
    return {"platform": m.group(1), "kind": m.group(2),
            "count": int(m.group(3)), "attention": m.group(4)}


def first_step_complete(lines: Lines) -> dict | None:
    """The line that follows a `block_until_ready` of the first step."""
    got = _first(_FIRST, lines)
    if got is None:
        return None
    t, m = got
    restore = None if m.group(2) == "none" else float(m.group(2))
    return {"t": t, "global_step": int(m.group(1)), "restore_s": restore}


def first_step_wall(lines: Lines) -> dict | None:
    got = _first(_WALL, lines)
    if got is None:
        return None
    cache = ast.literal_eval(got[1].group(2))
    return {"first_step_s": float(got[1].group(1)),
            "hits": int(cache.get("hits", 0)),
            "misses": int(cache.get("misses", 0))}


def sealed(lines: Lines) -> list[tuple[float, int]]:
    return [(t, int(m.group(1))) for t, ln in lines
            for m in [_SEALED.search(ln)] if m]


def restored(lines: Lines) -> list[tuple[int, float]]:
    return [(int(m.group(1)), float(m.group(2))) for _, ln in lines
            for m in [_RESTORED.search(ln)] if m]


def trace_written(lines: Lines) -> float | None:
    """Stamp of the line the loop writes once its profiler has stopped
    and written its file."""
    for t, ln in lines:
        if "profiler: trace written" in ln:
            return t
    return None


def started_trainers(lines: Lines) -> list[tuple[float, int]]:
    return [(t, int(m.group(1))) for t, ln in lines
            for m in [_STARTED.search(ln)] if m]


def after_start(lines: Lines, pid: int) -> list[str]:
    """What the launcher wrote after it started trainer ``pid`` and
    before it started the next: why that generation ended, if it says."""
    out = None
    for _, ln in lines:
        m = _STARTED.search(ln)
        if m and out is not None:
            break
        if m and int(m.group(1)) == pid:
            out = []
        elif out is not None:
            out.append(ln)
    return out or []
