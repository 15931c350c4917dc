"""What a failed run leaves behind.

`run.py` deletes a run's work directory whatever its end, so a run that
exits 1 used to take with it the only record of why: the launcher's log
(which says why it ended a generation), the store's, the workers', the
spans and the TPU runtime's own logs. `keep` copies those to
`.bench_failed/<cell>.<seed>/` in the checkout before the directory
goes: never the checkpoints, the shards or the profiler's trace, at most
`MAX_BYTES` a run (of a file that does not fit, its end), and the newest
`KEEP_RUNS` runs.
"""

from __future__ import annotations

import os
import shutil

MAX_BYTES = 20 << 20
KEEP_RUNS = 4
LEFT_OUT = ("ckpt", "data")  # of the work directory: gigabytes each
FIRST = ("launcher.log", "store.log", "workerlog.")


def _small_files(work: str) -> list[str]:
    found = []
    for d, dirs, names in os.walk(work):
        rel = os.path.relpath(d, work)
        if rel == ".":
            dirs[:] = [x for x in dirs if x not in LEFT_OUT]
        in_trace = rel.split(os.sep)[0] == "trace"
        found += [os.path.join(d, n) for n in names if not in_trace
                  or n.startswith("spans-") and n.endswith(".jsonl")]
    # the logs that name a cause first, then the smallest
    return sorted(found, key=lambda p: (
        not os.path.basename(p).startswith(FIRST), os.path.getsize(p)))


def keep(work: str, root: str, cell: str, seed: int) -> str:
    """Copies the small files of ``work``; returns where they lie."""
    base = os.path.join(root, ".bench_failed")
    dest = os.path.join(base, f"{cell}.{seed}")
    shutil.rmtree(dest, ignore_errors=True)
    os.makedirs(dest)
    room = MAX_BYTES
    for path in _small_files(work):
        take = min(os.path.getsize(path), room)
        if take == 0:
            continue
        to = os.path.join(dest, os.path.relpath(path, work))
        os.makedirs(os.path.dirname(to), exist_ok=True)
        with open(path, "rb") as src, open(to, "wb") as out:
            src.seek(-take, os.SEEK_END)
            out.write(src.read(take))
        room -= take
    runs = sorted((os.path.join(base, d) for d in os.listdir(base)),
                  key=os.path.getmtime)
    for old in runs[:-KEEP_RUNS]:
        shutil.rmtree(old, ignore_errors=True)
    return dest


def tail(dest: str, name: str, n: int = 40) -> str:
    """The last ``n`` lines of the kept file ``name``, wherever it lies."""
    for d, _, names in os.walk(dest):
        if name in names:
            with open(os.path.join(d, name), errors="replace") as f:
                return "".join(f.readlines()[-n:]).rstrip("\n")
    return f"(no {name})"
