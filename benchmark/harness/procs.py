"""Children of a parent that never touches JAX: start, watch, stop.

Copied from `chip_smoke.py` (spawn in an own session, /proc scans, group
kills) with one addition: `LogTail`, which stamps every line of a child's
log with this process's monotonic clock as it appears, so that the
benchmark times the program's progress on its own clock.
"""

from __future__ import annotations

import ctypes
import glob
import os
import signal
import socket
import subprocess
import sys
import threading
import time

PY = sys.executable
_procs: list[subprocess.Popen] = []


class BenchFailure(Exception):
    """The run cannot give a result (exit code 1, no result line)."""


class Refused(Exception):
    """No accelerator the cell can run on (exit code 3, no result line)."""


T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def connects(port: int) -> bool:
    try:
        socket.create_connection(("127.0.0.1", port), timeout=1).close()
        return True
    except OSError:
        return False


_libc = ctypes.CDLL(None, use_errno=True)
_PR_SET_PDEATHSIG = 1


def _end_with(parent: int):
    """A `preexec_fn`: the child asks the kernel for SIGKILL when the
    thread that started it ends. `stop_all` ends what this process
    started, but a process that is itself killed (the check's time
    limit, a memory guard) ends without a word to its children, and a
    child that holds the chip would keep it from the next run. The
    request outlives the exec; the kernel ties it to the starting
    thread, so `spawn` is called from the main thread only."""
    def ask() -> None:
        _libc.prctl(_PR_SET_PDEATHSIG, int(signal.SIGKILL), 0, 0, 0)
        if os.getppid() != parent:  # it ended before the call
            os._exit(1)
    return ask


def spawn(cmd: list[str], log_path: str, env: dict, cwd: str,
          stdout_path: str | None = None) -> subprocess.Popen:
    """Start a child in its own session, all output to ``log_path``
    (its standard output to ``stdout_path`` where that is given). The
    child ends with this process, whatever ends this process."""
    with open(log_path, "ab") as err, \
            open(stdout_path or log_path, "ab") as out:
        proc = subprocess.Popen(cmd, cwd=cwd, env=env, stdout=out,
                                stderr=err if stdout_path
                                else subprocess.STDOUT,
                                start_new_session=True,
                                preexec_fn=_end_with(os.getpid()))
    _procs.append(proc)
    return proc


def alive(pid: int) -> bool:
    """A process that exists and is not a zombie awaiting its parent.
    Existence is the kernel's word (signal 0); /proc only adds whether it
    is a zombie, and a /proc read that fails says nothing."""
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        pass
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except FileNotFoundError:
        return False
    except (OSError, IndexError):
        return True


def pids_matching(*needles: str, without: str = "\0") -> list[int]:
    """Live processes whose command line holds every needle, and not
    ``without``."""
    out = []
    for d in glob.glob("/proc/[0-9]*"):
        pid = int(d[6:])
        try:
            with open(d + "/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue
        if all(n in cmd for n in needles) and without not in cmd \
                and pid != os.getpid() and alive(pid):
            out.append(pid)
    return out


def kill_group(pid: int, sig: int = signal.SIGKILL) -> None:
    try:
        os.killpg(os.getpgid(pid), sig)
    except (ProcessLookupError, PermissionError):
        pass


def wait_gone(pids: list[int], timeout: float = 30.0) -> None:
    """Until none of ``pids`` lives; reaps this process's own children."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for p in _procs:
            p.poll()
        if not any(alive(pid) for pid in pids):
            return
        time.sleep(0.05)
    raise BenchFailure(f"processes {[p for p in pids if alive(p)]} "
                       f"outlived SIGKILL by {timeout:.0f}s")


def stop_all() -> None:
    """Nothing this process started is left: kill every group, then wait."""
    for p in _procs:
        if p.poll() is None:
            kill_group(p.pid)
    for p in _procs:
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass


def wait_for(probe, timeout: float, what: str, proc=None, poll=0.01):
    """Poll ``probe`` until it returns something truthy; give up when
    ``proc`` (whose doing it is) has exited."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = probe()
        if got:
            return got
        if proc is not None and proc.poll() is not None:
            raise BenchFailure(f"{what}: process exited rc={proc.poll()}")
        time.sleep(poll)
    raise BenchFailure(f"timed out after {timeout:.0f}s waiting for {what}")


class LogTail:
    """Follows a log file from a thread; `lines` holds (monotonic seconds
    at which the line was seen, text) in order. Polls every 2 ms, so a
    stamp is late by at most that plus the writer's own flush."""

    def __init__(self, path: str, poll: float = 0.002):
        self.path = path
        self.lines: list[tuple[float, str]] = []
        self._stop = threading.Event()
        self._poll = poll
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        f = None
        rest = b""
        while not self._stop.is_set():
            if f is None:
                try:
                    f = open(self.path, "rb")
                except FileNotFoundError:
                    time.sleep(self._poll)
                    continue
            chunk = f.read()
            if not chunk:
                time.sleep(self._poll)
                continue
            now = time.monotonic()
            *whole, rest = (rest + chunk).split(b"\n")
            for raw in whole:
                self.lines.append((now, raw.decode(errors="replace")))
        if f is not None:
            f.close()

    def close(self) -> None:
        self._stop.set()
        self._thread.join()

    def text(self, n: int = 40) -> str:
        return "\n".join(ln for _, ln in self.lines[-n:])
