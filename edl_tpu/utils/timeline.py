"""Env-gated per-stage host timeline profiler — a shim over the obs
plane since the observability PR.

Capability of the reference's distill timeline (distill/timeline.py:20-43:
``DISTILL_READER_PROFILE=1`` swaps a nop for a real recorder emitting
``pid/op/ms`` lines to stderr, hooked at every pipeline stage). Ours is
``EDL_TPU_PROFILE=1``; its one user is the distill reader
(``distill/reader.py``). The trainer's spans (loop, checkpoints,
start-up) go through ``obs.trace.span`` directly, and the device trace
is ``TrainLoop``'s profile window.

    tl = timeline("distill.worker")      # nop unless profiling/tracing
    with tl.span("predict"):
        ...

Sinks (the r19 hot-path fix — the old ``_RealTimeline.record`` did an
UNBUFFERED per-event ``print`` to stderr, a measurable syscall tax on
the distill reader's per-batch path):

- obs span plane: with ``EDL_TPU_TRACE`` on, every timeline op becomes
  a finished span in the process's trace sink (merged/viewed by
  ``python -m edl_tpu.obs trace``), parented onto whatever span is
  current — a ckpt write inside a resize trace lands inside the trace;
- flight recorder ring: every op is an always-on bounded ring event
  (``obs/recorder.py``) so a crash dump shows the last operations;
- stderr (``EDL_TPU_PROFILE=1``, the back-compat sink selection): the
  same ``timeline pid=... op ms`` lines, now BATCHED through a small
  buffer flushed every `_FLUSH_EVERY` lines and at exit.
"""

from __future__ import annotations

import atexit
import contextlib
import os
import sys
import threading
import time

from edl_tpu.obs import recorder as _flight
from edl_tpu.obs import trace as _trace
from edl_tpu.utils import config


class _NopTimeline:
    __slots__ = ()

    def span(self, op: str):
        return contextlib.nullcontext()

    def record(self, op: str, start: float) -> None:
        pass

    enabled = False


# -- buffered stderr sink (EDL_TPU_PROFILE=1) -------------------------------

_FLUSH_EVERY = 64
_buf_lock = threading.Lock()
_buf: list[str] = []         # guarded-by: _buf_lock
_atexit_armed = False        # guarded-by: _buf_lock


def _flush_stderr() -> None:
    with _buf_lock:
        lines, _buf[:] = list(_buf), []
    if lines:
        try:
            sys.stderr.write("\n".join(lines) + "\n")
            sys.stderr.flush()
        except (OSError, ValueError):
            pass


def _stderr_line(line: str) -> None:
    global _atexit_armed
    flush = False
    with _buf_lock:
        _buf.append(line)
        if not _atexit_armed:
            _atexit_armed = True
            atexit.register(_flush_stderr)
        flush = len(_buf) >= _FLUSH_EVERY
    if flush:
        _flush_stderr()


class _ObsTimeline:
    """Real timeline: routes every op into the obs planes (see module
    docstring). Construction is gated, so the hot path of a process
    with neither knob set stays the zero-cost nop."""

    __slots__ = ("name", "_stderr")
    enabled = True

    def __init__(self, name: str):
        self.name = name
        self._stderr = profiling_enabled()

    @contextlib.contextmanager
    def span(self, op: str):
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.record(op, t0)

    def record(self, op: str, start: float) -> None:
        dur_s = time.monotonic() - start
        full = f"{self.name}.{op}"
        _trace.event(full, dur_s)   # span plane (no-op when trace off)
        _flight.record("timeline", op=full, ms=round(dur_s * 1e3, 3))
        if self._stderr:
            _stderr_line(f"timeline pid={os.getpid()} {full} "
                         f"{dur_s * 1e3:.3f}ms")


def profiling_enabled() -> bool:
    return config.env_flag("EDL_TPU_PROFILE", False)


def timeline(name: str):
    """Nop unless EDL_TPU_PROFILE=1 or EDL_TPU_TRACE is on (zero
    overhead on the hot path either way — the nop is attribute-free,
    and the real sink batches instead of printing per event)."""
    if profiling_enabled() or _trace.enabled():
        return _ObsTimeline(name)
    return _NopTimeline()
