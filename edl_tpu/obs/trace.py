"""Causal spans: Dapper-style trace propagation across the wire seams.

A *span* is a named, timed operation; spans carry ``(trace_id,
span_id)`` and parent onto whatever context is current on their thread
— or onto an explicit remote context extracted from a wire frame. Both
wire planes propagate context in-band: ``coord/wire.py`` attaches a
``"_tc"`` key to request frames, ``data/tensor_wire.py`` attaches it to
the JSON header's ``meta``. One resize therefore becomes ONE causally
linked tree across every process it touches:

    resize.request (scaler/demo)                     <- root
      resize.actuate (JobServer /resize)             <- HTTP header hop
        store.put (epoch publication)                <- coord wire hop
        resize.adopt (surviving trainer)             <- epoch-doc hop
          resize.first_fresh_util                    <- util publisher
        resize.restore_peers (grown pod)
          migrate.fetch x chunks                     <- tensor wire hop
            migrate.serve_fetch (donor process)

Two switches turn spans on; with neither, a span site is a single
cached read + ``if`` (no record, no clock, no file):

- ``EDL_TPU_TRACE`` — unset/0 = off, ``1`` = on with the default sink
  directory ``./edl_trace``, any other value = on with that value as the
  sink directory. Every process appends each finished span to its own
  ``spans-<pid>.jsonl`` in the sink dir and flushes it (control-plane
  use: pods die by signal).
- a profile directory — ``EDL_TPU_PROFILE_DIR`` in the environment, or
  :func:`collect` called by an entry point that parsed ``--profile``.
  Finished spans are kept in memory from then on and written to
  ``<profile_dir>/spans-<pid>.jsonl`` only by :func:`flush` (the clock
  sampler's thread calls it about once a second, and at once when the
  train loop asks through :func:`flush_soon` after a save; the loop
  itself when its profiler stops; also at exit and on SIGTERM): no
  write per span on the loop's thread, and a SIGKILL loses the last
  second at most.

While spans are on, one daemon thread watches the host's clock beside
the program (:func:`_sample_clock`): it sleeps a fixed tick, and a tick
that comes back late leaves a ``host.clock_gap`` span, so that a stall
of the machine, of the process or of the interpreter lock is a record
and not a guess; its first record, ``host.clock_sampler``, says that it
watched. With neither switch the thread does not exist.

One clock: a span's ``t0`` is wall-clock (files of several processes
merge) and its ``dur`` is a ``perf_counter`` difference. While a device
profiler runs, whoever started it installs an annotator
(:func:`set_annotator`) and every scoped span opened on any thread of
the process is also an event on the host plane of the profiler's own
file, on the clock its device lines use. ``obs/`` itself never imports
JAX: the annotator is a callable ``(name, attrs) -> context manager``.

A bounded in-process ring keeps the most recent spans readable without
file I/O (tests). ``python -m edl_tpu.obs
trace <dir>`` merges the files into per-trace trees and exports
Chrome-trace/Perfetto JSON.

Pure stdlib, jax/numpy-free (layers.toml obs row).
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import json
import os
import signal
import threading
import time
from typing import Any

from edl_tpu.utils import config

DEFAULT_DIR = "edl_trace"
RING_CAP = 4096
# spans kept for the next flush() under a profile directory: three a
# step plus a few a save, so hours of steps; the oldest go first
PENDING_CAP = 1 << 16

_tls = threading.local()
# re-entrant: the SIGTERM handler flushes on whatever the main thread
# was doing, an _emit included
_lock = threading.RLock()
_ring: collections.deque = collections.deque(maxlen=RING_CAP)
_pending: collections.deque = collections.deque(maxlen=PENDING_CAP)
_file = None          # guarded-by: _lock
_file_pid = None      # guarded-by: _lock (fork detection)
# (enabled, sink_dir, buffered): buffered = the sink is a profile
# directory, written by flush() alone
_cached: tuple[bool, str | None, bool] | None = None
_exit_armed = False
_annotate = None      # (name, attrs) -> context manager, or None

# The clock sampler: constants, not knobs. A tick this many ticks late
# is a gap (a busy but healthy host wakes a sleeper within a tick).
TICK_S = 0.05
GAP_TICKS = 4
FLUSH_EVERY_S = 1.0
SAMPLER_THREAD = "edl-clock-sampler"
SAMPLER_MARK = "host.clock_sampler"
# guarded-by: _lock; (thread, its wake event, its stop event)
_sampler = None


def _setting() -> tuple[bool, str | None, bool]:
    """(enabled, sink_dir, buffered) — parsed once per process; tests
    reset via `reconfigure()`."""
    global _cached
    if _cached is None:
        raw = (config.env_str("EDL_TPU_TRACE") or "").strip()
        profile_dir = (config.env_str("EDL_TPU_PROFILE_DIR") or "").strip()
        if raw and raw.lower() not in ("0", "false", "no", "off"):
            _cached = (True, DEFAULT_DIR if raw.lower() in (
                "1", "true", "yes", "on") else raw, False)
        elif profile_dir:
            _cached = (True, profile_dir, True)
            _arm_exit_flush()
        else:
            _cached = (False, None, False)
        if _cached[0]:
            _start_sampler()
    return _cached


def collect(profile_dir: str) -> None:
    """Switch spans on for a process that was given a profile directory
    on its command line (the environment's ``EDL_TPU_PROFILE_DIR`` needs
    no call). ``EDL_TPU_TRACE``, where set, keeps its own sink."""
    global _cached
    if not _setting()[0]:
        _cached = (True, profile_dir, True)
        _arm_exit_flush()
        _start_sampler()


def _arm_exit_flush() -> None:
    """flush() at interpreter exit, and on a SIGTERM nobody handles (a
    trainer without a checkpoint directory dies of it)."""
    global _exit_armed
    if _exit_armed:
        return
    _exit_armed = True
    atexit.register(flush)
    try:
        if signal.getsignal(signal.SIGTERM) is signal.SIG_DFL:
            signal.signal(signal.SIGTERM, _flush_and_die)
    except ValueError:  # not the main thread: exit alone flushes
        pass


def _flush_and_die(signum, frame) -> None:
    flush()
    signal.signal(signum, signal.SIG_DFL)
    os.kill(os.getpid(), signum)


def _start_sampler() -> None:
    """One clock sampler a process, started where spans switch on."""
    global _sampler
    with _lock:
        if _sampler is not None:
            return
        wake, stop = threading.Event(), threading.Event()
        thread = threading.Thread(target=_sample_clock, args=(wake, stop),
                                  name=SAMPLER_THREAD, daemon=True)
        _sampler = (thread, wake, stop)
        thread.start()


def _stop_sampler() -> None:
    global _sampler
    with _lock:
        sampler, _sampler = _sampler, None
    if sampler is not None:
        thread, wake, stop = sampler
        stop.set()
        wake.set()
        thread.join(timeout=10 * TICK_S)


def flush_soon() -> None:
    """Have the sampler's thread flush now, not at its next second: for
    a record that a SIGKILL may follow within milliseconds (a save's
    snapshot, whose step line a supervisor may act on). No write on the
    caller's thread; nothing where spans are off."""
    sampler = _sampler
    if sampler is not None:
        sampler[1].set()


def _sample_clock(wake: threading.Event, stop: threading.Event) -> None:
    """The sampler's thread: sleep a tick, see when it came back. A tick
    more than ``GAP_TICKS`` ticks late becomes a finished span
    ``host.clock_gap`` (``t0`` when the tick was due, ``dur`` how late it
    was). Its one attribute is what tells the causes apart on the chip's
    host: ``cpu_s``, this process's CPU seconds over the tick, is about 0
    when nothing of ours ran (the machine stood still, or the process
    was stopped or kept off the cores) and about the gap or more when
    one of our threads ran all through it and kept the interpreter lock
    from this one. (The kernel there keeps neither a thread's run-queue
    delay nor ``/proc/pressure``, so neither is read; PERF.md §6, PR 38.)

    The same thread flushes what is buffered about once a second, and at
    once when :func:`flush_soon` wakes it. Neither blinds it: a wake
    that comes back late is as late as a tick that does (an early one
    reads negative), and the next tick is due from before the flush, so
    a write that stood still shows as a gap too.

    Its first record is ``host.clock_sampler``, of no duration: a reader
    then knows that a window without gaps was watched."""
    _, _, buffered = _setting()
    event(SAMPLER_MARK, 0.0)
    cpu = time.process_time()
    due = time.monotonic() + TICK_S
    next_flush = due + FLUSH_EVERY_S
    while True:
        woken = wake.wait(max(0.0, due - time.monotonic()))
        if stop.is_set():
            return
        now = time.monotonic()
        cpu, cpu_before = time.process_time(), cpu
        if now - due > GAP_TICKS * TICK_S:
            event("host.clock_gap", now - due, t0=time.time() - (now - due),
                  attrs={"cpu_s": round(cpu - cpu_before, 6)})
        due = now + TICK_S
        if woken:
            wake.clear()
        if buffered and (woken or now >= next_flush):
            next_flush = now + FLUSH_EVERY_S
            flush()


def reconfigure() -> None:
    """Re-read the environment and drop the sink file handle, the ring,
    what waits for a flush, the annotator and the clock sampler (tests
    flip the env mid-process; real processes never need this)."""
    global _cached, _file, _file_pid, _annotate
    _stop_sampler()
    with _lock:
        _cached = None
        _annotate = None
        if _file is not None:
            try:
                _file.close()
            except OSError:
                pass
        _file = None
        _file_pid = None
        _ring.clear()
        _pending.clear()


def enabled() -> bool:
    return _setting()[0]


def sink_dir() -> str | None:
    return _setting()[1]


def set_annotator(fn) -> None:
    """Install (or with None remove) the callable that turns a scoped
    span's ``(name, attrs)`` into a context manager entered with it.
    The train loop installs ``jax.profiler.TraceAnnotation`` for as long
    as its profiler runs, which puts the span into the profiler's file."""
    global _annotate
    _annotate = fn


def _new_id() -> str:
    return os.urandom(8).hex()


def current() -> tuple[str, str] | None:
    """The active ``(trace_id, span_id)`` on this thread, or None."""
    return getattr(_tls, "ctx", None)


def _emit(record: dict) -> None:
    _ring.append(record)
    _, directory, buffered = _setting()
    if directory is None:
        return
    if buffered:
        _pending.append(record)   # flush() writes it
        return
    _write(directory, [record])


def _write(directory: str, records: list[dict]) -> None:
    global _file, _file_pid
    text = "".join(json.dumps(r, separators=(",", ":"), default=str) + "\n"
                   for r in records)
    with _lock:
        if _file is None or _file_pid != os.getpid():
            # per-process file: concurrent writers never interleave, and
            # a fork (mp loader workers) gets its own file not a shared fd
            try:
                os.makedirs(directory, exist_ok=True)
                _file = open(os.path.join(
                    directory, f"spans-{os.getpid()}.jsonl"), "a")
                _file_pid = os.getpid()
            except OSError:
                return
        try:
            _file.write(text)
            _file.flush()   # pods die by signal mid-demo: don't buffer
        except (OSError, ValueError):
            pass


def flush() -> None:
    """Write the spans finished since the last flush to the profile
    directory's ``spans-<pid>.jsonl``. A no-op under ``EDL_TPU_TRACE``
    (every span is on disk already) and when spans are off."""
    _, directory, buffered = _setting()
    if not buffered:
        return
    with _lock:
        records = []
        while _pending:
            records.append(_pending.popleft())
        if records:
            _write(directory, records)


class Span:
    """A started span; ``end()`` stamps the duration and emits it.
    Returned by :func:`start_span` for operations that end on another
    thread or at a later callback (the in-place adoption gap)."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "t0",
                 "attrs", "_done", "_p0")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: str | None, attrs: dict | None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = time.time()             # wall: files of processes merge
        self._p0 = time.perf_counter()    # the duration's clock
        self.attrs = dict(attrs or {})
        self._done = False

    @property
    def context(self) -> tuple[str, str]:
        return (self.trace_id, self.span_id)

    def end(self, **attrs: Any) -> None:
        if self._done:
            return
        self._done = True
        self.attrs.update(attrs)
        _emit({"tid": self.trace_id, "sid": self.span_id,
               "parent": self.parent_id, "name": self.name,
               "pid": os.getpid(),
               "thread": threading.current_thread().name,
               "t0": round(self.t0, 6),
               "dur": round(time.perf_counter() - self._p0, 6),
               "attrs": self.attrs})


def start_span(name: str, parent: tuple[str, str] | None = None,
               attrs: dict | None = None) -> Span | None:
    """Begin a span (None when tracing is off). Does NOT alter the
    thread's current context — use :func:`span` for scoped work."""
    if not enabled():
        return None
    ctx = parent if parent is not None else current()
    if ctx is not None:
        trace_id, parent_id = ctx
    else:
        trace_id, parent_id = _new_id(), None
    return Span(name, trace_id, _new_id(), parent_id, attrs)


@contextlib.contextmanager
def span(name: str, parent: tuple[str, str] | None = None,
         attrs: dict | None = None):
    """Scoped span: children started inside the body (this thread, or
    remote via the wire seams) parent onto it. Yields the Span (None
    when tracing is off) so callers can add attrs."""
    if not enabled():
        yield None
        return
    s = start_span(name, parent=parent, attrs=attrs)
    prev = current()
    _tls.ctx = s.context
    annotate = _annotate
    try:
        if annotate is None:
            yield s
        else:
            # attributes known at entry ride into the profiler's file;
            # those added through the yielded Span reach the record only
            with annotate(name, s.attrs):
                yield s
    finally:
        _tls.ctx = prev
        s.end()


def instant(name: str, parent: tuple[str, str] | None = None,
            attrs: dict | None = None) -> None:
    """Zero-duration marker span (the 'first fresh util' tick)."""
    s = start_span(name, parent=parent, attrs=attrs)
    if s is not None:
        s.end()


def event(name: str, dur_s: float,
          parent: tuple[str, str] | None = None,
          attrs: dict | None = None,
          t0: float | None = None) -> tuple[str, str] | None:
    """Emit a pre-measured finished span (the operation already
    happened: start-up phases, the launcher's reform, the timeline
    shim). It ended now unless ``t0`` (wall-clock) says when it began.
    Parents onto the current/explicit context like any other span and
    returns its own context, so that children measured the same way can
    be emitted under it (None when spans are off)."""
    if not enabled():
        return None
    ctx = parent if parent is not None else current()
    if ctx is not None:
        trace_id, parent_id = ctx
    else:
        trace_id, parent_id = _new_id(), None
    if t0 is None:
        t0 = time.time() - dur_s
    span_id = _new_id()
    _emit({"tid": trace_id, "sid": span_id, "parent": parent_id,
           "name": name, "pid": os.getpid(),
           "thread": threading.current_thread().name,
           "t0": round(t0, 6), "dur": round(dur_s, 6),
           "attrs": dict(attrs or {})})
    return (trace_id, span_id)


class Phases:
    """Consecutive phases of one rare operation whose log line is
    written whether spans are on or not (a trainer's start-up, a
    launcher's reform): stamped as each ends, then emitted after the
    fact as ``name`` with a child ``prefix.<phase>`` each. Unlike a span
    site this reads the clock always, so it is not for a hot path."""

    def __init__(self, name: str, prefix: str, age_s: float = 0.0,
                 attrs: dict | None = None):
        self.name, self.prefix, self.attrs = name, prefix, attrs
        self._p0 = time.perf_counter() - age_s   # began ``age_s`` ago
        self._t0 = time.time() - age_s
        self._ends: list[tuple[str, float, dict | None, bool]] = []

    def done(self, phase: str, attrs: dict | None = None) -> None:
        """``phase`` began when the one before it ended, and ends now."""
        self._ends.append((phase, time.perf_counter(), attrs, False))

    def mark(self, what: str, attrs: dict | None = None) -> None:
        """An instant inside the operation (a child of no duration)."""
        self._ends.append((what, time.perf_counter(), attrs, True))

    def emit(self) -> tuple[float, str]:
        """(seconds in all, "<phase> <s>s, ..." for the log line)."""
        total = (self._ends[-1][1] if self._ends else self._p0) - self._p0
        ctx = event(self.name, total, t0=self._t0, attrs=self.attrs)
        at, parts = self._p0, []
        for phase, end, attrs, instant in self._ends:
            begin = end if instant else at
            event(f"{self.prefix}.{phase}", end - begin, parent=ctx,
                  t0=self._t0 + begin - self._p0, attrs=attrs)
            if not instant:
                parts.append(f"{phase} {end - at:.3f}s")
                at = end
        return total, ", ".join(parts)


def process_age_s() -> float | None:
    """Seconds since the kernel started this process (its start time in
    /proc/self/stat against the boot clock): what a span that begins at
    process start needs. None where /proc does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return age if 0 <= age < 86400 else None


@contextlib.contextmanager
def adopt(ctx):
    """Make a remote context current for the body (no span of its own):
    spans opened inside parent onto the remote span. ``ctx`` may be
    None or malformed (straight off a wire frame) — then it's a no-op."""
    ctx = parse_context(ctx)
    if ctx is None or not enabled():
        yield
        return
    prev = current()
    _tls.ctx = ctx
    try:
        yield
    finally:
        _tls.ctx = prev


def parse_context(raw) -> tuple[str, str] | None:
    """Validate a wire-shaped context (list/tuple of two id strings) —
    garbled frames yield None, never an exception."""
    if (isinstance(raw, (list, tuple)) and len(raw) == 2
            and all(isinstance(x, str) and 0 < len(x) <= 64 for x in raw)):
        return (raw[0], raw[1])
    return None


def inject() -> list[str] | None:
    """The current context in wire shape (``["tid", "sid"]``), or None
    when tracing is off / no span is active."""
    ctx = current() if enabled() else None
    return [ctx[0], ctx[1]] if ctx is not None else None


def attach(d: dict) -> dict:
    """Copy-on-write attach of the current context to a wire dict under
    the reserved ``"_tc"`` key (both wire planes call this on their
    send path). Returns ``d`` untouched when there is nothing to add."""
    ctx = inject()
    if ctx is None or "_tc" in d:
        return d
    out = dict(d)
    out["_tc"] = ctx
    return out


def extract(d: dict) -> tuple[str, str] | None:
    """Pop the propagated context off a received wire dict (request
    msg or tensor-frame meta); tolerant of absence and garbling."""
    if not isinstance(d, dict):
        return None
    return parse_context(d.pop("_tc", None))


def finished(prefix: str | None = None) -> list[dict]:
    """Snapshot of the in-process ring of finished spans (newest last),
    optionally filtered by name prefix."""
    spans = list(_ring)
    if prefix is not None:
        spans = [s for s in spans if s["name"].startswith(prefix)]
    return spans


def clear_ring() -> None:
    _ring.clear()


# -- merged-trace analysis (CLI `python -m edl_tpu.obs trace` reads
#    through these) --

def load_spans(directory: str) -> list[dict]:
    """Every span from every ``spans-*.jsonl`` in ``directory``
    (garbled lines skipped — a killed pod can tear its last write)."""
    out: list[dict] = []
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return out
    for fname in names:
        if not (fname.startswith("spans-") and fname.endswith(".jsonl")):
            continue
        try:
            with open(os.path.join(directory, fname)) as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(rec, dict) and "tid" in rec:
                        out.append(rec)
        except OSError:
            continue
    return out


def group_traces(spans: list[dict]) -> dict[str, list[dict]]:
    """trace_id -> spans sorted by start time."""
    traces: dict[str, list[dict]] = {}
    for s in spans:
        traces.setdefault(s["tid"], []).append(s)
    for tid in traces:
        traces[tid].sort(key=lambda s: s.get("t0", 0.0))
    return traces


def span_tree(spans: list[dict]) -> list[tuple[dict, int]]:
    """Depth-first (span, depth) ordering of one trace's spans.
    Orphans (parent span lost — a killed process) surface at depth 0
    rather than disappearing."""
    by_id = {s["sid"]: s for s in spans}
    children: dict[str | None, list[dict]] = {}
    for s in spans:
        parent = s.get("parent")
        if parent is not None and parent not in by_id:
            parent = None
        children.setdefault(parent, []).append(s)
    for v in children.values():
        v.sort(key=lambda s: s.get("t0", 0.0))
    out: list[tuple[dict, int]] = []

    def walk(parent_id, depth):
        for s in children.get(parent_id, []):
            out.append((s, depth))
            walk(s["sid"], depth + 1)

    walk(None, 0)
    return out


# The resize phase vocabulary: span-name prefixes -> the budget phase
# they account to (doc/design_obs.md has the full catalog).
RESIZE_PHASES = (
    ("decision", ("scaler.decide", "resize.request")),
    ("actuation", ("resize.actuate",)),
    ("restore", ("resize.adopt", "resize.restore_peers")),
    ("first_fresh_util", ("resize.first_fresh_util",)),
)


def resize_phase_summary(spans: list[dict]) -> list[dict]:
    """Per-resize-trace phase breakdown: every trace containing a
    resize-family span becomes ``{trace_id, spans, t0, phases: {phase:
    seconds}, downtime_s}`` where downtime_s is the restore-phase span
    time (the measured survivor gap / peer-restore wall time)."""
    out = []
    for tid, tspans in sorted(group_traces(spans).items()):
        names = {s["name"] for s in tspans}
        if not any(n.startswith(("resize.", "scaler.decide"))
                   for n in names):
            continue
        phases: dict[str, float] = {}
        for phase, prefixes in RESIZE_PHASES:
            total = sum(s.get("dur", 0.0) for s in tspans
                        if s["name"].startswith(prefixes))
            if total or any(s["name"].startswith(prefixes)
                            for s in tspans):
                phases[phase] = round(total, 6)
        restore = [s for s in tspans
                   if s["name"].startswith(("resize.adopt",
                                            "resize.restore_peers"))]
        out.append({
            "trace_id": tid,
            "spans": len(tspans),
            "t0": min(s.get("t0", 0.0) for s in tspans),
            "phases": phases,
            "downtime_s": round(max((s.get("dur", 0.0) for s in restore),
                                    default=0.0), 6)})
    return out


def to_chrome(spans: list[dict]) -> dict:
    """Chrome-trace ("Trace Event Format") JSON — loadable in
    chrome://tracing and Perfetto. Complete ("X") events; each trace id
    gets a synthetic thread lane so concurrent resizes don't stack."""
    events = []
    lanes: dict[str, int] = {}
    for s in sorted(spans, key=lambda s: s.get("t0", 0.0)):
        lane = lanes.setdefault(s["tid"], len(lanes) + 1)
        events.append({
            "name": s["name"], "ph": "X", "cat": "edl",
            "ts": round(s.get("t0", 0.0) * 1e6, 1),
            "dur": max(round(s.get("dur", 0.0) * 1e6, 1), 1.0),
            "pid": s.get("pid", 0), "tid": lane,
            "args": dict(s.get("attrs") or {},
                         trace_id=s["tid"], span_id=s["sid"])})
    return {"traceEvents": events, "displayTimeUnit": "ms"}
