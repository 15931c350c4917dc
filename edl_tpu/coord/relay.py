"""Watch relay tier: fan-out proxy between the store and the fleet.

The 100k-pod control plane's multiplier (doc/design_coord.md): a
replicated follower sustains ~hundreds of direct watch streams, so the
relay subscribes **once upstream per distinct prefix** and
re-multiplexes that single stream to thousands of downstream watchers —
the shape of etcd's gRPC proxy watch coalescing. Downstreams speak the
exact store wire protocol (``RelayServer`` serves the same ``watch`` op
with the same ack/event/heartbeat frames), so a consumer cannot tell a
relay from a store server, and ``EDL_TPU_RELAY_ENDPOINTS`` re-points
every ``StoreClient.watch`` at the tier with no call-site changes.

Contract preserved end to end (the part that makes a relay safe):

- **Revision resume**: a downstream attaching at ``start_revision`` is
  fenced at it (``min_revision``) — nothing at or below is ever
  re-delivered, including by an upstream reconnect replay. Late
  attachers replay from the relay's bounded per-prefix history; a
  resume point older than the history window gets an explicit
  ``compacted`` batch (resync via ``get_prefix``), exactly as the store
  itself answers.
- **Commit gating**: the relay never invents resume anchors. Every
  revision it advertises (event frames, heartbeats) was first delivered
  by the upstream store, which only releases majority-committed
  revisions (r20's fan-out gate) — so an anchor can never name a doomed
  leader's uncommitted suffix, even through two hops.
- **Relay death == server restart**: downstream ``ClientWatch``
  reconnects with jittered backoff and resumes by revision; a restarted
  relay re-subscribes upstream from that revision and the store's event
  history replays the gap. Zero lost, zero duplicated — verified by
  ``selftest`` here and by tests/test_relay.py.

Layering: stdlib-only (layers.toml pins coord jax/numpy-free) — the
relay tier runs on scheduler nodes with no accelerator stack.
"""

from __future__ import annotations

import argparse
import socket
import socketserver
import threading
import time

from edl_tpu.coord import wire
from edl_tpu.coord.client import StoreClient
from edl_tpu.coord.store import WatchBatch
from edl_tpu.obs import metrics, trace
from edl_tpu.obs import recorder as flight
from edl_tpu.utils import config
from edl_tpu.utils.exceptions import EdlStoreError
from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.coord.relay")

# a downstream this many undrained batches behind is collapsed to a
# compacted resync instead of buffering without bound
_MAX_SUB_BATCHES = 256


def relay_buffer(default: int = 4096) -> int:
    """Per-prefix replay-history length (EDL_TPU_RELAY_BUFFER): events
    kept so late/resuming downstreams replay locally instead of each
    forcing a store round trip."""
    return max(64, config.env_int("EDL_TPU_RELAY_BUFFER", default))


class RelayWatch:
    """One downstream stream. Duck-types ``coord.store.Watch`` (get /
    progress_revision / cancel / cancelled / created_revision) but is
    deliberately not a subclass: ``__slots__`` plus a shared per-stream
    Condition keep a handle small enough that a million of them fit on
    one host (the --fleet simulation's in-proc cohort)."""

    __slots__ = ("_stream", "cond", "min_revision", "created_revision",
                 "_queue", "_cancelled")
    expiry_events = True

    def __init__(self, stream: "_Stream", min_revision: int,
                 created_revision: int):
        self._stream = stream
        self.cond = stream.cond  # SHARED per-stream Condition, not ours
        # resume fence: events at or below this were already in the
        # subscriber's hands before it attached — never re-deliver
        self.min_revision = min_revision
        self.created_revision = created_revision
        self._queue: list[WatchBatch] = []  # guarded-by: cond
        self._cancelled = False             # guarded-by: cond

    @property
    def prefix(self) -> str:
        return self._stream.prefix

    def get(self, timeout: float | None = None) -> WatchBatch | None:
        deadline = None if timeout is None else time.monotonic() + timeout
        with self.cond:
            while not self._queue and not self._cancelled:
                remaining = None if deadline is None \
                    else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return None
                self.cond.wait(remaining)
            if self._queue:
                return self._queue.pop(0)
            return None

    def progress_revision(self) -> int | None:
        with self.cond:
            if self._queue or self._cancelled:
                return None
            # the stream anchor came off upstream frames, which the
            # store commit-gates — safe to advertise downstream
            return self._stream.anchor

    def cancel(self) -> None:
        self._stream.detach(self)

    @property
    def cancelled(self) -> bool:
        return self._cancelled

    def __enter__(self) -> "RelayWatch":
        return self

    def __exit__(self, *exc) -> None:
        self.cancel()


class _Stream:
    """ONE upstream watch for one distinct prefix, re-multiplexed to
    every downstream subscribed to it."""

    def __init__(self, relay: "WatchRelay", prefix: str,
                 start_revision: int | None):
        self.relay = relay
        self.prefix = prefix
        self.cond = threading.Condition()
        self.subs: set[RelayWatch] = set()   # guarded-by: cond
        self.history: list = []              # guarded-by: cond
        self.closed = False                  # guarded-by: cond
        # Opened synchronously (ClientWatch blocks until the server
        # ack), so anchor/first_rev are real before the first attach
        # returns — "events after attach() returned" stays a guarantee
        # through the relay. via_relay=False: never watch through
        # yourself.
        self.upstream = relay._client.watch(
            prefix, start_revision=start_revision,
            heartbeat=relay.heartbeat, via_relay=False,
            on_resume=self._on_resume)
        self.anchor = self.upstream.created_revision  # guarded-by: cond
        base = start_revision if start_revision is not None else self.anchor
        self.first_rev = base + 1            # guarded-by: cond
        self._thread = threading.Thread(
            target=self._pump, daemon=True,
            name=f"relay-pump-{prefix or '/'}")
        self._thread.start()

    def _on_resume(self, revision: int) -> None:
        flight.record("relay_resume", prefix=self.prefix, revision=revision)
        self.relay._note_resume()
        log.info("relay stream %r resumed upstream at revision %d",
                 self.prefix, revision)

    # -- upstream side -------------------------------------------------------

    def _pump(self) -> None:
        up = self.upstream
        while True:
            batch = up.get(timeout=0.25)
            with self.cond:
                if self.closed:
                    return
            if batch is None:
                if up.cancelled:
                    return
                rev = up.progress_revision()
                if rev is not None:
                    with self.cond:
                        if rev > self.anchor:
                            self.anchor = rev
                continue
            self._deliver(batch)

    def _deliver(self, batch: WatchBatch) -> None:
        limit = self.relay.buffer
        fanned = 0
        with self.cond:
            if self.closed:
                return
            self.anchor = max(self.anchor, batch.revision)
            if batch.compacted:
                # upstream lost coverage: the relay's window is void
                # too — every downstream must resync via get_prefix
                self.history.clear()
                self.first_rev = batch.revision + 1
                resync = WatchBatch((), batch.revision, True)
                for sub in self.subs:
                    sub._queue.clear()
                    sub._queue.append(resync)
                self.cond.notify_all()
                return
            self.history.extend(batch.events)
            if len(self.history) > limit:
                drop = len(self.history) - limit
                self.first_rev = self.history[drop].revision
                del self.history[:drop]
            if batch.events:
                lo = batch.events[0].revision
                for sub in self.subs:
                    q = sub._queue
                    if len(q) >= _MAX_SUB_BATCHES:
                        # lagging downstream: collapse to a resync
                        q.clear()
                        q.append(WatchBatch((), batch.revision, True))
                        continue
                    if sub.min_revision < lo:
                        # fast path — the batch object is shared (it is
                        # frozen), so a 1M-subscriber fan-out appends one
                        # reference per sub, not one copy
                        q.append(batch)
                        fanned += len(batch.events)
                    else:
                        fit = tuple(ev for ev in batch.events
                                    if ev.revision > sub.min_revision)
                        if fit:
                            q.append(WatchBatch(fit, batch.revision))
                            fanned += len(fit)
            self.cond.notify_all()
        if fanned:
            self.relay._count_fanout(fanned)

    # -- downstream side -----------------------------------------------------

    def attach(self, start_revision: int | None) -> RelayWatch | None:
        """Subscribe; None when the stream closed under the caller
        (WatchRelay.attach retries with a fresh stream)."""
        with self.cond:
            if self.closed:
                return None
            anchor = self.anchor
            if start_revision is None:
                sub = RelayWatch(self, anchor, anchor)
            else:
                sub = RelayWatch(self, start_revision, anchor)
                if start_revision + 1 < self.first_rev:
                    # resume point predates the replay window: same
                    # explicit resync the store itself would answer
                    sub._queue.append(WatchBatch((), anchor, True))
                else:
                    replay = tuple(ev for ev in self.history
                                   if ev.revision > start_revision)
                    if replay:
                        sub._queue.append(WatchBatch(replay, anchor))
            self.subs.add(sub)
            return sub

    def detach(self, sub: RelayWatch) -> None:
        with self.cond:
            sub._cancelled = True
            self.subs.discard(sub)
            empty = not self.subs and not self.closed
            self.cond.notify_all()
        if empty:
            self.relay._maybe_close(self.prefix, self)

    def close(self) -> None:
        with self.cond:
            if self.closed:
                return
            self.closed = True
            for sub in self.subs:
                sub._cancelled = True
            self.subs.clear()
            self.cond.notify_all()
        self.upstream.cancel()


class WatchRelay:
    """The fan-out core (in-proc API; ``RelayServer`` puts it on the
    wire). ``attach(prefix, start_revision)`` returns a RelayWatch;
    distinct prefixes get one upstream stream each, shared by every
    subscriber of that prefix."""

    def __init__(self, upstream: str, buffer: int | None = None,
                 heartbeat: float = 2.0):
        self._client = StoreClient(upstream)
        self.buffer = buffer if buffer is not None else relay_buffer()
        self.heartbeat = heartbeat
        self._lock = threading.Lock()
        self._streams: dict[str, _Stream] = {}  # guarded-by: _lock
        self._fanout = 0                        # guarded-by: _lock
        self._resumes = 0                       # guarded-by: _lock
        self._closed = False                    # guarded-by: _lock
        self._obs = metrics.register_stats("relay", self.stats)

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed

    def attach(self, prefix: str = "",
               start_revision: int | None = None) -> RelayWatch:
        while True:
            with self._lock:
                if self._closed:
                    raise EdlStoreError("relay is closed")
                stream = self._streams.get(prefix)
            if stream is None:
                # dial upstream outside the relay lock (it can block on
                # a failing-over store); first creation wins
                stream = _Stream(  # lifecycle: long-lived(owned by _streams; relay.close or the losing-race branch closes it)
                    self, prefix, start_revision)
                with self._lock:
                    cur = None if self._closed \
                        else self._streams.setdefault(prefix, stream)
                if cur is not stream:
                    stream.close()
                    if cur is None:
                        raise EdlStoreError("relay is closed")
                    stream = cur
            sub = stream.attach(start_revision)
            if sub is not None:
                return sub
            with self._lock:  # stream closed under us: retry fresh
                if self._streams.get(prefix) is stream:
                    del self._streams[prefix]

    # Watch-provider shim: coord.server._Handler._serve_watch calls
    # ``store.watch(prefix, start_revision=...)`` — giving the relay the
    # same method lets RelayServer reuse the store server's watch loop
    # (ack, frame merging, heartbeats) verbatim.
    def watch(self, prefix: str = "",
              start_revision: int | None = None) -> RelayWatch:
        return self.attach(prefix, start_revision)

    def _maybe_close(self, prefix: str, stream: _Stream) -> None:
        with self._lock:
            with stream.cond:
                live = bool(stream.subs) or stream.closed
            if live or self._streams.get(prefix) is not stream:
                return
            del self._streams[prefix]
        stream.close()

    def _count_fanout(self, n: int) -> None:
        with self._lock:
            self._fanout += n

    def _note_resume(self) -> None:
        with self._lock:
            self._resumes += 1

    def stats(self) -> dict:
        with self._lock:
            streams = list(self._streams.values())
            fanout = self._fanout
            resumes = self._resumes
        downstreams = 0
        for st in streams:
            with st.cond:
                downstreams += len(st.subs)
        return {"relay_downstreams": downstreams,
                "relay_upstream_streams": len(streams),
                "relay_events_fanned_out": fanout,
                "relay_resumes": resumes}

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            streams = list(self._streams.values())
            self._streams.clear()
        for st in streams:
            st.close()
        self._client.close()
        metrics.unregister(self._obs)


class _RelayHandler(socketserver.BaseRequestHandler):
    def handle(self) -> None:
        relay: WatchRelay = self.server.relay  # type: ignore[attr-defined]
        sock: socket.socket = self.request
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        from edl_tpu.coord.server import _Handler
        while True:
            try:
                req = wire.recv_msg(sock)
            except (wire.WireError, OSError):
                return
            trace.extract(req)  # pop the caller's span context
            op = req.get("op")
            if op == "watch":
                if relay.closed:
                    # drop the connection instead of sending a refusal:
                    # a refusal is permanent to ClientWatch, but a dying
                    # relay should look like a restart (reconnect+resume)
                    return
                # the store server's watch loop, fed by the relay core
                _Handler._serve_watch(relay, sock, req, self.server)
                return
            if op == "ping":
                resp = {"ok": True}
            elif op == "status":
                resp = {"ok": True, "role": "relay", "leader": None,
                        "term": 0, **relay.stats()}
            else:
                # non-watch ops proxy to the store through the shared
                # upstream client (failover/redirect handled there);
                # typed errors re-encode so the subtype survives the
                # extra hop
                try:
                    resp = relay._client._call(**req)
                except EdlStoreError as exc:
                    resp = {"ok": False,
                            "error": f"{type(exc).__name__}: {exc}"}
            try:
                wire.send_msg(sock, resp)
            except OSError:
                return


class _ThreadingServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class RelayServer:
    """Wire front of the relay: same framed protocol + watch semantics
    as StoreServer, so ``StoreClient`` works against it unchanged."""

    def __init__(self, upstream: str, port: int = 0, host: str = "0.0.0.0",
                 buffer: int | None = None, heartbeat: float = 2.0):
        self.relay = WatchRelay(upstream, buffer=buffer, heartbeat=heartbeat)
        self._server = _ThreadingServer((host, port), _RelayHandler)
        self._server.relay = self.relay  # type: ignore[attr-defined]
        self._server.active_watches = set()  # type: ignore[attr-defined]
        self._server.watch_lock = threading.Lock()  # type: ignore[attr-defined]
        self.port = self._server.server_address[1]
        self._thread: threading.Thread | None = None

    def start(self) -> "RelayServer":
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="edl-relay-serve", daemon=True)
        self._thread.start()
        log.info("watch relay listening on :%d (upstream %s)", self.port,
                 self.relay._client._endpoint)
        return self

    def stop(self) -> None:
        # listener first: once it is gone, downstream reconnects bounce
        # (connection refused -> jittered backoff) instead of landing on
        # a relay that is mid-teardown
        self._server.shutdown()
        self._server.server_close()
        self.relay.close()
        with self._server.watch_lock:  # type: ignore[attr-defined]
            watches = list(self._server.active_watches)  # type: ignore[attr-defined]
        for watch in watches:
            watch.cancel()

    def __enter__(self) -> "RelayServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


# --------------------------------------------------------------------------
# CLI: serve + stdlib-only selftest


def selftest(verbose: bool = True) -> int:
    """End-to-end relay invariants over real sockets: per-prefix
    upstream coalescing, fan-out delivery, the min_revision resume
    fence, compacted propagation for stale resume points, and the
    relay-death-equals-restart contract (kill the relay mid-stream,
    restart it, zero lost / zero duplicated events). Pure stdlib —
    asserted, per layers.toml."""
    from edl_tpu.coord.server import StoreServer

    failures: list[str] = []

    def check(cond: bool, what: str) -> None:
        if verbose:
            print(("ok   " if cond else "FAIL ") + what)
        if not cond:
            failures.append(what)

    def drain(watch, want: int, timeout: float = 10.0) -> list:
        evs: list = []
        deadline = time.monotonic() + timeout
        while len(evs) < want and time.monotonic() < deadline:
            batch = watch.get(timeout=0.25)
            if batch is not None:
                evs.extend(batch.events)
        return evs

    srv = StoreServer(port=0, host="127.0.0.1").start()
    ep = f"127.0.0.1:{srv.port}"
    rs = RelayServer(ep, port=0, host="127.0.0.1").start()  # lifecycle: long-lived(selftest; stopped at the end, a failed check exits the process)
    relay_ep = f"127.0.0.1:{rs.port}"

    store = StoreClient(ep)
    downs = [StoreClient(relay_ep) for _ in range(3)]
    w_a1 = downs[0].watch("/a/", via_relay=False)
    w_a2 = downs[1].watch("/a/", via_relay=False)
    w_b = downs[2].watch("/b/", via_relay=False)

    revs = [store.put(f"/a/{i:03d}", str(i)) for i in range(10)]
    store.put("/b/x", "y")

    got1 = drain(w_a1, 10)
    got2 = drain(w_a2, 10)
    gotb = drain(w_b, 1)
    check([e.revision for e in got1] == revs,
          f"fan-out: downstream 1 saw all 10 events in order "
          f"(got {len(got1)})")
    check([e.revision for e in got2] == revs,
          "fan-out: downstream 2 saw the same stream")
    check(len(gotb) == 1 and gotb[0].key == "/b/x",
          "prefix isolation: /b/ watcher saw only its event")

    stats = rs.relay.stats()
    check(stats["relay_upstream_streams"] == 2,
          f"coalescing: 3 downstreams -> 2 upstream streams "
          f"(got {stats['relay_upstream_streams']})")
    check(stats["relay_downstreams"] == 3,
          f"stats: 3 downstreams tracked (got {stats['relay_downstreams']})")

    # resume fence: attach mid-history — nothing at or below the anchor
    # may be re-delivered
    anchor = revs[4]
    w_mid = StoreClient(relay_ep).watch("/a/", start_revision=anchor,
                                        via_relay=False)
    got_mid = drain(w_mid, 5)
    check([e.revision for e in got_mid] == revs[5:],
          f"min_revision fence: resume at rev {anchor} replays exactly "
          f"the 5 later events (got {[e.revision for e in got_mid]})")
    w_mid.cancel()

    # stale resume point (predates the relay stream's window): explicit
    # compacted resync, the same answer the store would give
    relay2 = WatchRelay(ep, buffer=64)
    sub = relay2.attach("/a/", start_revision=None)
    first_rev_gate = relay2.attach("/a/", start_revision=0)
    batch = first_rev_gate.get(timeout=5.0)
    check(batch is not None and batch.compacted,
          "stale resume point answers an explicit compacted resync")
    sub.cancel()
    first_rev_gate.cancel()
    relay2.close()

    # relay death == server restart: kill the relay mid-stream, write
    # through the gap, restart on the same port — downstreams reconnect
    # and resume by revision with zero lost / zero duplicated events
    port = rs.port
    rs.stop()
    revs2 = [store.put(f"/a/{i:03d}", str(i)) for i in range(10, 20)]
    rs = RelayServer(ep, port=port, host="127.0.0.1").start()  # lifecycle: long-lived(selftest respawn; stopped at the end)
    got1b = drain(w_a1, 10, timeout=20.0)
    got2b = drain(w_a2, 10, timeout=20.0)
    check([e.revision for e in got1b] == revs2,
          f"relay kill: downstream 1 resumed with zero lost/dup "
          f"(got {[e.revision for e in got1b]})")
    check([e.revision for e in got2b] == revs2,
          "relay kill: downstream 2 resumed identically")
    deadline = time.monotonic() + 20.0
    stats = rs.relay.stats()
    while stats["relay_downstreams"] < 3 and time.monotonic() < deadline:
        time.sleep(0.2)
        stats = rs.relay.stats()
    check(stats["relay_downstreams"] == 3
          and stats["relay_upstream_streams"] == 2,
          f"restarted relay re-coalesced all 3 downstreams onto 2 "
          f"upstream streams (got {stats['relay_downstreams']}/"
          f"{stats['relay_upstream_streams']})")

    for w in (w_a1, w_a2, w_b):
        w.cancel()
    for d in downs:
        d.close()
    store.close()
    rs.stop()
    srv.stop()

    import sys
    heavy = [m for m in ("jax", "jaxlib", "numpy", "flax", "optax")
             if m in sys.modules]
    check(not heavy,
          f"relay tier imports stay jax/numpy-free (saw {heavy})")

    if failures:
        print(f"relay selftest: {len(failures)} FAILED")
        return 1
    print("relay selftest: all checks passed")
    return 0


def serve(args) -> int:
    upstream = args.upstream or config.env_str(
        "EDL_TPU_STORE_ENDPOINTS", "")
    if not upstream:
        print("relay serve: --upstream or EDL_TPU_STORE_ENDPOINTS required")
        return 2
    server = RelayServer(  # lifecycle: long-lived(serve: runs until the process is killed)
        upstream, port=args.port, host=args.host,
        heartbeat=args.heartbeat)
    server.start()
    print(f"relay: listening on :{server.port} (upstream {upstream})",
          flush=True)
    threading.Event().wait()
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description="edl_tpu watch relay tier")
    sub = parser.add_subparsers(dest="cmd", required=True)
    sub.add_parser("selftest", help="stdlib-only relay contract checks")
    ps = sub.add_parser("serve", help="run a relay server")
    ps.add_argument("--upstream", default="",
                    help="store endpoints (default EDL_TPU_STORE_ENDPOINTS)")
    ps.add_argument("--host", default="0.0.0.0")
    ps.add_argument("--port", type=int, default=2380)
    ps.add_argument("--heartbeat", type=float, default=2.0)
    args = parser.parse_args()
    if args.cmd == "selftest":
        return selftest()
    return serve(args)


if __name__ == "__main__":
    raise SystemExit(main())
