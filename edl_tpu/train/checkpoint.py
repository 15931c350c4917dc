"""Versioned atomic checkpoints with resume status.

Contract (capability of the reference's fleet save/load_check_point per
doc/fault_tolerance.md and train_with_fleet.py:422-434,562-570):

- rank 0 (JAX process 0) writes; all processes load;
- write to a temp dir then atomic ``os.rename`` to ``ckpt-{version}``;
- monotonically increasing integer versions; ``latest`` picks the max
  complete one (a crashed half-written temp dir is never visible);
- ``TrainStatus`` (epoch/step/world_size) saved in meta.json next to the
  state so an elastic restart knows where to resume and how the world was
  shaped at save time;
- keep the newest ``max_to_keep`` checkpoints.

Two state-payload formats behind one manager:

- replicated (default): a flax msgpack of the host-gathered pytree,
  written by rank 0 — right for data-parallel states, where every value
  is fully addressable and resharding is trivial re-placement;
- sharded (``sharded=True``): every process writes only its own array
  chunks + an index (train/sharded_checkpoint.py), and restore
  re-assembles each leaf onto the TARGET state's shardings — including a
  different mesh shape/device count — without ever materializing a full
  replica on host. ``restore`` auto-detects which format a version holds,
  so an elastic restart can move between formats.

Async snapshot-then-write (``save_async``, the CheckFreq/Check-N-Run
recipe): the step loop blocks only for the device->host snapshot. The
fetched arrays go to the writer's job as they are wherever they cannot
alias a live device buffer (sharded_checkpoint.may_alias_device: on an
accelerator the transfer allocates host memory of its own); the ones
that can (the cpu platform, host memory kinds) get a private copy
first. A single background writer thread then does serialization, chunk
writes, the tmp->final seal, mirror upload and GC. The queue is bounded
drop-to-latest — a NEW snapshot supersedes a queued unwritten one but
never an in-flight write — so checkpoint frequency can rise without the
writer ever falling unboundedly behind.
``wait()``/``close()`` are the epoch-end/shutdown barriers; a failed
background write surfaces as ``CheckpointWriteError`` on the NEXT
save/wait/close call. Sync and async saves produce bitwise-identical
checkpoint bytes.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import tempfile
import threading
import time
from typing import Any

import jax
import numpy as np
from flax import serialization

from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import recorder as flight
from edl_tpu.obs import trace
from edl_tpu.train import sharded_checkpoint as sc
from edl_tpu.train.state import TrainStatus
from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.train.checkpoint")


class CheckpointWriteError(RuntimeError):
    """A background checkpoint write failed. Raised on the save/wait/close
    call AFTER the failure (``save_async`` returns before its write runs,
    so the error surfaces at the next synchronization point)."""

_CKPT_RE = re.compile(r"^ckpt-(\d+)$")
_INDEX_FILE_RE = re.compile(r"^index\.(\d+)\.json$")


def _nbytes(arrays) -> int:
    """Bytes of the array leaves."""
    return int(sum(getattr(a, "nbytes", 0) for a in arrays))


def _local_sharded_complete(path: str) -> bool:
    """Does this sealed sharded dir hold every rank's index of the world
    that SAVED it (meta.json's world.process_count)? False on a pod-local
    dir that only ever received its own rank's files."""
    try:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
    except (OSError, ValueError):
        return False
    world = (meta.get("world") or {}).get("process_count")
    if not world:
        return True  # pre-world-record format: nothing to check against
    names = set(os.listdir(path))
    return all(f"index.{r}.json" in names for r in range(world))


class CheckpointManager:
    def __init__(self, directory: str, max_to_keep: int = 3,
                 process_index: int | None = None, sharded: bool = False,
                 remote: str | None = None):
        """`remote`: optional URI root (file://, gs://, hdfs:// — see
        utils/fs.py) mirroring the local dir. Rank 0 uploads each sealed
        version after save; restore on a pod whose local dir lacks the
        wanted version fetches it from the mirror first — the rank-0-
        writes / everyone-reads story on clusters without a shared FS
        (reference doc/fault_tolerance.md:30-45)."""
        self.directory = directory
        self.max_to_keep = max_to_keep
        self._process_index = process_index
        self.sharded = sharded
        self.remote = remote
        # replicated save folds the remote LATEST into its version choice
        # once per manager lifetime (single mirror writer — see save())
        self._remote_folded = False
        # wall seconds of the last restore() (elastic downtime accounting)
        self.last_restore_s: float | None = None
        # -- async snapshot-then-write plane (save_async) ------------------
        # The training thread and the background writer share everything
        # below under _cond (guarded-by annotations checked by edl-lint).
        self._cond = threading.Condition()
        # drop-to-latest slot (size 1)
        self._pending: dict | None = None   # guarded-by: _cond
        self._inflight = False              # guarded-by: _cond
        self._writer: threading.Thread | None = None  # guarded-by: _cond
        self._closed = False                # guarded-by: _cond
        self._write_error: BaseException | None = None  # guarded-by: _cond
        self._async_fallback_logged = False   # training-thread-only
        # -- sealed-snapshot retention (state-migration donor plane) -------
        # When retain_sealed is set (collective/migration.py), the newest
        # successfully sealed save's HOST-side payload is kept in memory
        # so surviving pods can serve it to peers during a resize without
        # re-reading disk. A snapshot's arrays are written once (by the
        # fetch or its private copy) and never again — no buffer is
        # reused across saves — so a peer fetch still reading the
        # previous payload when a newer one seals cannot be served torn
        # bytes; the old payload goes when its last reader lets go.
        self.retain_sealed = False
        self._sealed: dict | None = None    # guarded-by: _cond
        # called (no args, outside the lock) after each retention update;
        # the migration service republishes its advert from here
        self.on_sealed = None
        self._stats = {  # guarded-by: _cond
            "saves_async": 0, "saves_sync": 0, "superseded": 0,
            "writes": 0, "errors": 0, "state_bytes_last": 0,
            "copied_bytes_last": 0,
            "snapshot_ms_last": 0.0, "save_stall_ms_total": 0.0,
            "write_s_last": 0.0, "write_s_total": 0.0, "files_last": 0}
        # the stats() dict stays the benchlog API; the per-process obs
        # registry serves the same counters as gauges (close() drops it)
        self._obs = obs_metrics.register_stats("ckpt", self.stats)

    @property
    def process_index(self) -> int:
        if self._process_index is not None:
            return self._process_index
        return jax.process_index()

    # -- discovery ---------------------------------------------------------

    def versions(self) -> list[int]:
        if not os.path.isdir(self.directory):
            return []
        out = []
        for name in os.listdir(self.directory):
            m = _CKPT_RE.match(name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_version(self) -> int | None:
        versions = self.versions()
        return versions[-1] if versions else None

    def _path(self, version: int) -> str:
        return os.path.join(self.directory, f"ckpt-{version}")

    # -- save --------------------------------------------------------------

    def save(self, state: Any, status: TrainStatus) -> int | None:
        """Save a new checkpoint synchronously; returns its version (None
        on non-writers). The step loop pays the full serialize+write here
        — ``save_async`` is the cheap-per-step path.

        Replicated mode: rank 0 does everything. Sharded mode: every
        process writes its chunks into the same pending dir (all callers
        of the world must call save together), then rank 0 seals it with
        meta.json + atomic rename after a world barrier.
        """
        # An async writer may still be writing an older snapshot; two
        # concurrent writers would race the version choice — drain first
        # (also surfaces a prior background failure on this save call).
        self.wait()
        t0 = time.perf_counter()
        try:
            if self.sharded:
                # a synchronous sharded save takes its snapshot inside
                # the write (the world's barriers come first)
                with trace.span("ckpt.write", attrs={"step": status.step}):
                    return self._save_sharded(state, status)
            if self.process_index != 0:
                # Non-writers still accumulate sealed ckpt-N dirs locally
                # via restore-time mirror fetches — prune them
                # (sealed-only: no pending dirs exist in replicated mode,
                # but keep symmetry with the sharded branch).
                self._gc(sealed_only=True)
                return None
            with trace.span("ckpt.snapshot",
                            attrs={"step": status.step}) as sp:
                host_state = jax.device_get(state)
                if sp is not None:
                    sp.attrs["bytes"] = _nbytes(
                        jax.tree_util.tree_leaves(host_state))
            with trace.span("ckpt.write", attrs={"step": status.step}):
                version = self._write_replicated(host_state, status)
            self._retain("replicated", host_state, version, status)
            return version
        finally:
            with self._cond:
                self._stats["saves_sync"] += 1
                self._stats["save_stall_ms_total"] += (
                    time.perf_counter() - t0) * 1e3

    def _write_replicated(self, host_state: Any, status: TrainStatus) -> int:
        """Serialize + write + seal a host-side state pytree (rank 0's
        replicated format). Runs on the caller's thread for `save` and on
        the background writer for `save_async` — identical bytes."""
        latest = self.latest_version()
        mirror_this = self.remote is not None
        folded_now = False
        if self.remote is not None and not self._remote_folded:
            latest, folded_now = self._fold_remote_latest(latest)
            mirror_this = folded_now
        version = 0 if latest is None else latest + 1
        os.makedirs(self.directory, exist_ok=True)
        tmp = tempfile.mkdtemp(prefix=".tmp-ckpt-", dir=self.directory)
        try:
            with trace.span("ckpt.chunks"):
                payload = serialization.to_bytes(host_state)
                # serialized size AS STORED — quantized resident moments
                # (train/fused_opt.py int8 planes) msgpack their codes, so
                # the ~2x opt-state cut is visible here and in bench's
                # checkpoint row, not only in HBM
                with self._cond:
                    self._stats["state_bytes_last"] = len(payload)
                with open(os.path.join(tmp, "state.msgpack"), "wb") as f:
                    f.write(payload)
            meta = {"version": version, "status": status.to_dict()}
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump(meta, f)
            with trace.span("ckpt.seal"):
                os.rename(tmp, self._path(version))
            with self._cond:
                self._stats["files_last"] = 1
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        log.info("saved checkpoint %s (epoch=%d step=%d)",
                 self._path(version), status.epoch, status.step)
        if folded_now:
            # Single mirror writer: once a fold reaches a SEALED local
            # version, local latest >= remote latest by construction —
            # skip the remote round-trip on subsequent saves. Only now:
            # marking before the seal would let a failed write + retry
            # skip the fold and renumber over a published checkpoint.
            self._remote_folded = True
        if mirror_this:
            with trace.span("ckpt.mirror"):
                self._mirror(version)
        self._gc()
        return version

    def _fold_remote_latest(self, latest: int | None
                            ) -> tuple[int | None, bool]:
        """Fold the mirror's LATEST into the version choice — a
        cold-restarted rank 0 whose local dir is empty would otherwise
        recompute a PUBLISHED version number, and mirroring it would
        overwrite the published checkpoint / flip LATEST backwards.
        Returns (folded latest, read_ok); on read_ok=False the caller
        must skip this save's mirror (the next successful read resumes
        numbering above the remote's)."""
        from edl_tpu.utils import fs
        try:
            remote_latest = fs.remote_latest_version(self.remote)
        except Exception as exc:  # noqa: BLE001 — mirror-only
            log.warning("remote LATEST unreadable (%s) — skipping "
                        "this save's mirror", exc)
            return latest, False
        if remote_latest is not None:
            latest = remote_latest if latest is None else max(
                latest, remote_latest)
        return latest, True

    def _mirror(self, version: int) -> None:
        if self.remote is None:
            return
        from edl_tpu.utils import fs
        try:
            fs.mirror_checkpoint(self.directory, version, self.remote,
                                 keep=self.max_to_keep)
        except fs.EdlFsError as exc:
            # The local version is already sealed — a transient mirror
            # failure (GCS 5xx etc.) must not kill the trainer; the next
            # save's upload + LATEST flip supersedes this one.
            log.warning("mirror of ckpt-%d to %s failed: %s", version,
                        self.remote, exc)

    def _sync(self, tag: str) -> None:
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(tag)

    def _broadcast_int(self, value: int) -> int:
        """Rank 0's value, world-wide (identity in a 1-process world)."""
        if jax.process_count() > 1:
            import numpy as np
            from jax.experimental import multihost_utils
            return int(multihost_utils.broadcast_one_to_all(
                np.int32(value)))
        return value

    def _save_sharded(self, state: Any, status: TrainStatus,
                      snap: dict | None = None) -> int | None:
        # `snap`: a pre-taken host snapshot (sharded_checkpoint.
        # snapshot_shards) written in place of `state` — the async
        # writer's path, single-process worlds only (the barriers below
        # must run on the thread that owns the collective context).
        # All processes must agree on the version. A per-process
        # latest_version() listing diverges when local dirs are NOT
        # shared (only rank 0 ever seals locally, so other pods would
        # recompute version 0 forever and overwrite the published remote
        # ckpt-0 with later-step chunks) — so rank 0 decides, folding in
        # the remote mirror's LATEST (its own local dir may be cold
        # after an in-place restart), and broadcasts.
        self._sync("edl_ckpt_begin")
        latest = self.latest_version()
        remote_read_ok = True
        if self.remote is not None and self.process_index == 0:
            latest, remote_read_ok = self._fold_remote_latest(latest)
        version = self._broadcast_int(0 if latest is None else latest + 1)
        os.makedirs(self.directory, exist_ok=True)
        tmp = os.path.join(self.directory, f".tmp-ckpt-{version}")
        # A crashed earlier save may have left stale chunks/indexes under
        # the same deterministic name (possibly from a different world
        # shape); sealing them in would corrupt the restore, so rank 0
        # clears the dir before anyone writes.
        with trace.span("ckpt.clean"):
            if self.process_index == 0:
                shutil.rmtree(tmp, ignore_errors=True)
            # Every rank clears its OWN stale pending dirs from earlier
            # versions: on non-shared dirs only rank 0 ever renames or
            # runs _gc, so without this each save would leak a full
            # shard copy per pod (at most the CURRENT pending dir
            # remains between saves). Safe on shared dirs too —
            # anything below the agreed version is an orphan by the
            # begin barrier.
            for n in os.listdir(self.directory):
                if (n.startswith(".tmp-ckpt-")
                        and n != os.path.basename(tmp)):
                    shutil.rmtree(os.path.join(self.directory, n),
                                  ignore_errors=True)
        self._sync("edl_ckpt_clean")
        # A process that fails mid-write must still reach the barrier
        # (otherwise the healthy ranks hang in it until the coordination
        # timeout); it drops a poison marker so every rank raises after.
        failure: BaseException | None = None
        my_files: list[str] = []
        owns_snap = snap is None
        try:
            if owns_snap:
                with trace.span("ckpt.snapshot",
                                attrs={"step": status.step}) as sp:
                    snap = sc.snapshot_shards(state)
                    if sp is not None:
                        sp.attrs["bytes"] = _nbytes(
                            a for _, a in snap["chunks"])
            with trace.span("ckpt.chunks"):
                my_files = sc.write_snapshot(tmp, snap)
            with self._cond:
                self._stats["files_last"] = len(my_files)
        except BaseException as exc:  # noqa: BLE001 — re-raised below
            failure = exc
            try:
                os.makedirs(tmp, exist_ok=True)
                with open(os.path.join(
                        tmp, f"save_failed.{self.process_index}"), "w"):
                    pass
            except OSError:
                pass
        self._sync("edl_ckpt_chunks")
        poisoned = [n for n in (os.listdir(tmp) if os.path.isdir(tmp) else [])
                    if n.startswith("save_failed.")]
        ok = failure is None and not poisoned
        if self.remote is not None:
            # The mirror block runs its barriers on EVERY rank — healthy
            # or not — before any raise below: on non-shared dirs a
            # healthy rank cannot see a failed rank's poison marker, so
            # raising first would strand the healthy world in the mirror
            # barriers until the coordination timeout. A rank that
            # failed (or saw poison) participates without uploading.
            with trace.span("ckpt.mirror"):
                mirror_ok = self._mirror_sharded_upload(
                    tmp, version, my_files, ok=ok and remote_read_ok)
        else:
            mirror_ok = False
        if not ok:
            if self.process_index == 0:
                shutil.rmtree(tmp, ignore_errors=True)
            if failure is not None:
                raise failure
            raise RuntimeError(
                f"sharded save aborted: {poisoned} failed")
        try:
            if self.process_index == 0:
                meta = {"version": version, "status": status.to_dict(),
                        "format": "sharded",
                        "world": {"process_count": jax.process_count(),
                                  "device_count": jax.device_count()}}
                with trace.span("ckpt.seal"):
                    with open(os.path.join(tmp, "meta.json"), "w") as f:
                        json.dump(meta, f)
                    os.rename(tmp, self._path(version))
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        if owns_snap and self.retain_sealed:
            # Sync-path retention: a donated train step after this save
            # would overwrite the chunks that alias live buffers under
            # an in-flight peer fetch — copy those before retaining.
            # The async path retains its already-private snapshot in
            # the writer loop instead.
            aliased = snap.pop("may_alias")
            kept = dict(snap, chunks=[(n, np.array(a) if n in aliased else a)
                                      for n, a in snap["chunks"]])
            self._retain("sharded", kept, version, status)
        if self.process_index != 0:
            # Non-zero pods never seal versions locally, but restore-time
            # mirror fetches accumulate sealed ckpt-N dirs in their
            # (non-shared) local dirs — prune those here; rank 0's full
            # _gc below covers the shared/rank-0 case. Sealed-only: this
            # rank's pending .tmp-ckpt dir must survive until rank 0
            # renames it (shared dir) or the next save's clean sweeps it.
            self._gc(sealed_only=True)
            return None
        log.info("saved sharded checkpoint %s (epoch=%d step=%d)",
                 self._path(version), status.epoch, status.step)
        if self.remote is not None and mirror_ok:
            # mirror_ok=False means nobody uploaded (remote clean or
            # LATEST read failed) — finalizing would gate against STALE
            # files from a crashed earlier attempt at this version,
            # which (same world shape) could pass the exact-set check
            # and flip LATEST to old-step data.
            with trace.span("ckpt.mirror"):
                self._mirror_sharded_finalize(version)
        self._gc()
        return version

    def _mirror_sharded_upload(self, tmp: str, version: int,
                               my_files: list[str], *, ok: bool) -> bool:
        """EVERY process uploads its own chunks + index from its pending
        dir (local dirs need not be shared across pods); rank 0 uploads
        meta.json + flips LATEST only in `_mirror_sharded_finalize`, so
        the marker is last world-wide. `ok=False` ranks (their own write
        failed, they saw a poison marker, or rank 0 could not read the
        remote LATEST) run the barriers without uploading. Returns
        whether the world proceeded with uploads (rank 0's clean
        succeeded) — the caller gates `_mirror_sharded_finalize` on it,
        since finalizing after a failed clean would gate against STALE
        files from a crashed earlier attempt at this version."""
        from edl_tpu.utils import fs
        clean_ok = 1 if ok else 0  # rank 0's value wins via broadcast
        if self.process_index == 0 and ok:
            # A crashed earlier save at this version (possibly a
            # different world shape) may have left stale chunks/indexes
            # in the remote dir; merging them in would corrupt the
            # restore — same hazard the local tmp-clean guards against.
            # If the clean FAILS, a stale index.{r}.json could survive a
            # rank's failed re-upload and defeat the finalize gate's
            # exact-set check (old-attempt chunks merged into restores),
            # so the whole world skips this version's mirror instead.
            try:
                fs.resolve(self.remote).delete(
                    fs.join_uri(self.remote, f"ckpt-{version}"))
            except Exception as exc:  # noqa: BLE001 — mirror-only
                log.warning("remote clean of ckpt-%d failed — skipping "
                            "this version's mirror: %s", version, exc)
                clean_ok = 0
        clean_ok = self._broadcast_int(clean_ok)
        if ok and clean_ok:
            try:
                fs.mirror_checkpoint_files(tmp, version, self.remote,
                                           my_files)
            except Exception as exc:  # noqa: BLE001 — any transfer error
                # Swallow so this rank still reaches the barrier (a raw
                # OSError from LocalFS would strand the world in _sync).
                # The missing index.{rank}.json is what the finalize
                # gate keys on, so LATEST never flips to this
                # incomplete version.
                log.warning(
                    "sharded mirror of ckpt-%d (rank %d) failed: %s",
                    version, self.process_index, exc)
        self._sync("edl_ckpt_mirror")
        return bool(clean_ok)

    def _mirror_sharded_finalize(self, version: int) -> None:
        """Rank 0 only. NOT `_mirror`: a whole-dir upload would replace
        the remote version dir, wiping the other ranks' uploads."""
        from edl_tpu.utils import fs
        try:
            # Completeness gate before the LATEST flip: the remote dir
            # must hold EXACTLY index.{0..world-1}.json. A rank's index
            # uploads last (save_sharded returns it last), so presence
            # implies its chunks made it; an UNEXPECTED extra index —
            # survivor of a failed remote clean, e.g. from a crashed
            # save at a different world shape — would merge stale chunks
            # into every restore, so it also blocks the flip. Skipping
            # the flip keeps LATEST on the previous complete version
            # (and skips its GC).
            have = set(fs.resolve(self.remote).listdir(
                fs.join_uri(self.remote, f"ckpt-{version}")))
            want = {f"index.{r}.json" for r in range(jax.process_count())}
            got = {n for n in have if _INDEX_FILE_RE.match(n)}
            if got != want:
                log.warning(
                    "mirror of ckpt-%d inconsistent (missing indexes %s, "
                    "stale extras %s) — LATEST not flipped", version,
                    sorted(want - got), sorted(got - want))
                return
            fs.mirror_checkpoint_files(self._path(version), version,
                                       self.remote, ["meta.json"])
            fs.finalize_mirror(self.remote, version, keep=self.max_to_keep)
            log.info("mirrored sharded ckpt-%d -> %s", version, self.remote)
        except Exception as exc:  # noqa: BLE001 — a mirror failure must
            log.warning("mirror of ckpt-%d to %s failed: %s", version,
                        self.remote, exc)  # not kill a sealed local save

    def _gc(self, *, sealed_only: bool = False) -> None:
        with trace.span("ckpt.gc") as sp:
            versions = self.versions()
            stale = versions[: max(0, len(versions) - self.max_to_keep)]
            for version in stale:
                shutil.rmtree(self._path(version), ignore_errors=True)
            if sp is not None:
                sp.attrs["removed"] = len(stale)
            if sealed_only:
                return
            # clean any orphaned temp dirs from crashed saves
            for name in os.listdir(self.directory):
                if name.startswith(".tmp-ckpt-"):
                    path = os.path.join(self.directory, name)
                    shutil.rmtree(path, ignore_errors=True)

    def gc_stale_tmp(self) -> None:
        """Startup GC: remove torn ``.tmp-*`` dirs — partial saves from a
        crashed/killed writer (chunks written, never sealed) and orphaned
        refetch staging. The save-time ``_gc`` only runs on ranks that
        write and only after a successful save, so a run that dies before
        its first save leaks them forever. Call at (re)start — e.g.
        ``TrainLoop.try_restore`` — when no save of the current
        generation can be pending; NOT from passive readers (a teacher
        polling a shared dir must not sweep the trainer's in-progress
        pending dir)."""
        try:
            names = os.listdir(self.directory)
        except FileNotFoundError:
            return
        for name in names:
            if name.startswith(".tmp-"):
                path = os.path.join(self.directory, name)
                log.info("startup GC: removing stale partial save %s", path)
                shutil.rmtree(path, ignore_errors=True)

    # -- sealed-snapshot retention (state-migration donors) ----------------

    def _retain(self, kind: str, payload: Any, version: int | None,
                status: TrainStatus) -> None:
        """Keep the just-sealed save's host payload for peer serving.
        No-op unless `retain_sealed`. The PREVIOUS retained payload is
        simply dropped (see __init__ note)."""
        cb = None
        with self._cond:
            if not self.retain_sealed:
                return
            self._sealed = {"kind": kind, "payload": payload,
                            "version": version,
                            # isolate from the loop's live status cursor
                            "status": TrainStatus.from_dict(
                                status.to_dict())}
            cb = self.on_sealed
        if cb is not None:
            try:
                cb()
            except Exception:  # noqa: BLE001 — serving is best-effort;
                log.exception("on_sealed hook failed")  # never fail a save

    def sealed_snapshot(self) -> dict | None:
        """Newest sealed save as a serve-ready view — ``{version,
        status, process_index, leaves, chunks}`` where ``leaves`` is the
        self-describing chunk table (sharded_checkpoint format) and
        ``chunks`` maps chunk file names to host arrays. This is the
        donor manifest+payload the migration server answers peers with.
        None until a save seals with ``retain_sealed`` set."""
        with self._cond:
            rec = self._sealed
        if rec is None:
            return None
        if rec["kind"] == "sharded":
            snap = rec["payload"]
        else:
            snap = sc.snapshot_host_tree(rec["payload"])
        return {"version": rec["version"],
                "status": rec["status"].to_dict(),
                "process_index": snap.get("process_index", 0),
                "leaves": snap["leaves"],
                "chunks": dict(snap["chunks"])}

    # -- async snapshot-then-write -----------------------------------------

    def save_async(self, state: Any, status: TrainStatus) -> None:
        """Queue a checkpoint: the caller blocks only for the
        device->host snapshot; serialization, disk writes, the
        tmp->final seal, mirror upload and GC all happen on the
        background writer thread. Raises ``CheckpointWriteError`` here
        if a PREVIOUS background write failed.

        Drop-to-latest: if an earlier snapshot is still queued (writer
        busy), it is superseded by this one — the in-flight write is
        never aborted, so the newest sealed version only moves forward.
        Multi-process sharded worlds fall back to the synchronous path
        (its world barriers must run on the training thread).
        """
        self._raise_pending_error()
        if self.sharded and jax.process_count() > 1:
            if not self._async_fallback_logged:
                log.info("save_async: multi-process sharded world — "
                         "falling back to synchronous saves")
                self._async_fallback_logged = True
            self.save(state, status)
            return
        if not self.sharded and self.process_index != 0:
            self._gc(sealed_only=True)
            return
        t0 = time.perf_counter()
        with trace.span("ckpt.snapshot", attrs={"step": status.step}) as sp:
            # Supersede BEFORE the fetch: the dropped snapshot's host
            # memory is free again while this one's is allocated (at
            # most one in-flight + one pending snapshot live).
            with self._cond:
                writer_inflight = self._inflight
                superseded = self._pending is not None
                if superseded:
                    self._pending = None
                    self._stats["superseded"] += 1
            status = TrainStatus.from_dict(status.to_dict())  # isolate the
            # snapshot from the loop's live, mutating status cursor
            if self.sharded:
                with trace.span("ckpt.d2h"):
                    snap = sc.snapshot_shards(state)
                aliased = snap.pop("may_alias")
                names = [n for n, _ in snap["chunks"]]
                arrays, copied = self._stage(
                    [a for _, a in snap["chunks"]],
                    [n in aliased for n in names])
                snap["chunks"] = list(zip(names, arrays))
                job = {"kind": "sharded", "snap": snap}
            else:
                leaves, treedef = jax.tree_util.tree_flatten(state)
                with trace.span("ckpt.d2h"):
                    fetched = jax.device_get(leaves)
                arrays, copied = self._stage(
                    fetched, [sc.may_alias_device(x) for x in leaves])
                job = {"kind": "replicated",
                       "tree": jax.tree_util.tree_unflatten(treedef, arrays)}
            job["status"] = status
            if sp is not None:
                job["bytes"] = _nbytes(arrays)
                sp.attrs.update(bytes=job["bytes"], copied_bytes=copied,
                                superseded=superseded,
                                writer_inflight=writer_inflight)
        job["queued_at"] = time.perf_counter()
        stall_ms = (job["queued_at"] - t0) * 1e3
        with self._cond:
            if self._closed:
                raise RuntimeError("CheckpointManager is closed")
            self._stats["saves_async"] += 1
            self._stats["snapshot_ms_last"] = stall_ms
            self._stats["save_stall_ms_total"] += stall_ms
            self._stats["copied_bytes_last"] = copied
            self._pending = job
            if self._writer is None:
                self._writer = threading.Thread(
                    target=self._writer_loop, name="edl-ckpt-writer",
                    daemon=True)
                self._writer.start()
            self._cond.notify_all()

    @staticmethod
    def _stage(fetched: list, aliased: list[bool]) -> tuple[list, int]:
        """Make the fetched host arrays the snapshot's own: the ones
        flagged in `aliased` (`sc.may_alias_device` of their source) can
        be zero-copy VIEWS of live buffers, which a donating train step
        overwrites before the background write runs, and get a private
        copy; the others already are host memory nothing else writes to
        and go to the writer as they are. Returns (arrays, bytes
        copied); the `ckpt.stage` span exists only where a copy does."""
        # (a python scalar leaf is immutable: nothing to copy)
        copy = [m and isinstance(a, np.ndarray)
                for a, m in zip(fetched, aliased)]
        if not any(copy):
            return fetched, 0
        with trace.span("ckpt.stage"):
            arrays = [np.array(a) if c else a
                      for a, c in zip(fetched, copy)]
        return arrays, _nbytes(a for a, c in zip(fetched, copy) if c)

    def _writer_loop(self) -> None:
        while True:
            with self._cond:
                while self._pending is None and not self._closed:
                    self._cond.wait()
                if self._pending is None:
                    return  # closed and drained
                job = self._pending
                self._pending = None
                self._inflight = True
            try:
                t0 = time.perf_counter()
                with trace.span("ckpt.write", attrs={
                        "step": job["status"].step,
                        "bytes": job.get("bytes", 0),
                        "queued_s": round(t0 - job["queued_at"], 6)}) as sp:
                    if job["kind"] == "sharded":
                        ver = self._save_sharded(None, job["status"],
                                                 snap=job["snap"])
                        self._retain("sharded", job["snap"], ver,
                                     job["status"])
                    else:
                        ver = self._write_replicated(job["tree"],
                                                     job["status"])
                        self._retain("replicated", job["tree"], ver,
                                     job["status"])
                    if sp is not None:
                        with self._cond:
                            sp.attrs["files"] = self._stats["files_last"]
                dt = time.perf_counter() - t0
                with self._cond:
                    self._stats["writes"] += 1
                    self._stats["write_s_last"] = dt
                    self._stats["write_s_total"] += dt
            except BaseException as exc:  # noqa: BLE001 — surfaced on the
                log.exception(            # next save/wait/close call
                    "async checkpoint write failed")
                with self._cond:
                    self._write_error = exc
                    self._stats["errors"] += 1
            finally:
                # let go of the snapshot now, not when the next job
                # arrives: unless _retain kept it, its host memory is
                # free again for the next fetch
                del job
                with self._cond:
                    self._inflight = False
                    self._cond.notify_all()

    def wait(self, timeout: float | None = None) -> None:
        """Barrier: block until every queued snapshot is durably written
        (the epoch-end sync point). Re-raises a background write failure
        as ``CheckpointWriteError``."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self._pending is not None or self._inflight:
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(
                        "checkpoint writer did not drain in time")
                self._cond.wait(remaining)
        self._raise_pending_error()

    def close(self, raise_errors: bool = True) -> None:
        """Shutdown barrier: drain the queued snapshot (a valid snapshot
        is never thrown away — crash paths still seal their last state)
        and stop the writer thread. ``raise_errors=False`` is for
        crash-path ``finally`` blocks where raising would mask the
        original exception; failures are logged either way. The manager
        is reusable after close (a later ``save_async`` restarts the
        writer)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            writer = self._writer
        if writer is not None:
            writer.join()
        with self._cond:
            self._writer = None
            self._closed = False
        # drop the registry view (the manager stays usable for saves,
        # but a closed manager must not pin itself in the per-process
        # registry forever — tests build thousands of these)
        obs_metrics.unregister(self._obs)
        if raise_errors:
            self._raise_pending_error()

    def _raise_pending_error(self) -> None:
        with self._cond:
            exc, self._write_error = self._write_error, None
        if exc is not None:
            raise CheckpointWriteError(
                "background checkpoint write failed") from exc

    def stats(self) -> dict:
        """Save-stall / write accounting. ``save_stall_ms_total`` is the
        step-loop-visible time across BOTH paths: full save duration for
        sync saves, snapshot-copy duration for async ones."""
        with self._cond:
            s = dict(self._stats)
        saves = s["saves_async"] + s["saves_sync"]
        s["save_stall_ms_mean"] = (s["save_stall_ms_total"] / saves
                                   if saves else 0.0)
        if self.last_restore_s is not None:
            s["restore_s"] = self.last_restore_s
        return s

    # -- load --------------------------------------------------------------

    def restore_raw(self, version: int | None = None
                    ) -> tuple[dict, TrainStatus] | None:
        """Structure-FREE restore of a replicated checkpoint: the raw
        nested state dict (``{'params': ..., 'batch_stats': ..., ...}``)
        with no target pytree. For consumers that only want a sub-tree —
        a teacher server restoring params saved by a trainer whose
        optimizer state it neither has nor wants (serialization
        `from_bytes` would reject the opt_state structure mismatch)."""
        if version is None:
            version = self.latest_version()
            if self.remote is not None:
                # Same prefer-remote-when-newer rule as restore(): a
                # teacher pod restarted in place must not serve stale
                # local params while the trainer's mirror moved on.
                from edl_tpu.utils import fs
                try:
                    remote_latest = fs.remote_latest_version(self.remote)
                except fs.EdlFsError as exc:
                    log.warning("mirror %s unreachable for restore_raw: "
                                "%s", self.remote, exc)
                    remote_latest = None
                if remote_latest is not None and (
                        version is None or remote_latest > version):
                    version = fs.fetch_latest_checkpoint(self.remote,
                                                         self.directory)
        if version is None:
            return None
        if (not os.path.isdir(self._path(version))
                and self.remote is not None):
            from edl_tpu.utils import fs
            fs.fetch_latest_checkpoint(self.remote, self.directory,
                                       version=version)
        path = self._path(version)
        if sc.is_sharded_dir(path):
            raise ValueError(
                f"{path} is a sharded checkpoint; restore_raw serves the "
                "replicated msgpack format (pass a target to restore())")
        with open(os.path.join(path, "state.msgpack"), "rb") as f:
            raw = serialization.msgpack_restore(f.read())
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        return raw, TrainStatus.from_dict(meta["status"])

    def restore(self, target: Any, version: int | None = None
                ) -> tuple[Any, TrainStatus] | None:
        """Restore into the structure of ``target``; None if no checkpoint.

        Auto-detects the version's format. Sharded checkpoints re-place
        each leaf per ``target``'s shardings (so pass the new world's
        freshly built state — any mesh shape); replicated checkpoints
        deserialize to host numpy in ``target``'s structure. Sharded
        chunk regions are read through a per-file handle cache on a
        thread pool (``EDL_TPU_CKPT_RESTORE_THREADS``) — restore wall
        time is the elastic-downtime term this call owns.

        Integrity: a chunk failing its sealed crc32 raises the typed
        ``EdlCheckpointCorrupt``; with ``version=None`` the manager
        falls back to the next older sealed version (loudly) instead of
        loading garbage — only an explicit ``version`` surfaces the
        corruption to the caller.
        """
        from edl_tpu.utils.exceptions import EdlCheckpointCorrupt
        if version is not None:
            return self._restore_version(target, version)
        try:
            return self._restore_version(target, None)
        except EdlCheckpointCorrupt as exc:
            last_exc = exc
        # The auto-picked latest (mirror fetches land locally first, so
        # latest_version() names it) is corrupt: walk older sealed
        # versions, newest first, loudly.
        bad = self.latest_version()
        flight.record("corruption", plane="checkpoint", version=bad,
                      directory=self.directory, error=str(last_exc))
        log.error("checkpoint ckpt-%s corrupt (%s) — falling back to "
                  "the previous sealed version", bad, last_exc)
        older = [v for v in self.versions() if bad is None or v < bad]
        for v in reversed(older):
            try:
                return self._restore_version(target, v)
            except EdlCheckpointCorrupt as exc:
                last_exc = exc
                log.error("checkpoint ckpt-%d also corrupt (%s)", v, exc)
        raise EdlCheckpointCorrupt(
            "every local sealed checkpoint failed its integrity check "
            f"under {self.directory}") from last_exc

    def _restore_version(self, target: Any, version: int | None
                         ) -> tuple[Any, TrainStatus] | None:
        # ended only where something was restored: a restore that finds
        # nothing, or raises, leaves no span
        sp = trace.start_span("ckpt.restore", attrs={"source": "disk"})
        t_start = time.perf_counter()
        if version is None:
            version = self.latest_version()
            if self.remote is not None:
                # The mirror may be ahead of this pod's local dir (e.g. a
                # container restarted in place while rank 0 kept saving);
                # restoring the stale local latest would diverge from the
                # rest of the world, so prefer the remote LATEST marker
                # whenever it is newer.
                from edl_tpu.utils import fs
                try:
                    remote_latest = fs.remote_latest_version(self.remote)
                except fs.EdlFsError as exc:
                    log.warning("mirror %s unreachable for restore: %s",
                                self.remote, exc)
                    remote_latest = None
                if remote_latest is not None and (version is None
                                                  or remote_latest > version):
                    version = fs.fetch_latest_checkpoint(self.remote,
                                                         self.directory)
        if version is None:
            return None
        if (not os.path.isdir(self._path(version))
                and self.remote is not None):
            from edl_tpu.utils import fs
            fs.fetch_latest_checkpoint(self.remote, self.directory,
                                       version=version)
        path = self._path(version)
        if (self.remote is not None and os.path.isdir(path)
                and sc.is_sharded_dir(path)
                and not _local_sharded_complete(path)):
            # Non-shared dirs: a pod's locally sealed sharded version
            # holds only its OWN chunks + index (rank 0's, after an
            # in-place restart). Reassembling from it would miss every
            # region other ranks owned — refetch the complete mirrored
            # copy instead of trusting local presence. Verify the mirror
            # actually HAS a complete copy before deleting the local dir
            # (it is this pod's only copy of its own chunks).
            from edl_tpu.utils import fs
            # Fetch into a temp dir FIRST and only then swap: the local
            # dir is this pod's only copy of its own chunks, so it must
            # survive a fetch that fails mid-flight (remote GC race,
            # transient transport error).
            fetch_tmp = tempfile.mkdtemp(prefix=".tmp-refetch-",
                                         dir=self.directory)
            got = None
            try:
                got = fs.fetch_latest_checkpoint(self.remote, fetch_tmp,
                                                 version=version)
            except Exception as exc:  # noqa: BLE001 — mirror-only
                log.warning("mirror refetch of ckpt-%d failed: %s",
                            version, exc)
            if got is not None:
                log.info("local %s incomplete for its saved world — "
                         "replaced with the mirror's complete copy", path)
                shutil.rmtree(path, ignore_errors=True)
                os.rename(os.path.join(fetch_tmp, f"ckpt-{version}"), path)
            else:
                log.warning(
                    "local %s incomplete and mirror has no complete "
                    "copy — restoring from local (may fail coverage)",
                    path)
            shutil.rmtree(fetch_tmp, ignore_errors=True)
        if sc.is_sharded_dir(path):
            state = sc.restore_sharded(path, target)
        else:
            with open(os.path.join(path, "state.msgpack"), "rb") as f:
                state = serialization.from_bytes(target, f.read())
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        status = TrainStatus.from_dict(meta["status"])
        self.last_restore_s = time.perf_counter() - t_start
        log.info("restored checkpoint %s (epoch=%d step=%d) in %.3fs", path,
                 status.epoch, status.step, self.last_restore_s)
        if sp is not None:
            sp.end(version=version,
                   bytes=_nbytes(jax.tree_util.tree_leaves(state)))
        return state, status
