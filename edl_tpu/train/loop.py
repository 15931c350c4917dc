"""Epoch-based training loop with checkpoint/resume and throughput logging.

The host-side driver equivalent of the reference's trainer main loop
(example/collective/resnet50/train_with_fleet.py:347-610: resume epoch from
TrainStatus, hot loop over the input pipeline, rank-0 checkpoint each epoch,
periodic img/s + loss prints, optional eval each epoch) — redesigned for
JAX: the step is a jitted pure function `(state, batch) -> (state, metrics)`
with the batch sharded over the mesh's data axes and state placement left to
the step's shardings; elasticity comes from re-entering `run()` after a
restart with a different mesh.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Any, Callable, Iterable

import jax

from edl_tpu.obs import recorder as flight
from edl_tpu.obs import trace
from edl_tpu.parallel import distributed
from edl_tpu.parallel import mesh as mesh_lib
from edl_tpu.parallel import sharding as sharding_lib
from edl_tpu.train.checkpoint import CheckpointManager
from edl_tpu.train.state import TrainStatus
from edl_tpu.utils.config import field
from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.train.loop")


@dataclass
class LoopConfig:
    num_epochs: int = field(1, env="EDL_TPU_NUM_EPOCHS")
    log_every_steps: int = field(20, env="EDL_TPU_LOG_EVERY")
    ckpt_dir: str | None = field(None, env="EDL_TPU_CHECKPOINT_PATH")
    ckpt_every_epochs: int = field(1, env="EDL_TPU_SAVE_CHECKPOINT_INTER")
    # Step-interval checkpointing — cheap under async saves, so elastic
    # jobs can shrink their replay-after-reformation window to N steps.
    ckpt_every_steps: int = field(0, env=("EDL_TPU_CKPT_STEPS",
                                          "EDL_TPU_SAVE_CHECKPOINT_STEPS"))
    ckpt_max_to_keep: int = field(3, env="EDL_TPU_CHECKPOINT_KEEP")
    # Async snapshot-then-write saves (checkpoint.save_async): the step
    # loop blocks only for the device->host snapshot; serialization +
    # disk + mirror ride a background writer. False = the synchronous
    # escape hatch (every save is a full stall, bytes identical).
    ckpt_async: bool = field(True, env="EDL_TPU_CKPT_ASYNC")
    # Sharded (per-process chunk) checkpoints — required once params are
    # fsdp/tp-sharded; replicated msgpack is the small-model default.
    ckpt_sharded: bool = field(False, env="EDL_TPU_CHECKPOINT_SHARDED")
    # Remote mirror URI (gs://, hdfs://, file://) — rank 0 uploads each
    # sealed version, cold pods fetch before restore (utils/fs.py).
    ckpt_remote: str | None = field(None, env="EDL_TPU_CKPT_REMOTE")
    # jax.profiler trace window (the reference's --profile traces batches
    # 100-105 on trainer 0, train_with_fleet.py:521-530): when
    # profile_dir is set, rank 0 captures [profile_start_step,
    # profile_start_step + profile_steps) global steps.
    profile_dir: str | None = field(None, env="EDL_TPU_PROFILE_DIR")
    profile_start_step: int = field(10, env="EDL_TPU_PROFILE_START")
    profile_steps: int = field(5, env="EDL_TPU_PROFILE_STEPS")
    # Host->device prefetch: stage up to N placed batches on a daemon
    # thread while the current step computes, so the device_put of batch
    # i+1 hides under step i (H2D overlap — the distill serving path's
    # student-side half). 0 = place inline on the training thread.
    prefetch_batches: int = field(0, env="EDL_TPU_PREFETCH_BATCHES")


class TrainLoop:
    """Drives (state, batch) -> (state, metrics) steps over epochs.

    Args:
      step_fn: jitted step. Called as step_fn(state, batch).
      state: initial TrainState (ignored if a checkpoint is restored).
      mesh: device mesh; batches are sharded over its data axes.
      config: LoopConfig.
      eval_fn: optional callable(state, epoch) -> dict, run after each epoch.
      hooks: optional callables(loop, epoch, step, metrics) run at log points.
    """

    def __init__(self, step_fn: Callable, state: Any,
                 mesh=None, config: LoopConfig | None = None,
                 eval_fn: Callable | None = None,
                 hooks: list[Callable] | None = None,
                 batch_axes: tuple[str, ...] | None = None,
                 place_state: Callable | None = None,
                 on_reform: Callable | None = None,
                 reform_mesh: Callable | None = None,
                 reform_config=None,
                 augment_fn: Callable | None = None):
        self.step_fn = step_fn
        self.state = state
        self.mesh = mesh
        # Device-side augmentation hook (ops.augment.make_device_augment):
        # `(placed_batch, seed) -> batch`, applied after placement with
        # the per-step seed the loader emitted (emit_batch_seed=True) —
        # the jitted dispatch overlaps the running step.
        self.augment_fn = augment_fn
        # Re-places a restored host-side state pytree onto devices (required
        # in a multi-process world where host numpy can't feed a global-mesh
        # jit directly — e.g. mesh_lib.replicate_host_tree, or a sharded
        # checkpoint's re-placement rules).
        self.place_state = place_state
        self.config = config or LoopConfig()
        self.eval_fn = eval_fn
        self.hooks = hooks or []
        self.batch_axes = batch_axes
        self.status = TrainStatus(
            world_size=mesh_lib.dp_size(mesh) if mesh is not None
            else jax.device_count())
        self.ckpt = (CheckpointManager(self.config.ckpt_dir,
                                       self.config.ckpt_max_to_keep,
                                       sharded=self.config.ckpt_sharded,
                                       remote=self.config.ckpt_remote)
                     if self.config.ckpt_dir else None)
        self.last_metrics: dict = {}
        self._profiling = False
        if self.config.profile_dir:
            # a process that will be profiled records its spans from
            # here on (entry points that parse --profile call this
            # earlier, for their start-up spans); written at flush()
            trace.collect(self.config.profile_dir)
        # Saves this loop asked for (the stall each cost is the
        # manager's `save_stall_ms_total`), plus the restore seconds of
        # this run's resume.
        self.ckpt_saves = 0
        self.restore_s: float | None = None
        self._first_step_done = False
        # World size recorded in the restored checkpoint, set by
        # try_restore(); None until a restore happens. Consumers use it to
        # rescale LR/batch after an elastic resize (lr.scale_for_world).
        self.saved_world_size: int | None = None
        # Under the elastic launcher, publish step rate / samples_seen
        # into the pod's leased /{job}/util/ record so the Collector
        # (scheduler data path, reference discovery/register.py:36-40
        # `info`) sees fresh trainer utilization. No-op standalone, and
        # never blocks training: a failure here only disables publishing.
        try:
            from edl_tpu.coord.collector import UtilizationPublisher
            self._util_publisher = UtilizationPublisher.from_env()
        except Exception:  # noqa: BLE001 — observability is optional
            self._util_publisher = None
        if self._util_publisher is not None:
            self.hooks = list(self.hooks) + [self._util_publisher]
        # State-migration plane (collective/migration.py): under the
        # elastic launcher with EDL_TPU_RESIZE_P2P on, this trainer (a)
        # serves its retained sealed checkpoint snapshot to peers, (b)
        # prefers restoring from live donors over disk, and (c) adopts
        # resizes that keep this pod IN PLACE — re-entering the epoch at
        # the cursor with the new (rank, world) instead of dying into a
        # stop-resume. `on_reform(rank, world, cluster)` is the caller's
        # hook to re-derive data sharding for the new world.
        self.on_reform = on_reform
        # Reform state machine hooks (collective/reform.py) — the
        # device-world half of elasticity: `reform_mesh(rank, world,
        # cluster)` returns the NEW mesh when the resize changes this
        # process's device world (None = unchanged, the fast adoption
        # path). The hook owns any `jax.distributed` re-initialization
        # (parallel/distributed.reform_world) for true multi-host
        # worlds; the loop then reshapes state through peer restore
        # (disk fallback), re-jits under the compilation cache, and
        # acks generation-fenced. A loop wired with this hook seals its
        # live state at quiesce, so a reform loses zero progress.
        self.reform_mesh = reform_mesh
        self._reform_config = reform_config
        self._reform_machine = None
        self.last_reform: dict | None = None
        self._migration = None
        if self.ckpt is not None:
            try:
                from edl_tpu.collective.migration import MigrationService
                self._migration = MigrationService.from_env(self.ckpt)
            except Exception:  # noqa: BLE001 — the plane is optional;
                log.warning("migration service unavailable",  # train on
                            exc_info=True)
        self.restore_source: str | None = None
        self.bytes_from_peers = 0
        self.reforms = 0
        self.last_reform_downtime_s: float | None = None
        self.stop_reason: str | None = None
        self._reform_t0: float | None = None
        # the in-flight adoption's trace span: opened at reform, ended
        # at the first step of the new generation — its duration IS the
        # measured p2p downtime, inside the resize's causal trace
        self._reform_span = None

    # -- checkpoint glue ---------------------------------------------------

    def try_restore(self) -> bool:
        if self.ckpt is None:
            return False
        # Startup GC: torn .tmp-* partial saves from a crashed/killed
        # writer are invisible to restore (never sealed) but leak disk
        # forever otherwise — the trainer start path owns the sweep.
        self.ckpt.gc_stale_tmp()
        restored = None
        if self._migration is not None:
            # Peer-first restore: live donors serve the state straight
            # from memory over the tensor wire; disk is only the
            # fallback. The local disk version is the fence — a stale
            # donor never beats a newer sealed local checkpoint.
            from edl_tpu.collective.migration import PeerRestoreError
            t0 = time.perf_counter()
            try:
                # ended on success only: no donor is the common case
                sp = trace.start_span("ckpt.restore",
                                      attrs={"source": "peers"})
                state, status, stats = self._migration.restore_from_peers(
                    self.state, local_version=self.ckpt.latest_version())
                if sp is not None:
                    sp.end(bytes=int(stats["bytes_from_peers"]))
                restored = (state, status)
                self.restore_source = "peers"
                self.bytes_from_peers = int(stats["bytes_from_peers"])
                self.ckpt.last_restore_s = time.perf_counter() - t0
            except PeerRestoreError as exc:
                log.info("peer restore unavailable (%s) — falling back "
                         "to disk", exc)
        if restored is None:
            restored = self.ckpt.restore(self.state)
            if restored is not None:
                self.restore_source = "disk"
        self.restore_s = self.ckpt.last_restore_s
        if restored is None:
            return False
        self.state, self.status = restored
        if self.place_state is not None:
            self.state = self.place_state(self.state)
        log.info("state bytes per device: %s",
                 sharding_lib.bytes_per_device(self.state))
        # Preserve the save-time world size (the resharding/LR-rescale hint)
        # before stamping the current world for the next save.
        self.saved_world_size = self.status.world_size
        self.status.world_size = (mesh_lib.dp_size(self.mesh)
                                  if self.mesh is not None
                                  else jax.device_count())
        return True

    def _save(self, sync: bool | None = None) -> None:
        """Checkpoint now. Async by default (config.ckpt_async): blocks
        only for the snapshot copy; ``sync=True`` is the per-call escape
        hatch that waits for the full write."""
        if self.ckpt is None:
            return
        use_sync = (not self.config.ckpt_async) if sync is None else sync
        # `ckpt.snapshot` is its child: what a save costs the loop
        # outside the snapshot is this span's self time
        with trace.span("train.save"):
            if use_sync:
                self.ckpt.save(self.state, self.status)
            else:
                self.ckpt.save_async(self.state, self.status)
        # a supervisor may act on this save's step line within
        # milliseconds (a SIGKILL, say): its record goes to disk now, on
        # the sampler's thread
        trace.flush_soon()
        self.ckpt_saves += 1

    def _adopt(self, reform) -> str:
        """Adopt a resize in place: the new cluster still contains this
        pod, so instead of dying into a stop-resume it walks the reform
        state machine (collective/reform.py). An unchanged device set
        keeps the fast path (no seal, no restore — the 0.061 s survivor
        gap); a device-world change (reform_mesh returns a new mesh)
        pays quiesce-seal -> mesh-reform -> peer-restore (disk
        fallback) -> re-jit, all inside the same OS process. Returns
        "reform" (in place) or "stop" — the clean stop-resume downgrade
        when a phase missed its deadline or failed past its fallback.
        The measured gap (adoption -> first step of the new generation)
        is the resize downtime for survivors either way."""
        from edl_tpu.collective import reform as rf
        self._reform_t0 = time.perf_counter()
        if trace.enabled():
            from edl_tpu.collective.migration import resize_trace_ctx
            self._reform_span = trace.start_span(
                "resize.adopt",
                parent=resize_trace_ctx(self._migration.store,
                                        self._migration.job_id),
                attrs={"pod": self._migration.pod_id,
                       "rank": reform.rank, "world": reform.world_size,
                       "generation": reform.generation})
        log.info("live-reform: adopting cluster v%d rank=%d world=%d in "
                 "place (no respawn)", reform.generation, reform.rank,
                 reform.world_size)
        machine = rf.ReformMachine(
            reform.generation, self._reform_config,
            trace_parent=(self._reform_span.context
                          if self._reform_span is not None else None),
            who=self._migration.pod_id)
        self._reform_machine = machine
        changed: dict = {}
        try:
            machine.run_phase("quiesce", self._reform_quiesce)
            machine.run_phase(
                "mesh-reform",
                lambda dl: changed.update(
                    mesh=self._reform_mesh_phase(reform, dl)))
            if changed.get("mesh") is not None:
                try:
                    machine.run_phase("peer-restore",
                                      self._reform_restore_peers)
                    machine.restore = "peers"
                except rf.ReformError as exc:
                    if exc.downgrade != "disk":
                        raise
                    log.warning("reform peer-restore failed (%s) — "
                                "disk-restore downgrade", exc)
                    machine.run_phase("disk-restore",
                                      self._reform_restore_disk)
                    machine.restore = "disk"
                self.status.world_size = mesh_lib.dp_size(self.mesh)
            machine.result = rf.IN_PLACE
        except rf.ReformError as exc:
            # The defined downgrade: degrade to a CLEAN stop-resume.
            # This trainer behaves exactly like a graceful SIGTERM stop
            # — run() seals the live state, exits 143 and the migration
            # shutdown lingers as a donor — and the launcher's adopt
            # timeout respawns the world. A half-reformed survivor
            # never acks: its generation is stale, so even a late ack
            # attempt bounces off the epoch-doc fence.
            machine.result = rf.STOP_RESUME
            machine.error = str(exc)
            self.last_reform = machine.finish()
            self._reform_machine = None
            log.warning("reform of generation %d degraded to "
                        "stop-resume: %s", reform.generation, exc)
            self.stop_reason = "reform-downgrade"
            self._migration.stop_requested.set()
            if self._reform_span is not None:
                self._reform_span.end(result=machine.result,
                                      error=machine.error)
                self._reform_span = None
            self._reform_t0 = None
            return "stop"
        if self.on_reform is not None:
            self.on_reform(reform.rank, reform.world_size, reform.cluster)
        if self._util_publisher is not None:
            # the scaler's unit contract: rates must be tagged with the
            # allocation (pod count) + generation that produced them
            self._util_publisher.world_size = reform.world_size
            self._util_publisher.generation = reform.generation
        self._migration.adopted(reform)
        self.reforms += 1
        return "reform"

    # -- reform phase executors (collective/reform.py ladder) --------------

    def _reform_quiesce(self, deadline: float) -> None:
        """Settle the device and (for device-world reforms) seal the
        LIVE state: peer-restore then reassembles exactly this step on
        the new mesh — a reform loses zero progress. Orchestration-only
        adoptions (no reform_mesh hook) keep the cheap drain."""
        if self._first_step_done:
            jax.block_until_ready(self.state)
        if self.ckpt is None:
            return
        if self.reform_mesh is not None:
            self._save()
        # TimeoutError here is the typed quiesce failure the machine
        # downgrades on (a writer that cannot drain is a torn world)
        self.ckpt.wait(timeout=max(0.1, deadline - time.monotonic()))
        if self._migration is not None and self.reform_mesh is not None:
            # make the fresh seal discoverable before peer-restore runs
            self._migration.flush_advert()

    def _reform_mesh_phase(self, reform, deadline: float):
        """Apply the new topology. The hook owns any jax.distributed
        re-initialization (reform_world) for true multi-host worlds and
        returns the new mesh, or None when this process's device world
        is unchanged (the fast adoption path)."""
        del deadline  # cooperative: the hook gets the machine's budget
        if self.reform_mesh is None:
            return None
        mesh = self.reform_mesh(reform.rank, reform.world_size,
                                reform.cluster)
        if mesh is None:
            return None
        log.info("reform: device world changed — new mesh %s",
                 getattr(mesh, "shape", mesh))
        self.mesh = mesh
        return mesh

    def _reform_target(self):
        """Zero state pytree shaped like the live state, placed for the
        NEW mesh — what the resharding planner assembles into."""
        import numpy as np
        zeros = jax.tree.map(
            lambda a: np.zeros(a.shape, a.dtype)
            if hasattr(a, "shape") else a, self.state)
        return self.place_state(zeros) if self.place_state else zeros

    def _reform_restore_peers(self, deadline: float) -> None:
        del deadline  # restore_from_peers carries its own wire timeouts
        # Sharded worlds merge every donor (versions are world-aligned
        # by the save barrier); replicated per-pod states restore from
        # their OWN just-sealed snapshot — per-pod version counters are
        # not comparable, and each pod's state is its own lineage.
        pods = None if self.config.ckpt_sharded \
            else [self._migration.pod_id]
        state, status, stats = self._migration.restore_from_peers(
            self._reform_target(),
            local_version=self.ckpt.latest_version()
            if self.ckpt else None, pods=pods)
        self.state = state
        self.status = status
        self.restore_source = "peers"
        self.bytes_from_peers = int(stats["bytes_from_peers"])

    def _reform_restore_disk(self, deadline: float) -> None:
        del deadline
        restored = self.ckpt.restore(self._reform_target()) \
            if self.ckpt else None
        if restored is None:
            raise RuntimeError("no sealed local checkpoint to fall "
                               "back to")
        state, status = restored
        if self.place_state is not None:
            state = self.place_state(state)
        self.state = state
        self.status = status
        self.restore_source = "disk"

    def ckpt_stats(self) -> dict:
        """Checkpoint-plane accounting for benchlog extras: the loop's
        save count + the manager's stall/snapshot/write/supersede stats
        (`ckpt_save_stall_ms_total` and `_mean` are the manager's; 0
        without a checkpoint directory)."""
        out = {"ckpt_save_stall_ms_total": 0.0,
               "ckpt_save_stall_ms_mean": 0.0,
               "ckpt_saves": self.ckpt_saves,
               "ckpt_async": bool(self.config.ckpt_async)}
        if self.restore_s is not None:
            out["ckpt_restore_s"] = round(self.restore_s, 3)
        # state-migration plane accounting (the demo's audits)
        out["restore_source"] = self.restore_source
        out["bytes_from_peers"] = self.bytes_from_peers
        out["reforms"] = self.reforms
        if self.last_reform_downtime_s is not None:
            out["reform_downtime_s"] = round(
                self.last_reform_downtime_s, 4)
        if self.last_reform is not None:
            # the state machine's outcome (result / restore source /
            # per-phase seconds) — what the --resize-reform demo audit
            # reads
            out["reform"] = self.last_reform
        if self.ckpt is not None:
            out.update({f"ckpt_{k}": (round(v, 3)
                                      if isinstance(v, float) else v)
                        for k, v in self.ckpt.stats().items()})
        return out

    # -- main loop ---------------------------------------------------------

    def _place(self, batch):
        # Device augmentation: the loader-emitted per-step seed comes off
        # the batch BEFORE placement (a 0-d scalar can't shard over the
        # batch axes); the jitted augment applies after. Batches already
        # augmented upstream (prefetch_to_device(augment=...)) carry no
        # seed and pass through; a seed with no augment_fn (or the
        # reverse) raises a wiring error instead of mis-sharding.
        seed = None
        if self.augment_fn is not None or (isinstance(batch, dict)
                                           and "augment_seed" in batch):
            from edl_tpu.data.pipeline import pop_augment_seed
            batch, seed = pop_augment_seed(batch, self.augment_fn)
        if self.mesh is not None:
            # form_global_batch degenerates to shard_batch in a
            # single-process world; in a multi-process world it treats
            # the fed batch as this process's slice of the global batch
            # (multipod contract).
            batch = mesh_lib.form_global_batch(self.mesh, batch,
                                               self.batch_axes)
        if self.augment_fn is not None:
            batch = self.augment_fn(batch, seed)
        return batch

    def run(self, data_fn: Callable[[int], Iterable],
            batch_size_fn: Callable[[Any], int] | None = None) -> TrainStatus:
        """Train from the resume point to num_epochs.

        data_fn(epoch) returns the epoch's batch iterator (the seed-per-pass
        hook: the callee should derive data order from the epoch number so an
        elastic restart replays the same order — reference reader_cv2
        pass_id_as_seed, train_with_fleet.py:459-464).
        """
        try:
            self.try_restore()
            cfg = self.config
            start_epoch = self.status.next_epoch()
            if start_epoch >= cfg.num_epochs:
                log.info("training already complete (epoch=%d)",
                         self.status.epoch)
                return self.status
            for epoch in range(start_epoch, cfg.num_epochs):
                outcome = self._run_epoch(epoch, data_fn, batch_size_fn)
                while outcome == "reform":
                    # In-place adoption: same epoch re-entered at the
                    # step cursor with the new (rank, world) — the
                    # mid-epoch resume machinery replays the skip, the
                    # state never leaves the devices.
                    outcome = self._run_epoch(epoch, data_fn,
                                              batch_size_fn)
                if outcome == "stop":
                    # Graceful stop (SIGTERM under the launcher): seal
                    # the live state so the donor linger serves the
                    # freshest params to the re-formed world, then exit
                    # 143 — the finally block drains the write and
                    # lingers. Raising (not returning) matters: an
                    # example main that returns 0 after run() would
                    # read to the launcher as "training complete" and
                    # mark the whole job done off a stray SIGTERM.
                    log.info("graceful stop at epoch %d step %d",
                             epoch, self.status.step)
                    self._save()
                    raise SystemExit(143)
                self.status.epoch = epoch
                self.status.step_in_epoch = 0
                if (epoch + 1) % max(1, cfg.ckpt_every_epochs) == 0 \
                        or epoch == cfg.num_epochs - 1:
                    self._save()
                if self.eval_fn is not None:
                    results = self.eval_fn(self.state, epoch)
                    log.info("eval epoch %d: %s", epoch, _fmt(results))
                if self.ckpt is not None:
                    # Epoch-end barrier: the epoch's (async) save becomes
                    # durable before the next epoch starts — its write
                    # overlapped eval above — and a background write
                    # failure surfaces here, not epochs later.
                    self.ckpt.wait()
            if self._profiling:  # run shorter than the window: still flush
                self._stop_profiler()
            return self.status
        finally:
            if self.ckpt is not None:
                # Shutdown barrier: drain the pending snapshot (crash
                # paths still seal their last state) without masking an
                # in-flight exception; clean-path write errors already
                # surfaced at the epoch-end wait() above.
                self.ckpt.close(raise_errors=False)
            if self.ckpt_saves:  # on every way out, the graceful stop's
                # SystemExit too, and after the drain: the last write counts
                log.info("ckpt plane: %s", self.ckpt_stats())
            if self._migration is not None:
                # After ckpt.close() so the drained final snapshot is
                # retained and served: on a graceful stop this lingers
                # as a donor until the re-formed world acks (bounded).
                try:
                    self._migration.shutdown()
                except Exception:  # noqa: BLE001 — teardown
                    log.exception("migration shutdown failed")
            # Even on a crash or the already-complete early return, the
            # lease must be revoked so a dead trainer's utilization
            # record expires instead of being kept fresh forever.
            if self._util_publisher is not None:
                self._util_publisher.stop()
            # The loop owns the lifetime of the data plane it drives: a
            # data_fn with a close() (DataLoader is callable and is one;
            # examples attach loader.close to their wrappers) gets its
            # decode pool / worker processes joined and shm unlinked —
            # including on the crash path, where an abandoned mp pool
            # would otherwise linger until GC.
            closer = getattr(data_fn, "close", None)
            if callable(closer):
                closer()

    def close(self) -> None:
        """Teardown for a loop that never ran (or whose owner wants a
        deterministic release without calling `run`): drain/stop the
        checkpoint writer, migration donor and utilization publisher.
        `run()` performs the same teardown on its own finally path —
        this exists so an owner that builds a TrainLoop and aborts
        before running it still has a joining close (edl-lint
        resource-lifecycle); idempotent either way."""
        if self.ckpt is not None:
            self.ckpt.close(raise_errors=False)
        if self._migration is not None:
            try:
                self._migration.shutdown()
            except Exception:  # noqa: BLE001 — teardown
                log.exception("migration shutdown failed")
        if self._util_publisher is not None:
            self._util_publisher.stop()

    def _profile_window(self) -> None:
        """Start/stop the jax profiler trace at the configured global
        steps (rank 0 only — one host's trace is the analysis unit)."""
        cfg = self.config
        if cfg.profile_dir is None or jax.process_index() != 0:
            return
        if self.status.step == cfg.profile_start_step \
                and not self._profiling:
            log.info("profiler: tracing steps %d..%d -> %s",
                     cfg.profile_start_step,
                     cfg.profile_start_step + cfg.profile_steps,
                     cfg.profile_dir)
            with trace.span("train.profiler"):
                jax.profiler.start_trace(cfg.profile_dir)
            # from here every span of this process, on any thread, is
            # also an event of the profiler's host plane: one file, one
            # clock with the device's lines
            trace.set_annotator(_annotation)
            self._profiling = True
        elif self._profiling and self.status.step >= \
                cfg.profile_start_step + cfg.profile_steps:
            self._stop_profiler()
            log.info("profiler: trace written to %s", cfg.profile_dir)
            _log_device_memory()

    def _stop_profiler(self) -> None:
        trace.set_annotator(None)
        with trace.span("train.profiler"):
            # force pending dispatches to land inside the trace; the
            # state is the live device data (last_metrics is already
            # host numpy by the time it's stored)
            jax.block_until_ready(self.state)
            jax.profiler.stop_trace()
            self._profiling = False
            trace.flush()   # spans-<pid>.jsonl beside the profiler's file

    def _step_annotation(self):
        """The profiler's own step marker around one iteration, while
        it runs (`Steps` in its viewers group by it)."""
        if self._profiling:
            return jax.profiler.StepTraceAnnotation(
                "train", step_num=self.status.step + 1)
        return contextlib.nullcontext()

    def _batches(self, it):
        """``it``'s items, the wait for each (the loader's next batch
        and its placement on the device) inside a span."""
        end = object()
        while True:
            with trace.span("train.loader_wait"):
                item = next(it, end)
            if item is end:
                return
            yield item

    def _epoch_iter(self, src, skip: int):
        """(index, device-placed batch) pairs starting at ``skip``.

        Skipping happens BEFORE placement so a mid-epoch resume never
        transfers already-trained batches. With ``prefetch_batches > 0``
        placement runs on a staging thread `prefetch_batches` deep, so
        the host->device copy of batch i+1 hides under step i.
        """
        end = object()
        it = iter(src)
        for _ in range(skip):
            if next(it, end) is end:
                return
        if self.config.prefetch_batches > 0:
            from edl_tpu.data.pipeline import prefetch
            staged = prefetch(it, size=self.config.prefetch_batches,
                              place=self._place)
            try:
                yield from enumerate(staged, start=skip)
            finally:
                staged.close()
        else:
            for i, batch in enumerate(it, start=skip):
                yield i, self._place(batch)

    def _run_epoch(self, epoch: int, data_fn, batch_size_fn) -> None:
        cfg = self.config
        window_start = time.perf_counter()
        window_samples = 0
        rejit_s = 0.0  # set at the first dispatch of an adopted reform
        # Intra-epoch resume: a mid-epoch checkpoint recorded how many steps
        # of this (deterministically re-generated, seed-per-pass) epoch were
        # already applied — skip exactly that many batches without training
        # or re-counting them. The data-level analogue of the reference's
        # record-skip design (collective/dataloader.py:100-120 "PROCSSED"
        # record ranges).
        skip = self.status.step_in_epoch
        if skip:
            log.info("resuming mid-epoch: skipping %d already-trained "
                     "batches of epoch %d", skip, epoch)
        src = data_fn(epoch)
        it = self._epoch_iter(src, skip)
        for i, batch in self._batches(it):
            if self._migration is not None:
                if self._migration.stop_requested.is_set():
                    # Graceful stop: leave at the step boundary with the
                    # cursor intact; run() seals the live state and the
                    # donor linger takes over.
                    self.stop_reason = "sigterm"
                    it.close()
                    return "stop"
                reform = self._migration.poll_reform()
                if reform is not None:
                    it.close()
                    # "reform" re-enters the epoch in place; "stop" is
                    # the machine's clean stop-resume downgrade
                    return self._adopt(reform)
            self._profile_window()
            with self._step_annotation():
                t_dispatch = time.perf_counter()
                with trace.span("train.dispatch", attrs={
                        "step": self.status.step + 1}) as sp:
                    self.state, metrics = self.step_fn(self.state, batch)
                    if sp is not None and not self._first_step_done:
                        # the first dispatch traces and compiles, or
                        # loads from the persistent cache
                        counts = distributed.compilation_cache_counts()
                        sp.attrs.update(
                            cache_hits=counts.get("hits", 0),
                            cache_misses=counts.get("misses", 0))
                if self._reform_t0 is not None:
                    # first dispatch of the adopted generation: the call
                    # wall covers trace + (cache-missing) compile — the
                    # re-jit phase of the reform ladder
                    rejit_s = time.perf_counter() - t_dispatch
                if not self._first_step_done:
                    # Downtime-accounting marker: the first step of THIS run
                    # (post-restore, post-compile) has really executed — the
                    # elastic kill->resume bench keys on this line, so force
                    # the dispatch before stamping it.
                    jax.block_until_ready(self.state)
                    self._first_step_done = True
                    log.info("first-step-complete global_step=%d restore_s=%s",
                             self.status.step + 1,
                             "%.3f" % self.restore_s
                             if self.restore_s is not None else "none")
                    log.info("first-step wall (trace+compile+run) %.3fs, "
                             "persistent compile cache %s",
                             time.perf_counter() - t_dispatch,
                             distributed.compilation_cache_counts())
                    _log_device_memory()
                    if self._migration is not None:
                        # restore ack: this pod is trained-and-running —
                        # what lingering donors and the resize audit key on
                        self._migration.ack(
                            self.restore_source or "fresh",
                            bytes_from_peers=self.bytes_from_peers,
                            restore_s=self.restore_s)
                        if self.restore_source == "peers" \
                                and trace.enabled() \
                                and self._util_publisher is not None:
                            # a grown pod's first fresh util closes the
                            # resize trace the same way an adoption's does
                            from edl_tpu.collective.migration import \
                                resize_trace_ctx
                            self._util_publisher.resize_trace = \
                                resize_trace_ctx(self._migration.store,
                                                 self._migration.job_id)
                if self._reform_t0 is not None:
                    # First step of the adopted generation: force the
                    # dispatch so the measured gap covers real training
                    # resumption, not an async enqueue.
                    t_block = time.perf_counter()
                    jax.block_until_ready(self.state)
                    now = time.perf_counter()
                    gap = now - self._reform_t0
                    self._reform_t0 = None
                    self.last_reform_downtime_s = gap
                    reform_doc = None
                    if self._reform_machine is not None:
                        # close the deferred ladder phases: the first
                        # post-reform step IS re-jit (dispatch wall; a
                        # compile-cache hit collapses it) + first-step
                        machine = self._reform_machine
                        self._reform_machine = None
                        machine.note_deferred("re-jit", rejit_s)
                        machine.note_deferred("first-step", now - t_block)
                        reform_doc = self.last_reform = machine.finish()
                    log.info("reform-step-complete generation=%d "
                             "downtime_s=%.3f",
                             self._migration.generation, gap)
                    flight.record("resize_adopt",
                                  pod=self._migration.pod_id,
                                  generation=self._migration.generation,
                                  downtime_s=round(gap, 4))
                    if self._reform_span is not None:
                        # the span covers reform -> first step of the new
                        # generation: duration == the measured survivor gap
                        self._reform_span.end(downtime_s=round(gap, 4))
                        if self._util_publisher is not None:
                            # first fresh util at the new world closes the
                            # trace (the scaler's downtime probe signal)
                            self._util_publisher.resize_trace = \
                                self._reform_span.context
                        self._reform_span = None
                    self._migration.ack(
                        "adopted", downtime_s=round(gap, 4),
                        bytes_from_peers=self.bytes_from_peers
                        if reform_doc and reform_doc.get("restore") == "peers"
                        else 0,
                        reform=reform_doc)
                self.status.step += 1
                self.status.step_in_epoch = i + 1
                n = (batch_size_fn(batch) if batch_size_fn
                     else _default_batch_size(batch))
                window_samples += n
                self.status.samples_seen += n
                if cfg.ckpt_every_steps and \
                        self.status.step % cfg.ckpt_every_steps == 0:
                    # epoch = last complete; step_in_epoch = cursor
                    self._save()
                if self.status.step % max(1, cfg.log_every_steps) == 0:
                    with trace.span("train.log_fetch"):
                        metrics = jax.device_get(metrics)
                    self.last_metrics = metrics
                    elapsed = time.perf_counter() - window_start
                    rate = window_samples / max(elapsed, 1e-9)
                    with trace.span("train.log_line"):
                        log.info("epoch %d step %d: %s %.1f samples/s",
                                 epoch, self.status.step, _fmt(metrics),
                                 rate)
                        for hook in self.hooks:
                            hook(self, epoch, self.status.step, metrics)
                    window_start = time.perf_counter()
                    window_samples = 0


def _annotation(name: str, attrs: dict):
    """A span as an event of the running profiler's host plane."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def _log_device_memory() -> None:
    """The fullest local chip's peak so far. This runtime books a
    running program's temporaries as reserved, not in use, so the peak
    counter alone misses them: log the larger of the two."""
    stats = [s for s in (_memory_stats(d) for d in jax.local_devices()) if s]
    if not stats:
        return
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    held = max(s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0)
               for s in stats)
    log.info("device-memory peak_bytes=%d (peak_bytes_in_use=%d, "
             "bytes_in_use+bytes_reserved=%d)", max(peak, held), peak, held)


def _memory_stats(device) -> dict | None:
    try:
        return device.memory_stats()
    except Exception:  # noqa: BLE001 — a backend without the counter
        return None


def _default_batch_size(batch) -> int:
    leaves = jax.tree.leaves(batch)
    return int(leaves[0].shape[0]) if leaves else 0


def _fmt(metrics: dict) -> str:
    parts = []
    for k, v in metrics.items():
        try:
            parts.append(f"{k}={float(v):.4f}")
        except (TypeError, ValueError):
            parts.append(f"{k}={v}")
    return " ".join(parts)
