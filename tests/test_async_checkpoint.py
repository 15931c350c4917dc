"""Async snapshot-then-write checkpointing: semantics and atomicity.

The contracts the elastic story leans on (CheckFreq/Check-N-Run recipe,
train/checkpoint.py `save_async`):

- snapshot isolation: the checkpoint holds the state AS OF the save
  call, however the live state mutates before the write runs;
- drop-to-latest: a queued unwritten snapshot is superseded by a newer
  one; an in-flight write is never aborted;
- wait()/close() barriers drain the writer; a background write failure
  surfaces on the NEXT save/wait call, and the manager recovers;
- sync and async saves produce bitwise-identical checkpoint bytes
  (replicated msgpack AND sharded chunk files);
- copy or hand over: a fetched array that may alias a live buffer
  (sharded_checkpoint.may_alias_device — always, on this CPU backend)
  reaches the writer as a private copy, every other one as the very
  object the fetch returned; no buffer is reused across saves;
- crash-mid-save atomicity: a writer killed between chunk writes and the
  seal leaves a torn .tmp dir that restore never sees and startup GC
  removes.
"""

import gc
import json
import os
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.obs import trace
from edl_tpu.parallel import mesh as mesh_lib, sharding as shd
from edl_tpu.train import sharded_checkpoint as sc
from edl_tpu.train.checkpoint import (CheckpointManager,
                                      CheckpointWriteError)
from edl_tpu.train.state import TrainState, TrainStatus

# tier-1 has no accelerator, so the aliasing predicate is forced both
# ways: "copy" is what it says of every array here, "hand-over" what it
# says of device memory on a chip
COPY_OR_HAND_OVER = pytest.mark.parametrize(
    "may_alias", [True, False], ids=["copy", "hand-over"])
PAYLOAD_FORMATS = pytest.mark.parametrize(
    "sharded", [False, True], ids=["replicated", "sharded"])


def _state(value: float) -> TrainState:
    params = {"w": jnp.full((4,), value), "b": jnp.zeros((2, 2))}
    return TrainState.create(apply_fn=lambda *a: None, params=params,
                             tx=optax.sgd(0.1))


def _w(state) -> float:
    return float(np.asarray(state.params["w"])[0])


# -- async semantics ---------------------------------------------------------


def test_async_roundtrip_and_wait_barrier(tmp_path):
    mgr = CheckpointManager(str(tmp_path), process_index=0)
    mgr.save_async(_state(1.5), TrainStatus(epoch=3, step=30))
    mgr.wait()
    # after the barrier the version is sealed and visible
    assert mgr.versions() == [0]
    restored, status = mgr.restore(_state(0.0))
    assert _w(restored) == 1.5
    assert status.epoch == 3 and status.step == 30
    mgr.close()


def test_snapshot_isolation_from_live_state_and_status(tmp_path):
    """The write happens later — it must capture save-call-time values,
    not whatever the training loop mutated them into since."""
    mgr = CheckpointManager(str(tmp_path), process_index=0)
    gate = threading.Event()
    real_write = mgr._write_replicated

    def gated_write(host_state, status):
        gate.wait(10.0)
        return real_write(host_state, status)

    mgr._write_replicated = gated_write
    state = _state(7.0)
    status = TrainStatus(epoch=1, step=10)
    mgr.save_async(state, status)
    # mutate the live objects while the write is still pending
    status.step = 999
    status.epoch = 42
    state = None  # the loop would donate/overwrite the buffers
    gate.set()
    mgr.wait()
    restored, got = mgr.restore(_state(0.0))
    assert _w(restored) == 7.0
    assert got.step == 10 and got.epoch == 1
    mgr.close()


def test_drop_to_latest_supersede_never_inflight(tmp_path):
    mgr = CheckpointManager(str(tmp_path), process_index=0)
    started = threading.Event()
    gate = threading.Event()
    real_write = mgr._write_replicated

    def gated_write(host_state, status):
        started.set()
        gate.wait(10.0)
        return real_write(host_state, status)

    mgr._write_replicated = gated_write
    mgr.save_async(_state(1.0), TrainStatus(step=1))
    assert started.wait(10.0)  # save #1 is IN FLIGHT (never aborted)
    mgr.save_async(_state(2.0), TrainStatus(step=2))  # queued ...
    mgr.save_async(_state(3.0), TrainStatus(step=3))  # ... superseded by #3
    gate.set()
    mgr.wait()
    # exactly two versions: the in-flight #1 and the latest #3; #2 died
    assert mgr.versions() == [0, 1]
    assert mgr.stats()["superseded"] == 1
    r1, s1 = mgr.restore(_state(0.0), version=0)
    r3, s3 = mgr.restore(_state(0.0), version=1)
    assert _w(r1) == 1.0 and s1.step == 1
    assert _w(r3) == 3.0 and s3.step == 3
    mgr.close()


def test_writer_error_surfaces_on_next_save_then_recovers(tmp_path):
    mgr = CheckpointManager(str(tmp_path), process_index=0)
    real_write = mgr._write_replicated
    boom = RuntimeError("disk on fire")

    def failing_write(host_state, status):
        raise boom

    mgr._write_replicated = failing_write
    mgr.save_async(_state(1.0), TrainStatus(step=1))  # enqueues fine
    # drain without raising (close(raise_errors=False) is the crash path)
    mgr.close(raise_errors=False)
    with pytest.raises(CheckpointWriteError) as exc_info:
        mgr.save_async(_state(2.0), TrainStatus(step=2))
    assert exc_info.value.__cause__ is boom
    # the error was consumed; the manager keeps working afterwards
    mgr._write_replicated = real_write
    mgr.save_async(_state(3.0), TrainStatus(step=3))
    mgr.wait()
    restored, status = mgr.restore(_state(0.0))
    assert _w(restored) == 3.0 and status.step == 3
    mgr.close()


def test_wait_raises_writer_error(tmp_path):
    mgr = CheckpointManager(str(tmp_path), process_index=0)
    mgr._write_replicated = lambda *a: (_ for _ in ()).throw(
        RuntimeError("boom"))
    mgr.save_async(_state(1.0), TrainStatus(step=1))
    with pytest.raises(CheckpointWriteError):
        mgr.wait()
    mgr.close()


def test_nonzero_rank_save_async_is_noop(tmp_path):
    mgr = CheckpointManager(str(tmp_path), process_index=1)
    mgr.save_async(_state(1.0), TrainStatus(step=1))
    mgr.wait()
    mgr.close()
    assert mgr.versions() == []


# -- bitwise identity --------------------------------------------------------


@COPY_OR_HAND_OVER
def test_sync_async_bitwise_identical_replicated(tmp_path, monkeypatch,
                                                 may_alias):
    monkeypatch.setattr(sc, "may_alias_device", lambda x: may_alias)
    state, status = _state(4.25), TrainStatus(epoch=2, step=20, world_size=8)
    sync_mgr = CheckpointManager(str(tmp_path / "sync"), process_index=0)
    sync_mgr.save(state, status)
    async_mgr = CheckpointManager(str(tmp_path / "async"), process_index=0)
    async_mgr.save_async(state, status)
    async_mgr.close()
    for name in ("state.msgpack", "meta.json"):
        a = (tmp_path / "sync" / "ckpt-0" / name).read_bytes()
        b = (tmp_path / "async" / "ckpt-0" / name).read_bytes()
        assert a == b, f"{name} differs between sync and async saves"


def _sharded_state(mesh):
    from edl_tpu.models.transformer import Transformer, TransformerConfig
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_len=64,
                            dtype=jnp.float32, mesh=mesh)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    variables = shd.init_sharded(
        lambda: model.init(jax.random.PRNGKey(0), toks, train=False), mesh)
    return TrainState.create(apply_fn=model.apply,
                             params=variables["params"],
                             tx=optax.adamw(1e-3))


@COPY_OR_HAND_OVER
def test_sync_async_bitwise_identical_sharded(tmp_path, monkeypatch,
                                              may_alias):
    monkeypatch.setattr(sc, "may_alias_device", lambda x: may_alias)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"fsdp": 2, "tp": 2}),
                              n_devices=4)
    state = _sharded_state(mesh)
    status = TrainStatus(epoch=1, step=5)
    sync_mgr = CheckpointManager(str(tmp_path / "sync"), sharded=True)
    sync_mgr.save(state, status)
    async_mgr = CheckpointManager(str(tmp_path / "async"), sharded=True)
    async_mgr.save_async(state, status)
    async_mgr.close()
    sdir, adir = tmp_path / "sync" / "ckpt-0", tmp_path / "async" / "ckpt-0"
    names = sorted(os.listdir(sdir))
    assert names == sorted(os.listdir(adir))
    for name in names:
        assert (sdir / name).read_bytes() == (adir / name).read_bytes(), \
            f"{name} differs between sync and async sharded saves"


def test_async_sharded_roundtrip_onto_other_mesh(tmp_path):
    big = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": 2, "fsdp": 2,
                                                "tp": 2}))
    state = _sharded_state(big)
    mgr = CheckpointManager(str(tmp_path), sharded=True)
    mgr.save_async(state, TrainStatus(epoch=0, step=1))
    mgr.wait()
    small = mesh_lib.make_mesh(mesh_lib.MeshSpec({"fsdp": 2, "tp": 2}),
                               n_devices=4)
    fresh = _sharded_state(small)
    restored, status = mgr.restore(fresh)
    assert status.step == 1
    for a, b in zip(jax.tree.leaves(jax.device_get(state)),
                    jax.tree.leaves(jax.device_get(restored))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


# -- copy or hand over -------------------------------------------------------


def _small_state(value: float, sharded: bool):
    """A device leaf split over two devices (sharded format) or on one,
    a replicated device leaf and a numpy leaf."""
    w = np.full((8, 4), value, np.float32)
    if sharded:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        w = jax.device_put(w, NamedSharding(mesh, P("dp")))
    return {"w": jnp.asarray(w), "b": jnp.arange(6.0),
            "n": np.arange(3, dtype=np.int32)}


def _spy(mgr, monkeypatch):
    """Record the arrays each fetch returned and the arrays each write
    was handed (a list per save, in call order)."""
    fetched, handed = [], []
    real_get, real_snap = jax.device_get, sc.snapshot_shards

    def device_get(x):
        out = real_get(x)
        fetched.append(jax.tree_util.tree_leaves(out))
        return out

    def snapshot_shards(state):
        snap = real_snap(state)
        fetched.append([a for _, a in snap["chunks"]])
        return snap

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(sc, "snapshot_shards", snapshot_shards)
    real_rep, real_sh = mgr._write_replicated, mgr._save_sharded

    def write_replicated(host_state, status):
        handed.append(jax.tree_util.tree_leaves(host_state))
        return real_rep(host_state, status)

    def save_sharded(state, status, snap=None):
        handed.append([a for _, a in snap["chunks"]])
        return real_sh(state, status, snap=snap)

    mgr._write_replicated, mgr._save_sharded = write_replicated, save_sharded
    return fetched, handed


@PAYLOAD_FORMATS
@COPY_OR_HAND_OVER
def test_writer_gets_private_copies_or_the_fetched_arrays(
        tmp_path, monkeypatch, may_alias, sharded):
    monkeypatch.setattr(sc, "may_alias_device", lambda x: may_alias)
    mgr = CheckpointManager(str(tmp_path), process_index=0, sharded=sharded)
    fetched, handed = _spy(mgr, monkeypatch)
    state = _small_state(2.5, sharded)
    trace.collect(str(tmp_path / "prof"))
    try:
        mgr.save_async(state, TrainStatus(step=7))
        mgr.wait()
        (snap,) = trace.finished("ckpt.snapshot")
        stages = trace.finished("ckpt.stage")
    finally:
        trace.reconfigure()
    (got,), gave = handed, fetched[0]
    nbytes = sum(a.nbytes for a in gave)
    assert len(got) == len(gave) == (4 if sharded else 3)
    assert snap["attrs"]["bytes"] == nbytes > 0
    if may_alias:
        assert all(not np.shares_memory(g, f) for g in got for f in gave)
        assert snap["attrs"]["copied_bytes"] == nbytes
        assert mgr.stats()["copied_bytes_last"] == nbytes
        assert [s["parent"] for s in stages] == [snap["sid"]]
    else:
        assert all(g is f for g, f in zip(got, gave))
        assert snap["attrs"]["copied_bytes"] == 0
        assert mgr.stats()["copied_bytes_last"] == 0
        assert stages == []  # the span exists only where a copy does
    restored, status = mgr.restore(_small_state(0.0, sharded))
    assert status.step == 7
    for k in state:
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(state[k]))
    mgr.close()


@PAYLOAD_FORMATS
def test_the_predicate_says_copy_for_everything_on_the_cpu(tmp_path,
                                                           sharded):
    """Unforced: every array of a CPU state may be a view of a live
    buffer (a device array's fetch is zero-copy here, a numpy leaf is
    its owner's own object), so all of it is copied, as before."""
    state = _small_state(1.0, sharded)
    assert all(sc.may_alias_device(x) for x in state.values())
    assert all(sc.may_alias_device(s.data)
               for s in state["w"].addressable_shards)
    mgr = CheckpointManager(str(tmp_path), process_index=0, sharded=sharded)
    mgr.save_async(state, TrainStatus(step=1))
    mgr.close()
    assert mgr.stats()["copied_bytes_last"] == sum(
        np.asarray(x).nbytes for x in state.values())


@PAYLOAD_FORMATS
@COPY_OR_HAND_OVER
def test_superseded_dropped_and_retained_kept_without_a_pool(
        tmp_path, monkeypatch, may_alias, sharded):
    """No buffer is reused across saves: a superseded snapshot is freed,
    and a retained one that a peer still reads keeps its bytes when
    newer saves seal."""
    monkeypatch.setattr(sc, "may_alias_device", lambda x: may_alias)
    mgr = CheckpointManager(str(tmp_path), process_index=0, sharded=sharded)
    mgr.retain_sealed = True
    fetched, handed = _spy(mgr, monkeypatch)
    started, gate = threading.Event(), threading.Event()
    write = "_save_sharded" if sharded else "_write_replicated"
    spied = getattr(mgr, write)

    def gated(*a, **kw):
        started.set()
        assert gate.wait(10.0)
        return spied(*a, **kw)

    setattr(mgr, write, gated)
    mgr.save_async(_small_state(1.0, sharded), TrainStatus(step=1))
    assert started.wait(10.0)                      # 1 is in flight ...
    mgr.save_async(_small_state(2.0, sharded), TrainStatus(step=2))
    with mgr._cond:                                # ... 2 is queued ...
        job = mgr._pending
        queued = (job["snap"]["chunks"] if sharded
                  else list(job["tree"].items()))
        doomed = [weakref.ref(a) for _, a in queued
                  if isinstance(a, np.ndarray)]
        del job, queued
    assert doomed and all(r() is not None for r in doomed)
    mgr.save_async(_small_state(3.0, sharded), TrainStatus(step=3))
    del fetched[:]                                 # ... and superseded
    gc.collect()
    if may_alias:  # private copies: the job was their only owner
        assert all(r() is None for r in doomed)
    gate.set()
    mgr.wait()
    assert mgr.versions() == [0, 1] and mgr.stats()["superseded"] == 1
    first = mgr.sealed_snapshot()
    assert first["version"] == 1 and first["status"]["step"] == 3
    # the retained payload is what the writer was handed (no re-copy)
    assert all(any(a is h for h in handed[-1])
               for a in first["chunks"].values())
    held = {k: np.array(a) for k, a in first["chunks"].items()}
    for step in (4, 5, 6):                         # three more seals
        mgr.save_async(_small_state(float(step), sharded),
                       TrainStatus(step=step))
        mgr.wait()
    assert mgr.sealed_snapshot()["status"]["step"] == 6
    for k, a in first["chunks"].items():
        np.testing.assert_array_equal(a, held[k])
    mgr.close()


# -- crash-mid-save atomicity + startup GC -----------------------------------


def test_crash_between_chunks_and_seal_falls_back_and_gcs(tmp_path):
    """Kill the writer after the chunk writes but before the seal: the
    torn .tmp dir must never be visible to restore (previous sealed
    version wins) and must be GC'd at the next start."""
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"fsdp": 2, "tp": 2}),
                              n_devices=4)
    state = _sharded_state(mesh)
    mgr = CheckpointManager(str(tmp_path), sharded=True)
    assert mgr.save(state, TrainStatus(epoch=0, step=10)) == 0

    # the "crash": chunks + index of version 1 land in the pending dir,
    # but the writer dies before meta.json + the atomic rename
    torn = tmp_path / ".tmp-ckpt-1"
    sc.save_sharded(str(torn), state)
    assert torn.is_dir() and not (torn / "meta.json").exists()

    # a re-formed world restores the previous SEALED version
    mgr2 = CheckpointManager(str(tmp_path), sharded=True)
    assert mgr2.latest_version() == 0
    restored, status = mgr2.restore(_sharded_state(mesh))
    assert status.step == 10

    # ... and startup GC (the TrainLoop.try_restore path) removes the
    # torn dir instead of leaking it forever
    mgr2.gc_stale_tmp()
    assert not torn.exists()
    assert mgr2.versions() == [0]


def test_train_loop_startup_gcs_torn_tmp(tmp_path):
    """The trainer start path itself sweeps torn partial saves."""
    from edl_tpu.examples import fit_a_line
    from edl_tpu.parallel.mesh import make_mesh
    from edl_tpu.train.loop import LoopConfig, TrainLoop

    for name in (".tmp-ckpt-7", ".tmp-refetch-x"):
        (tmp_path / name).mkdir()
        (tmp_path / name / "leaf0-o0.npy").write_bytes(b"torn")
    cfg = fit_a_line.Config(num_epochs=1, steps_per_epoch=3)
    state, step_fn = fit_a_line.build(cfg)
    loop = TrainLoop(step_fn, state, mesh=make_mesh(),
                     config=LoopConfig(num_epochs=1, ckpt_dir=str(tmp_path),
                                       log_every_steps=1000))
    loop.run(lambda e: fit_a_line.synthetic_batches(e, cfg))
    assert not any(n.startswith(".tmp-") for n in os.listdir(tmp_path))
    assert loop.status.epoch == 0  # and training completed


# -- restore: parallel region reads + one open per chunk ---------------------


def test_restore_parallel_matches_serial(tmp_path):
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": 2, "fsdp": 2,
                                                 "tp": 2}))
    state = _sharded_state(mesh)
    sc.save_sharded(str(tmp_path / "s"), state)
    fresh = _sharded_state(mesh)
    serial = sc.restore_sharded(str(tmp_path / "s"), fresh, threads=1)
    parallel = sc.restore_sharded(str(tmp_path / "s"), fresh, threads=4)
    for a, b in zip(jax.tree.leaves(jax.device_get(serial)),
                    jax.tree.leaves(jax.device_get(parallel))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_opens_each_chunk_once(tmp_path, monkeypatch):
    """A resharding restore intersects each chunk with many target
    regions; the handle cache must np.load each file once, not once per
    region."""
    small = mesh_lib.make_mesh(mesh_lib.MeshSpec({"fsdp": 2, "tp": 2}),
                               n_devices=4)
    state = _sharded_state(small)
    sc.save_sharded(str(tmp_path / "s"), state)

    opens: dict[str, int] = {}
    real_load = np.load

    def counting_load(path, *a, **kw):
        opens[os.path.basename(str(path))] = \
            opens.get(os.path.basename(str(path)), 0) + 1
        return real_load(path, *a, **kw)

    monkeypatch.setattr(sc.np, "load", counting_load)
    # 4 -> 8 devices: every saved chunk feeds multiple target shards
    big = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": 2, "fsdp": 2,
                                                "tp": 2}))
    sc.restore_sharded(str(tmp_path / "s"), _sharded_state(big))
    assert opens, "no chunk reads recorded"
    multi = [n for n, c in opens.items() if c > 1]
    assert not multi, f"chunks re-opened per region: {multi}"


def test_restore_threads_env_knob(monkeypatch):
    monkeypatch.setenv("EDL_TPU_CKPT_RESTORE_THREADS", "3")
    assert sc.restore_threads() == 3
    monkeypatch.setenv("EDL_TPU_CKPT_RESTORE_THREADS", "bogus")
    assert sc.restore_threads() >= 1
    monkeypatch.delenv("EDL_TPU_CKPT_RESTORE_THREADS")
    assert sc.restore_threads() >= 1


# -- TrainLoop integration ---------------------------------------------------


def test_loop_async_saves_match_sync_saves(tmp_path):
    """ckpt_async must not change WHAT gets checkpointed — final
    checkpoint bytes of an async run equal the sync run's."""
    from edl_tpu.examples import fit_a_line
    from edl_tpu.parallel.mesh import make_mesh
    from edl_tpu.train.loop import LoopConfig, TrainLoop

    def run(subdir, ckpt_async):
        cfg = fit_a_line.Config(num_epochs=2, steps_per_epoch=6)
        state, step_fn = fit_a_line.build(cfg)
        loop = TrainLoop(step_fn, state, mesh=make_mesh(),
                         config=LoopConfig(num_epochs=2,
                                           ckpt_dir=str(tmp_path / subdir),
                                           ckpt_every_steps=4,
                                           ckpt_async=ckpt_async,
                                           log_every_steps=1000))
        loop.run(lambda e: fit_a_line.synthetic_batches(e, cfg))
        return loop

    sync_loop, async_loop = run("sync", False), run("async", True)
    assert async_loop.ckpt_saves == sync_loop.ckpt_saves
    stats = async_loop.ckpt_stats()
    assert stats["ckpt_saves_async"] > 0 and stats["ckpt_errors"] == 0
    # On a loaded host the writer may legally coalesce back-to-back
    # saves (a snapshot superseded before its write starts), so the
    # version LISTS can differ; every save must still be accounted for
    # as either a write or a supersede...
    assert stats["ckpt_writes"] + stats["ckpt_superseded"] == \
        stats["ckpt_saves_async"]

    # ...and the NEWEST checkpoint — what a restore would see — must be
    # byte-identical to the sync run's.
    def newest(subdir):
        versions = sorted(os.listdir(tmp_path / subdir),
                          key=lambda v: int(v.rsplit("-", 1)[-1]))
        return tmp_path / subdir / versions[-1]

    assert (newest("async") / "state.msgpack").read_bytes() == \
        (newest("sync") / "state.msgpack").read_bytes()


def test_loop_surfaces_writer_failure(tmp_path):
    """A background write failure must fail the RUN (at the epoch-end
    wait barrier), not vanish into a daemon thread."""
    from edl_tpu.examples import fit_a_line
    from edl_tpu.parallel.mesh import make_mesh
    from edl_tpu.train.loop import LoopConfig, TrainLoop

    cfg = fit_a_line.Config(num_epochs=1, steps_per_epoch=4)
    state, step_fn = fit_a_line.build(cfg)
    loop = TrainLoop(step_fn, state, mesh=make_mesh(),
                     config=LoopConfig(num_epochs=1,
                                       ckpt_dir=str(tmp_path / "ck"),
                                       log_every_steps=1000))
    loop.ckpt._write_replicated = lambda *a: (_ for _ in ()).throw(
        OSError("no space left on device"))
    with pytest.raises(CheckpointWriteError):
        loop.run(lambda e: fit_a_line.synthetic_batches(e, cfg))


def test_status_json_matches_sync_semantics(tmp_path):
    """meta.json of an async mid-epoch save records the cursor AS OF the
    save step (the resume contract), not the end-of-run cursor."""
    from edl_tpu.examples import fit_a_line
    from edl_tpu.parallel.mesh import make_mesh
    from edl_tpu.train.loop import LoopConfig, TrainLoop

    cfg = fit_a_line.Config(num_epochs=1, steps_per_epoch=10)
    state, step_fn = fit_a_line.build(cfg)
    loop = TrainLoop(step_fn, state, mesh=make_mesh(),
                     config=LoopConfig(num_epochs=1,
                                       ckpt_dir=str(tmp_path),
                                       ckpt_every_steps=4,
                                       log_every_steps=1000))
    loop.run(lambda e: fit_a_line.synthetic_batches(e, cfg))
    with open(tmp_path / "ckpt-0" / "meta.json") as f:
        meta = json.load(f)
    assert meta["status"]["step"] == 4
    assert meta["status"]["step_in_epoch"] == 4
