"""Peer-to-peer state migration: restore-path equivalence + fencing.

The contract under test (collective/migration.py): a state restored
from live donors over the tensor wire is BITWISE identical to the same
version restored from disk — replicated and sharded layouts, including
cross-mesh resharding — and every failure mode (donor death
mid-transfer, stale donors, a donor resealing mid-restore) degrades to
the disk path without corrupting the world. The full multi-process loop
(launchers + scripted /resize shrink/grow with the in-place-adoption
audit) runs in the slow tier via `elastic_demo --resize-p2p`.
"""

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from edl_tpu.collective import migration as mig
from edl_tpu.coord.store import InMemStore
from edl_tpu.train import sharded_checkpoint as sc
from edl_tpu.train.checkpoint import CheckpointManager
from edl_tpu.train.state import TrainStatus


def wait_until(pred, timeout=5.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    pytest.fail(f"timeout waiting for {what}")


def make_service(store, ckpt=None, pod="pod0", job="mjob"):
    svc = mig.MigrationService(store, job, pod, addr="127.0.0.1")
    if ckpt is not None:
        svc.attach(ckpt)
    return svc


def assert_trees_bitwise(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, (x.dtype, y.dtype)
        assert np.array_equal(x, y), "peer- and disk-restored leaves differ"


def rep_state():
    rng = np.random.default_rng(7)
    return {"w": rng.normal(size=(8, 16)).astype(np.float32),
            "b": rng.normal(size=(16,)).astype(np.float64),
            "step": 41}


def rep_target():
    return {"w": np.zeros((8, 16), np.float32),
            "b": np.zeros((16,), np.float64), "step": 0}


class TestPeerRestoreEquivalence:
    def test_replicated_peer_restore_bitwise_identical_to_disk(
            self, tmp_path):
        store = InMemStore()
        mgr = CheckpointManager(str(tmp_path / "c"), process_index=0)
        svc = make_service(store, mgr)
        try:
            mgr.save(rep_state(), TrainStatus(epoch=2, step=41))
            wait_until(lambda: mig.live_donors(store, "mjob"),
                       what="donor advert")
            peer, pstatus, stats = mig.restore_from_peers(
                store, "mjob", rep_target())
            disk, dstatus = mgr.restore(rep_target())
            assert_trees_bitwise(peer, disk)
            assert pstatus.to_dict() == dstatus.to_dict()
            assert stats["bytes_from_peers"] > 0
        finally:
            svc.shutdown(linger=False)

    @pytest.mark.parametrize("tgt_n", [2, 8])
    def test_sharded_peer_restore_reshards_bitwise(self, tmp_path,
                                                   tgt_n):
        """A state saved dp-sharded on 4 devices restores onto a 2- and
        an 8-device mesh identically through peers and disk (the same
        region planner drives both)."""
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs the 8-virtual-device test mesh")
        src = Mesh(np.array(devs[:4]), ("dp",))
        tgt = Mesh(np.array(devs[:tgt_n]), ("dp",))
        rng = np.random.default_rng(3)
        state = {f"l{i}": jax.device_put(
            rng.normal(size=(16, 6)).astype(np.float32),
            NamedSharding(src, P("dp"))) for i in range(3)}

        store = InMemStore()
        mgr = CheckpointManager(str(tmp_path / "c"), process_index=0,
                                sharded=True)
        svc = make_service(store, mgr)
        try:
            mgr.save(state, TrainStatus(epoch=0, step=7))
            wait_until(lambda: mig.live_donors(store, "mjob"),
                       what="donor advert")

            def target():
                return {k: jax.device_put(
                    np.zeros((16, 6), np.float32),
                    NamedSharding(tgt, P("dp"))) for k in state}

            peer, _, stats = mig.restore_from_peers(store, "mjob",
                                                    target())
            disk, _ = mgr.restore(target())
            assert_trees_bitwise(peer, disk)
            assert_trees_bitwise(peer, state)
            assert stats["bytes_from_peers"] \
                == sum(np.asarray(v).nbytes for v in state.values())
        finally:
            svc.shutdown(linger=False)


class TestExpertReshard:
    """ep elasticity: MoE expert tables (leading dim sharded P('ep') by
    sharding.DEFAULT_RULES) reshard through the SAME planner as every
    other sharded leaf — peer restore onto a shrunk or grown ep mesh is
    bitwise identical to disk, with zero process restarts (everything
    here happens in-process over the tensor wire)."""

    @staticmethod
    def _expert_state(mesh, rng):
        from edl_tpu.parallel.sharding import logical_to_spec
        spec = logical_to_spec(("expert", "embed", "mlp"), mesh=mesh)
        assert spec == P("ep")
        return {f"block{i}.moe_mlp.{name}": jax.device_put(
            rng.normal(size=(8, 4, 6)).astype(np.float32),
            NamedSharding(mesh, spec))
            for i in range(2) for name in ("w_in", "w_out")}

    @pytest.mark.parametrize("tgt_n", [2, 8])
    def test_expert_tables_peer_reshard_bitwise(self, tmp_path, tgt_n):
        """Expert tables saved ep=4 restore onto ep=2 (shrink: each
        chip adopts two experts' rows) and ep=8 (grow: rows split)
        identically through peers and disk."""
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs the 8-virtual-device test mesh")
        src = Mesh(np.array(devs[:4]), ("ep",))
        tgt = Mesh(np.array(devs[:tgt_n]), ("ep",))
        rng = np.random.default_rng(13)
        state = self._expert_state(src, rng)

        store = InMemStore()
        mgr = CheckpointManager(str(tmp_path / "c"), process_index=0,
                                sharded=True)
        svc = make_service(store, mgr)
        try:
            mgr.save(state, TrainStatus(epoch=0, step=11))
            wait_until(lambda: mig.live_donors(store, "mjob"),
                       what="donor advert")

            def target():
                return {k: jax.device_put(
                    np.zeros((8, 4, 6), np.float32),
                    NamedSharding(tgt, P("ep"))) for k in state}

            peer, _, stats = mig.restore_from_peers(store, "mjob",
                                                    target())
            disk, _ = mgr.restore(target())
            assert_trees_bitwise(peer, disk)
            assert_trees_bitwise(peer, state)
            assert stats["bytes_from_peers"] > 0
            # every restored leaf really lands ep-sharded on the new
            # mesh: one distinct expert row range per chip
            for v in peer.values():
                assert len(v.sharding.device_set) == tgt_n
        finally:
            svc.shutdown(linger=False)

    def test_expert_resize_round_trip_bitwise(self, tmp_path):
        """The full 4 -> 2 -> 4 resize cycle: shrink onto 2 chips,
        re-save from the shrunk world, grow back — tables return to
        the original placement bitwise (no quantization, no reorder:
        the planner moves expert rows, never rewrites them)."""
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs the 8-virtual-device test mesh")
        m4 = Mesh(np.array(devs[:4]), ("ep",))
        m2 = Mesh(np.array(devs[:2]), ("ep",))
        rng = np.random.default_rng(17)
        state = self._expert_state(m4, rng)

        def target(mesh):
            return {k: jax.device_put(
                np.zeros((8, 4, 6), np.float32),
                NamedSharding(mesh, P("ep"))) for k in state}

        d1 = str(tmp_path / "ep4")
        sc.save_sharded(d1, state)
        shrunk = sc.restore_sharded(d1, target(m2))
        assert_trees_bitwise(shrunk, state)
        d2 = str(tmp_path / "ep2")
        sc.save_sharded(d2, shrunk)
        regrown = sc.restore_sharded(d2, target(m4))
        assert_trees_bitwise(regrown, state)
        for v in regrown.values():
            assert len(v.sharding.device_set) == 4


class _FetchDropsServer(mig.MigrationServer):
    """Donor that dies mid-transfer: serves the manifest, then drops
    the connection on the first chunk fetch."""

    def _handle(self, conn, meta):
        if meta.get("op") == "fetch":
            conn.close()
            raise OSError("donor died mid-transfer")
        super()._handle(conn, meta)


class _ResealsServer(mig.MigrationServer):
    """Donor that seals a NEWER version between manifest and fetch."""

    def _handle(self, conn, meta):
        if meta.get("op") == "fetch":
            newer = dict(self.snapshot())
            newer["version"] = newer["version"] + 1
            self.publish(newer)
        super()._handle(conn, meta)


def publish_donor(store, server, snap, job="mjob", pod="pod0"):
    server.publish(snap)
    store.put(mig.donor_key(job, pod), json.dumps(
        {"pod_id": pod, "addr": "127.0.0.1", "port": server.port,
         "version": snap["version"]}))


def serveable(state, version=0, step=9):
    snap = sc.snapshot_host_tree(state)
    return {"version": version,
            "status": TrainStatus(step=step).to_dict(),
            "process_index": 0, "leaves": snap["leaves"],
            "chunks": dict(snap["chunks"])}


class TestFallbackAndFencing:
    def test_donor_death_mid_transfer_falls_back_to_disk(self, tmp_path):
        """The donor serves its manifest then drops every fetch: peer
        restore must raise (not hang, not return garbage) and the disk
        restore of the SAME version must still produce intact state."""
        store = InMemStore()
        state = rep_state()
        mgr = CheckpointManager(str(tmp_path / "c"), process_index=0)
        mgr.save(state, TrainStatus(step=9))
        server = _FetchDropsServer(host="127.0.0.1")
        try:
            publish_donor(store, server, serveable(state))
            with pytest.raises(mig.PeerRestoreError):
                mig.restore_from_peers(store, "mjob", rep_target())
            disk, status = mgr.restore(rep_target())
            assert_trees_bitwise(disk, state)
            assert status.step == 9
        finally:
            server.stop()

    def test_loop_try_restore_survives_peer_failure(self, tmp_path,
                                                    monkeypatch):
        """TrainLoop.try_restore: a failing migration plane degrades to
        the disk path transparently (restore_source records it)."""
        from edl_tpu.examples import fit_a_line
        from edl_tpu.parallel.mesh import make_mesh
        from edl_tpu.train.loop import LoopConfig, TrainLoop

        cfg = fit_a_line.Config(num_epochs=1, steps_per_epoch=5)
        state, step_fn = fit_a_line.build(cfg)
        loop = TrainLoop(step_fn, state, mesh=make_mesh(),
                         config=LoopConfig(num_epochs=1,
                                           ckpt_dir=str(tmp_path)))
        loop.run(lambda e: fit_a_line.synthetic_batches(e, cfg))

        loop2 = TrainLoop(step_fn, state, mesh=make_mesh(),
                          config=LoopConfig(num_epochs=1,
                                            ckpt_dir=str(tmp_path)))

        class _BrokenMigration:
            def restore_from_peers(self, target, **kw):
                raise mig.PeerRestoreError("no live donors advertised")
        loop2._migration = _BrokenMigration()
        assert loop2.try_restore()
        assert loop2.restore_source == "disk"
        loop2._migration = None

    def test_stale_donors_fenced_by_local_disk_version(self, tmp_path):
        """Epoch fence: donors serving an OLDER version than this pod's
        own sealed disk checkpoint are refused (total-kill recovery must
        not resurrect an old state via a lagging donor)."""
        store = InMemStore()
        state = rep_state()
        mgr = CheckpointManager(str(tmp_path / "c"), process_index=0)
        mgr.save(state, TrainStatus(step=1))   # ckpt-0
        mgr.save(state, TrainStatus(step=2))   # ckpt-1
        server = mig.MigrationServer(host="127.0.0.1")
        try:
            publish_donor(store, server, serveable(state, version=0))
            with pytest.raises(mig.PeerRestoreError, match="stale"):
                mig.restore_from_peers(
                    store, "mjob", rep_target(),
                    local_version=mgr.latest_version())
        finally:
            server.stop()

    def test_donor_resealing_mid_restore_is_fenced(self, tmp_path):
        """A donor that seals a newer version between the manifest and
        a chunk fetch must not hand the restorer a mixed-step state —
        the version fence turns it into a disk fallback."""
        store = InMemStore()
        state = rep_state()
        server = _ResealsServer(host="127.0.0.1")
        try:
            publish_donor(store, server, serveable(state, version=3))
            with pytest.raises(mig.PeerRestoreError,
                               match="mid-restore"):
                mig.restore_from_peers(store, "mjob", rep_target())
        finally:
            server.stop()

    def test_no_donors_raises(self):
        with pytest.raises(mig.PeerRestoreError, match="no live donors"):
            mig.restore_from_peers(InMemStore(), "mjob", rep_target())

    def test_merge_leaf_tables_shape_mismatch_raises(self):
        t1 = [{"key": "w", "shape": [4], "dtype": "float32",
               "chunks": []}]
        t2 = [{"key": "w", "shape": [8], "dtype": "float32",
               "chunks": []}]
        with pytest.raises(ValueError, match="shape mismatch"):
            sc.merge_leaf_tables([t1, t2])


class TestSealedRetention:
    def test_async_saves_retain_newest_sealed_snapshot(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), process_index=0)
        mgr.retain_sealed = True
        state = rep_state()
        mgr.save_async(state, TrainStatus(step=1))
        mgr.close()
        snap = mgr.sealed_snapshot()
        assert snap is not None and snap["version"] == 0
        assert snap["status"]["step"] == 1
        # a previously handed-out snapshot survives the next seal intact
        # (retained payloads are never recycled into the staging pool)
        w0 = snap["chunks"][snap["leaves"][0]["chunks"][0]["file"]]
        w0_copy = np.array(w0)
        state2 = {**rep_state(), "w": np.full((8, 16), 5.0, np.float32)}
        mgr.save_async(state2, TrainStatus(step=2))
        mgr.close()
        assert mgr.sealed_snapshot()["version"] == 1
        assert np.array_equal(w0, w0_copy), \
            "older retained snapshot was overwritten while serveable"

    def test_sync_sharded_save_retains_a_copy(self, tmp_path):
        devs = jax.devices()
        mesh = Mesh(np.array(devs[:2]), ("dp",))
        state = {"w": jax.device_put(
            np.arange(32, dtype=np.float32).reshape(8, 4),
            NamedSharding(mesh, P("dp")))}
        mgr = CheckpointManager(str(tmp_path), process_index=0,
                                sharded=True)
        mgr.retain_sealed = True
        mgr.save(state, TrainStatus(step=3))
        snap = mgr.sealed_snapshot()
        assert snap["version"] == 0
        total = sum(a.nbytes for a in snap["chunks"].values())
        assert total == 32 * 4


class TestResizeEpochPublish:
    def test_resize_publishes_epoch_with_donor_roster(self):
        from edl_tpu.collective.job_server import JobState
        store = InMemStore()
        store.put(mig.donor_key("j", "podA"), json.dumps(
            {"pod_id": "podA", "addr": "127.0.0.1", "port": 1234,
             "version": 5}))
        state = JobState("j", 1, 4, desired=2, store=store)
        state.resize(3)
        doc = json.loads(store.get(mig.epoch_key("j")).value)
        assert doc["epoch"] == 1 and doc["desired"] == 3
        assert doc["from"] == 2
        assert [d["pod_id"] for d in doc["donors"]] == ["podA"]
        # unchanged desired -> no new epoch (fencing stays monotonic)
        state.resize(3)
        assert json.loads(
            store.get(mig.epoch_key("j")).value)["epoch"] == 1
        state.random_resize()
        assert json.loads(
            store.get(mig.epoch_key("j")).value)["epoch"] == 2


def seed_job(store, job="j1", world=2, rate=120.0, now=None):
    """A live job in the store: rank claims + cluster + fresh util."""
    from edl_tpu.collective.cluster import Cluster, Pod
    from edl_tpu.collective.register import cluster_key, rank_key
    from edl_tpu.coord.collector import util_key
    now = time.time() if now is None else now
    pods = []
    for i in range(world):
        pod_id = f"pod{i}"
        store.put(rank_key(job, i),
                  Pod(pod_id=pod_id, addr=f"10.0.0.{i}", n_devices=1,
                      claimed_rank=i, rank=i).to_json(),
                  lease=store.lease_grant(30.0))
        store.put(util_key(job, pod_id),
                  json.dumps({"pod_id": pod_id, "step": 10,
                              "examples_per_sec": rate / world,
                              "world_size": world,
                              "published_unix": now}),
                  lease=store.lease_grant(30.0))
        pods.append(Pod(pod_id=pod_id, addr=f"10.0.0.{i}", rank=i))
    store.put(cluster_key(job),
              Cluster(job_id=job, version=world, pods=pods).to_json())


class TestMeasuredDowntimeFeedback:
    def _controller(self, store, state, clock):
        from edl_tpu.scaler.controller import (ScalerConfig,
                                               ScalerController)
        from edl_tpu.scaler.policy import ThroughputPolicy
        return ScalerController(
            store, [state.job_id],
            ThroughputPolicy(gain_threshold=0.05, cooldown_s=1.0,
                             horizon_s=60.0),
            config=ScalerConfig(cooldown_s=1.0, downtime_s=1.5,
                                staleness_s=3600.0),
            actuate=lambda _job, desired: state.resize(desired),
            elect=False, clock=clock)

    def test_observed_downtime_replaces_configured_constant(self):
        """The amortization charge follows the MEASURED resize price:
        actuation -> first fresh utilization at the new world closes the
        probe, the EWMA lands in subsequent JobViews and the journal,
        and a takeover controller replays it."""
        from edl_tpu.collective.job_server import JobState
        from edl_tpu.scaler.controller import journal_prefix
        store = InMemStore()
        t0 = time.time()
        now = [t0]
        seed_job(store, world=2, now=t0)
        state = JobState("j1", 1, 4, desired=2)
        ctl = self._controller(store, state, clock=lambda: now[0])
        (entry,) = ctl.tick()
        assert entry["action"] == "resize" and entry["applied"] == 3
        # before any observation: the configured fallback is the charge
        assert entry["downtime_s"] == 1.5

        # 0.4s later the re-formed world publishes fresh utilization
        now[0] = t0 + 0.4
        seed_job(store, world=3, rate=150.0, now=now[0])
        view = ctl.observe("j1", now=now[0])
        assert view.downtime_s == pytest.approx(0.4, abs=1e-6)

        # the next tick journals the measurement alongside the charge
        # it actually used
        now[0] = t0 + 1.3  # past cooldown
        (entry,) = ctl.tick()
        assert entry["downtime_s"] == pytest.approx(0.4, abs=0.01)
        assert entry["observed_downtime_s"] == pytest.approx(0.4,
                                                             abs=0.01)
        recs, _ = store.get_prefix(journal_prefix("j1"))
        journaled = [json.loads(r.value).get("observed_downtime_s")
                     for r in recs]
        assert any(m is not None for m in journaled)
        ctl.stop()

    def test_journal_replay_reseeds_measured_downtime(self):
        from edl_tpu.collective.job_server import JobState
        store = InMemStore()
        t0 = time.time()
        now = [t0]
        seed_job(store, world=2, now=t0)
        state = JobState("j1", 1, 4, desired=2)
        ctl = self._controller(store, state, clock=lambda: now[0])
        ctl.tick()                       # resize 2->3, probe armed
        now[0] = t0 + 0.5
        seed_job(store, world=3, rate=150.0, now=now[0])
        ctl.observe("j1", now=now[0])    # probe closes at 0.5s
        now[0] = t0 + 1.6
        ctl.tick()                       # journals the measurement
        ctl.stop()

        takeover = self._controller(store, state, clock=lambda: now[0])
        takeover._restore_from_journal()
        assert takeover._downtime.get("j1") == pytest.approx(0.5,
                                                             abs=0.01)
        takeover.stop()

    def test_artifact_downtime_prefers_p2p_number(self, tmp_path):
        from edl_tpu.scaler.controller import artifact_downtime
        art = tmp_path / "BENCH.json"
        art.write_text(json.dumps({"extras": {
            "elastic_downtime_s": 1.2,
            "elastic_downtime_p2p_s": 0.06}}))
        assert artifact_downtime(str(art)) == pytest.approx(0.06)
        art2 = tmp_path / "B2.json"
        art2.write_text(json.dumps({"extras": {
            "elastic_downtime_s": 1.2}}))
        assert artifact_downtime(str(art2)) == pytest.approx(1.2)
        assert artifact_downtime(str(tmp_path / "missing.json")) is None


@pytest.mark.slow
def test_resize_p2p_demo_end_to_end(tmp_path):
    """The full loop under real processes: store + JobServer + launcher
    pods, scripted shrink (survivor ADOPTS in place) and grow (joiner
    restores FROM PEERS over the wire), self-audited — the demo exits
    non-zero when any resize silently degraded to the disk recipe.
    Covers the SIGKILL-free churn path; donor-death-mid-transfer is
    pinned by the fast in-process tests above."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1"})
    proc = subprocess.run(
        [sys.executable, "-m", "edl_tpu.examples.elastic_demo",
         "--resize-p2p"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert proc.returncode == 0, \
        f"p2p demo failed:\n{proc.stdout[-4000:]}\n{proc.stderr[-4000:]}"
    summary = json.loads(
        proc.stdout.split("p2p_summary=", 1)[1].splitlines()[0])
    assert summary["ok"] and summary["adoptions"] >= 1
    assert summary["peer_restores"] >= 1
    assert summary["resize_bytes_from_peers"] > 0
    # the headline: surviving pods' resize gap is far below the ~1.2s
    # stop-resume respawn floor (no respawn, no re-jit, no restore)
    assert summary["elastic_downtime_p2p_s"] < 0.5
