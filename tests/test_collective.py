"""Elastic launcher components on InMemStore (no processes, no network).

Mirrors the reference's WIP register/launch test intent
(register_test.py env fixture, SURVEY.md §4) with the working machinery.
"""

import threading
import time

import pytest

from edl_tpu.collective import barrier as bar
from edl_tpu.collective import register as reg
from edl_tpu.collective.cluster import Cluster, Pod, form_cluster
from edl_tpu.collective.job_env import JobEnv, TrainerEnv, trainer_environ
from edl_tpu.collective.watcher import ClusterWatcher
from edl_tpu.coord.store import InMemStore

JOB = "testjob"


def make_pod(i: int, **kw) -> Pod:
    kw.setdefault("addr", "127.0.0.1")
    kw.setdefault("port", 20000 + i)
    return Pod(pod_id=f"pod{i}", **kw)


def test_cluster_round_trip_and_ranks():
    pods = [make_pod(2, claimed_rank=7), make_pod(1, claimed_rank=3)]
    c = form_cluster(JOB, 1, pods)
    assert [p.pod_id for p in c.pods] == ["pod1", "pod2"]  # by claimed rank
    assert [p.rank for p in c.pods] == [0, 1]              # dense
    c2 = Cluster.from_json(c.to_json())
    assert c2.pod_ids() == {"pod1", "pod2"}
    assert c2.rank_of("pod2") == 1
    assert c2.coordinator == "127.0.0.1:20001"
    assert c2.same_membership(c)


def test_rank_claim_smallest_free_slot():
    store = InMemStore()
    r0 = reg.PodRegister(store, JOB, make_pod(0), ttl=5.0)
    r1 = reg.PodRegister(store, JOB, make_pod(1), ttl=5.0)
    assert r0.claim() == 0
    assert r1.claim() == 1
    r0.release()
    r2 = reg.PodRegister(store, JOB, make_pod(2), ttl=5.0)
    assert r2.claim() == 0  # hole filled
    for r in (r1, r2):
        r.release()


def test_rank_claim_concurrent_unique():
    store = InMemStore()
    results, regs = [], []

    def claim(i):
        r = reg.PodRegister(store, JOB, make_pod(i), ttl=5.0)
        results.append(r.claim())
        regs.append(r)

    threads = [threading.Thread(target=claim, args=(i,)) for i in range(6)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert sorted(results) == list(range(6))
    [r.release() for r in regs]


def test_claim_expires_on_lease_timeout():
    store = InMemStore()
    r0 = reg.PodRegister(store, JOB, make_pod(0), ttl=0.3)
    r0.claim()
    r0._keeper.stop(revoke=False)  # simulate pod death (no keepalive)
    time.sleep(0.7)
    pods, _ = reg.live_pods(store, JOB)
    assert pods == []


def test_barrier_three_pods_one_leader():
    store = InMemStore()
    regs = []
    for i in range(3):
        r = reg.PodRegister(store, JOB, make_pod(i), ttl=5.0)
        r.claim()
        regs.append(r)
    out = {}

    def wait(i):
        out[i] = bar.cluster_barrier(store, JOB, f"pod{i}",
                                     stable_secs=0.2, timeout=10.0)

    threads = [threading.Thread(target=wait, args=(i,)) for i in range(3)]
    [t.start() for t in threads]
    [t.join(15.0) for t in threads]
    assert len(out) == 3
    versions = {c.version for c in out.values()}
    assert versions == {1}
    assert all(c.world_size == 3 for c in out.values())
    ranks = sorted(out[i].rank_of(f"pod{i}") for i in range(3))
    assert ranks == [0, 1, 2]
    [r.release() for r in regs]


def test_barrier_resize_bumps_version():
    store = InMemStore()
    regs = [reg.PodRegister(store, JOB, make_pod(i), ttl=5.0)
            for i in range(2)]
    [r.claim() for r in regs]
    c1 = bar.cluster_barrier(store, JOB, "pod0", stable_secs=0.1,
                             timeout=10.0)
    assert c1.version == 1 and c1.world_size == 2

    regs[1].release()  # pod1 departs
    c2 = bar.cluster_barrier(store, JOB, "pod0", after_version=c1.version,
                             stable_secs=0.1, timeout=10.0)
    assert c2.version == 2
    assert c2.pod_ids() == {"pod0"}
    assert c2.rank_of("pod0") == 0
    regs[0].release()


def test_barrier_waits_for_min_nodes():
    store = InMemStore()
    r = reg.PodRegister(store, JOB, make_pod(0), ttl=5.0)
    r.claim()
    with pytest.raises(Exception):
        bar.cluster_barrier(store, JOB, "pod0", min_nodes=2,
                            stable_secs=0.1, timeout=1.0)
    r.release()


def test_watcher_fires_on_change():
    store = InMemStore()
    regs = [reg.PodRegister(store, JOB, make_pod(i), ttl=5.0)
            for i in range(2)]
    [r.claim() for r in regs]
    cluster = bar.cluster_barrier(store, JOB, "pod0", stable_secs=0.1,
                                  timeout=10.0)
    w = ClusterWatcher(store, cluster, interval=0.1).start()
    assert not w.changed.wait(0.4)
    regs[1].release()
    assert w.changed.wait(3.0)
    w.stop()
    regs[0].release()


def test_watcher_fires_on_new_generation_without_membership_blip():
    # A pod that crashes and rejoins between two watcher polls produces no
    # membership diff; peers must still notice the new cluster generation.
    store = InMemStore()
    regs = [reg.PodRegister(store, JOB, make_pod(i), ttl=5.0)
            for i in range(2)]
    [r.claim() for r in regs]
    cluster = bar.cluster_barrier(store, JOB, "pod0", stable_secs=0.1,
                                  timeout=10.0)
    w = ClusterWatcher(store, cluster, interval=0.1).start()
    assert not w.changed.wait(0.4)
    # Same membership, newer version published (as the rejoined pod's
    # barrier would do).
    pods, _ = reg.live_pods(store, JOB)
    nxt = form_cluster(JOB, cluster.version + 1, pods)
    store.put(reg.cluster_key(JOB), nxt.to_json())
    assert w.changed.wait(3.0)
    w.stop()
    [r.release() for r in regs]


def test_trainer_environ_round_trip(monkeypatch):
    pods = [make_pod(0, claimed_rank=0, n_devices=4),
            make_pod(1, claimed_rank=1, n_devices=4)]
    cluster = form_cluster(JOB, 3, pods)
    job = JobEnv(job_id=JOB, checkpoint_path="/tmp/ckpt",
                 store_endpoints="127.0.0.1:2379")
    env = trainer_environ(cluster, "pod1", job)
    for k, v in env.items():
        if k.startswith("EDL_TPU_"):
            monkeypatch.setenv(k, v)
    te = TrainerEnv.from_environ()
    assert te.rank == 1 and te.world_size == 2
    assert te.coordinator == "127.0.0.1:20000"
    assert te.cluster_version == 3
    assert te.cluster.n_devices == 8
    assert not te.is_leader
    assert te.checkpoint_path == "/tmp/ckpt"


def test_job_env_nodes_range(monkeypatch):
    monkeypatch.setenv("EDL_TPU_NODES_RANGE", "2:8")
    job = JobEnv.from_environ()
    assert (job.min_nodes, job.max_nodes) == (2, 8)
    assert job.pod_id  # auto-generated


_FAKE_TRAINER = '''
import os, signal, sys, time
events = sys.argv[1]
def note(what):
    with open(events, "a") as f:
        f.write(f"{what} {os.getpid()} {time.monotonic()}\\n")
def stop(signum, frame):  # a donor: lingers, then exits like a stopped trainer
    time.sleep(1.5)
    note("exit")
    os._exit(143)
signal.signal(signal.SIGTERM, stop)
note("start")
while True:
    time.sleep(0.05)
'''


def test_replacement_starts_after_lingering_donor_exits(tmp_path):
    """Stop-resume with a donor linger: one trainer per host drives all
    its chips and a lingering donor still holds them, so the launcher
    starts the replacement only once the donor has exited."""
    import sys

    from edl_tpu.collective.launch import launch

    script, events = tmp_path / "trainer.py", tmp_path / "events"
    script.write_text(_FAKE_TRAINER)
    events.write_text("")
    store = InMemStore()
    job = JobEnv(job_id=JOB, pod_id="pod0", nodes_range="1:2",
                 log_dir=str(tmp_path / "log"), lease_ttl=5.0,
                 barrier_stable_secs=0.2, barrier_timeout=20.0,
                 adopt_timeout_secs=0.3, donor_linger_secs=5.0)
    done = {}
    runner = threading.Thread(
        target=lambda: done.update(rc=launch(
            job, [sys.executable, str(script), str(events)], store=store,
            poll=0.1)), daemon=True)
    runner.start()

    def seen(what):
        return [(int(pid), float(t)) for w, pid, t in
                (ln.split() for ln in events.read_text().splitlines())
                if w == what]

    def wait(cond, timeout=30.0):
        deadline = time.monotonic() + timeout
        while not cond():
            assert time.monotonic() < deadline, events.read_text()
            time.sleep(0.05)

    wait(lambda: len(seen("start")) == 1)
    # a pod joins: the fake trainer cannot adopt the new world in place,
    # so the launcher releases it (SIGTERM -> linger) and respawns
    joiner = reg.PodRegister(store, JOB, make_pod(1), max_nodes=2, ttl=5.0)
    joiner.claim()
    wait(lambda: len(seen("start")) == 2)
    (donor, exited), = seen("exit")
    (first, _), (second, started) = seen("start")
    assert donor == first != second
    assert started >= exited, "replacement started while the donor lived"
    store.put(reg.complete_key(JOB), "1")
    runner.join(20.0)
    assert done == {"rc": 0}
    wait(lambda: len(seen("exit")) == 2)  # the released replacement, too
    joiner.release()


def test_crash_respawn_logs_one_reform_line(tmp_path, caplog):
    """A crashed trainer's way back, by phase: the launcher writes one
    `reform:` line a respawn, where the next trainer is started, and a
    first start writes none."""
    import logging
    import os
    import re
    import signal
    import sys

    from edl_tpu.collective.launch import launch

    script, events = tmp_path / "trainer.py", tmp_path / "events"
    script.write_text(_FAKE_TRAINER)
    events.write_text("")
    store = InMemStore()
    job = JobEnv(job_id=JOB, pod_id="pod0", nodes_range="1:1",
                 log_dir=str(tmp_path / "log"), lease_ttl=5.0,
                 barrier_stable_secs=0.2, barrier_timeout=20.0,
                 rejoin_delay_secs=0.3)
    done = {}
    runner = threading.Thread(
        target=lambda: done.update(rc=launch(
            job, [sys.executable, str(script), str(events)], store=store,
            poll=0.1)), daemon=True)
    # the framework's loggers do not propagate: listen on this one
    launch_log = logging.getLogger("edl_tpu.collective.launch")
    launch_log.addHandler(caplog.handler)
    runner.start()

    def starts():
        return [int(ln.split()[1]) for ln in
                events.read_text().splitlines() if ln.startswith("start")]

    def reform_lines():
        return [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("reform:")]

    def wait(cond, timeout=30.0):
        deadline = time.monotonic() + timeout
        while not cond():
            assert time.monotonic() < deadline, events.read_text()
            time.sleep(0.05)

    wait(lambda: len(starts()) == 1)
    assert reform_lines() == []
    for n in (2, 3):
        os.kill(starts()[-1], signal.SIGKILL)
        wait(lambda: len(starts()) == n)
        wait(lambda: len(reform_lines()) == n - 1)
    store.put(reg.complete_key(JOB), "1")
    runner.join(20.0)
    launch_log.removeHandler(caplog.handler)
    assert done == {"rc": 0}
    assert len(reform_lines()) == 2
    m = re.match(r"reform: exit_seen\S+spawn ([\d.]+)s \(rejoin_wait "
                 r"([\d.]+)s, barrier ([\d.]+)s, spawn ([\d.]+)s\)$",
                 reform_lines()[-1])
    total, *parts = map(float, m.groups())
    assert parts[0] >= 0.3 and abs(total - sum(parts)) < 0.01
