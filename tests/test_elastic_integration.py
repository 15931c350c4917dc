"""End-to-end elastic training: real store server, launchers, trainers.

The working analogue of the reference's flagship demo flow (SURVEY.md §3.1:
JobServer/JobClient -> launch -> register/barrier -> trainers -> resize ->
stop-resume from checkpoint), shrunk to pytest scale: 2 launcher processes
on one host, each spawning the elastic_demo trainer on CPU; killing one
launcher (pod failure) forces the survivor through a stop-resume into a
1-pod world, and training still completes with a checkpoint-resumed epoch
cursor.
"""

import os
import signal
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.slow  # real process kills under the launcher

from edl_tpu.coord.client import StoreClient
from edl_tpu.collective import register as reg
from edl_tpu.collective.barrier import read_cluster
from edl_tpu.utils import net


CPU_ENV = {"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1"}


def cpu_env(extra=None):
    env = dict(os.environ)
    env.update(CPU_ENV)
    env.update(extra or {})
    return env


NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "native", "store")


def start_store(flavor, tmp_path, port=None):
    """Start a coordination store: the Python reference server or the
    production C++ `edl-store --data-dir` daemon (durable)."""
    port = port or net.free_port()
    if flavor == "native":
        binary = os.path.join(NATIVE_DIR, "edl-store")
        build = subprocess.run(["make", "-C", NATIVE_DIR],
                               capture_output=True, text=True)
        assert build.returncode == 0, f"native build failed:\n{build.stderr}"
        cmd = [binary, "--host", "127.0.0.1", "--port", str(port),
               "--sweep-interval", "0.05",
               "--data-dir", str(tmp_path / "store-data")]
    else:
        cmd = [sys.executable, "-m", "edl_tpu.coord.server",
               "--port", str(port)]
    proc = subprocess.Popen(
        cmd, env=cpu_env(), stdout=open(tmp_path / "store.log", "ab"),
        stderr=subprocess.STDOUT)
    client = StoreClient(f"127.0.0.1:{port}")
    deadline = time.time() + 15
    while time.time() < deadline:
        if client.ping():
            return proc, client, port
        time.sleep(0.2)
    proc.kill()
    pytest.fail(f"{flavor} store server never came up")


# The launcher/trainer stack must behave identically against the Python
# server and the durable C++ daemon — the latter is the production store.
@pytest.fixture(params=["python", "native"])
def store_server(request, tmp_path):
    proc, client, port = start_store(request.param, tmp_path)
    yield f"127.0.0.1:{port}", client
    client.close()
    proc.terminate()
    proc.wait(timeout=5)


def start_launcher(store_addr, tmp_path, name, epochs=3, step_time=0.05):
    env = cpu_env({
        "EDL_TPU_JOB_ID": "itjob",
        "EDL_TPU_STORE_ENDPOINTS": store_addr,
        "EDL_TPU_POD_ID": name,
        "EDL_TPU_CHECKPOINT_PATH": str(tmp_path / "ckpt"),
        "EDL_TPU_LOG_DIR": str(tmp_path / f"log_{name}"),
        "EDL_TPU_LEASE_TTL": "2.0",
        "EDL_TPU_BARRIER_STABLE": "0.5",
        "EDL_TPU_NODES_RANGE": "1:4",
        # This suite pins the BASELINE stop-resume recipe (kill world ->
        # re-form -> restore from disk); with p2p live migration on,
        # survivors adopt in place and the restart-banner assertions
        # below would see no restart. The p2p plane has its own suite
        # (test_state_migration.py + elastic_demo --resize-p2p).
        "EDL_TPU_RESIZE_P2P": "0",
    })
    return subprocess.Popen(
        [sys.executable, "-m", "edl_tpu.collective.launch", "--",
         sys.executable, "-m", "edl_tpu.examples.elastic_demo",
         "--epochs", str(epochs), "--steps-per-epoch", "10",
         "--step-time", str(step_time)],
        env=env, stdout=open(tmp_path / f"{name}.log", "wb"),
        stderr=subprocess.STDOUT, start_new_session=True)


def wait_for(cond, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.3)
    pytest.fail(f"timeout waiting for {what}")


def test_single_pod_completes(store_server, tmp_path):
    store_addr, client = store_server
    p = start_launcher(store_addr, tmp_path, "solo", epochs=2,
                       step_time=0.0)
    try:
        wait_for(lambda: p.poll() is not None, 120, "launcher exit")
        assert p.returncode == 0, open(tmp_path / "solo.log").read()
        assert client.get("/itjob/complete") is not None
        cluster = read_cluster(client, "itjob")
        assert cluster.world_size == 1
    finally:
        if p.poll() is None:
            os.killpg(os.getpgid(p.pid), signal.SIGKILL)


def test_sigterm_launcher_leaves_no_orphan_trainer(store_server, tmp_path):
    # A JobClient shrink SIGTERMs the launcher only (the trainer is in its
    # own session); the launcher must kill the trainer tree and release its
    # rank claim instead of orphaning a trainer that keeps training.
    store_addr, client = store_server
    p = start_launcher(store_addr, tmp_path, "victim", epochs=100,
                       step_time=0.5)
    try:
        def demo_procs():
            # Matches the launcher too (the trainer module appears in its
            # argv), so "orphan-free" below means zero matches once the
            # launcher has exited.
            out = subprocess.run(["pgrep", "-f", "edl_tpu.examples.elastic_demo"],
                                 capture_output=True)
            return [x for x in out.stdout.split() if x.strip()]

        wait_for(lambda: read_cluster(client, "itjob") is not None, 60,
                 "cluster formation")
        wait_for(lambda: len(demo_procs()) >= 2, 60, "trainer start")
        assert len(reg.live_pods(client, "itjob")[0]) == 1

        os.kill(p.pid, signal.SIGTERM)  # launcher only, not the group
        wait_for(lambda: p.poll() is not None, 30, "launcher exit")
        wait_for(lambda: not demo_procs(), 30, "trainer cleanup")
        # Rank claim released immediately (lease revoked, not TTL-drained).
        assert reg.live_pods(client, "itjob")[0] == []
    finally:
        if p.poll() is None:
            os.killpg(os.getpgid(p.pid), signal.SIGKILL)
        subprocess.run(["pkill", "-9", "-f", "edl_tpu.examples.elastic_demo"],
                       capture_output=True)


def test_two_pods_then_pod_failure_stop_resume(store_server, tmp_path):
    store_addr, client = store_server
    a = start_launcher(store_addr, tmp_path, "podA", epochs=4,
                       step_time=0.25)
    b = start_launcher(store_addr, tmp_path, "podB", epochs=4,
                       step_time=0.25)
    try:
        # Both pods join one cluster (v>=1, world=2).
        def two_up():
            c = read_cluster(client, "itjob")
            return c is not None and c.world_size == 2
        wait_for(two_up, 60, "2-pod cluster formation")

        # Kill pod B's whole tree: lease drains, survivor must stop-resume.
        os.killpg(os.getpgid(b.pid), signal.SIGKILL)

        def resized():
            c = read_cluster(client, "itjob")
            return (c is not None and c.world_size == 1
                    and c.pod_ids() == {"podA"})
        wait_for(resized, 60, "stop-resume into 1-pod world")

        wait_for(lambda: a.poll() is not None, 120, "survivor completion")
        assert a.returncode == 0, open(tmp_path / "podA.log").read()
        assert client.get("/itjob/complete") is not None

        # Trainer really restarted: the survivor's worker log has at least
        # two generations (start banner per spawn).
        logdir = tmp_path / "log_podA"
        banners = sum(open(logdir / f).read().count("==== start rank=")
                      for f in os.listdir(logdir))
        assert banners >= 2, "no trainer restart recorded"
    finally:
        for p in (a, b):
            if p.poll() is None:
                os.killpg(os.getpgid(p.pid), signal.SIGKILL)


def test_coordinator_restart_mid_job(tmp_path):
    """Kill -9 the durable edl-store mid-job and restart it on the same
    data dir/port: rank leases replay with a grace TTL, the pods' keepalive
    loops ride out the outage, and the job completes without a restart —
    the coordinator is no longer a job-killing single point of failure
    (reference relies on etcd's own durability for this,
    pkg/master/etcd_client.go:49-176)."""
    proc, client, port = start_store("native", tmp_path)
    addr = f"127.0.0.1:{port}"
    p = start_launcher(addr, tmp_path, "solo", epochs=4, step_time=0.3)
    try:
        def cluster_up():
            c = read_cluster(client, "itjob")
            return c is not None and c.world_size == 1
        wait_for(cluster_up, 60, "cluster formation")

        os.kill(proc.pid, signal.SIGKILL)      # coordinator crash
        proc.wait(timeout=5)
        client.close()
        time.sleep(0.5)                        # real downtime window
        proc, client, _ = start_store("native", tmp_path, port=port)

        # The job survives the outage: same cluster (no re-registration
        # storm), training runs to completion.
        cluster = read_cluster(client, "itjob")
        assert cluster is not None and cluster.pod_ids() == {"solo"}, \
            "cluster state lost across coordinator restart"
        wait_for(lambda: p.poll() is not None, 180, "job completion")
        assert p.returncode == 0, open(tmp_path / "solo.log").read()
        assert client.get("/itjob/complete") is not None
        # Single generation throughout — the outage caused no stop-resume.
        logdir = tmp_path / "log_solo"
        banners = sum(open(logdir / f).read().count("==== start rank=")
                      for f in os.listdir(logdir))
        assert banners == 1, f"unexpected trainer restarts: {banners}"
    finally:
        if p.poll() is None:
            os.killpg(os.getpgid(p.pid), signal.SIGKILL)
        client.close()
        proc.terminate()
        proc.wait(timeout=5)
