"""One-world multi-pod data parallelism: the flagship capability proof.

The reference's collective mode forms ONE NCCL world across trainers
(train_with_fleet.py:376-377 `fleet.init(is_collective=True)` over the
launcher's PADDLE_TRAINER_* env); here N processes form one jax.distributed
world (gloo CPU collectives stand in for ICI) and a global-mesh jitted step
carries the gradient all-reduce. Tests assert:

  1. loss/param parity: a 2-process world trains to the SAME parameters as
     a single-process run on the same global batch stream;
  2. elastic resize: a world trained 2-process, then resumed 1-process from
     its checkpoint, matches an unresized 1-process run end-to-end;
  3. the full launcher path: two launchers -> one 2-pod world -> pod kill
     -> stop-resume into a 1-pod world -> completion with parity.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.slow  # multi-process jax.distributed worlds

from edl_tpu.utils import net

DEMO = "edl_tpu.examples.multipod_demo"


def cpu_env(extra=None):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1"})
    env.update(extra or {})
    return env


def run_world(tmp_path, tag, world, epochs=3, ckpt=None, steps=8,
              global_batch=16, timeout=120):
    """Spawn `world` trainer processes forming one world; return rank-0 out."""
    port = net.free_port()
    out_path = tmp_path / f"{tag}.json"
    procs = []
    for rank in range(world):
        env = cpu_env({
            "EDL_TPU_RANK": str(rank),
            "EDL_TPU_WORLD_SIZE": str(world),
            "EDL_TPU_COORDINATOR": f"127.0.0.1:{port}",
            "EDL_TPU_CHECKPOINT_PATH": str(ckpt) if ckpt else "",
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-m", DEMO, "--epochs", str(epochs),
             "--steps-per-epoch", str(steps),
             "--global-batch", str(global_batch), "--out", str(out_path)],
            env=env, stdout=open(tmp_path / f"{tag}.r{rank}.log", "wb"),
            stderr=subprocess.STDOUT))
    deadline = time.time() + timeout
    try:
        for rank, p in enumerate(procs):
            rc = p.wait(timeout=max(1.0, deadline - time.time()))
            assert rc == 0, (tmp_path / f"{tag}.r{rank}.log").read_text()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    with open(out_path) as f:
        return json.load(f)


def test_two_process_parity_with_single(tmp_path):
    solo = run_world(tmp_path, "solo", world=1)
    duo = run_world(tmp_path, "duo", world=2)
    assert duo["world"] == 2 and solo["world"] == 1
    assert duo["step"] == solo["step"]  # same number of global steps
    assert abs(duo["w"] - solo["w"]) < 1e-5, (solo, duo)
    assert abs(duo["b"] - solo["b"]) < 1e-5, (solo, duo)
    # and it moved decisively toward the generating function (w*=3, b*=-1.5)
    assert solo["w"] > 2.0 and solo["b"] < -1.0


def test_resize_resume_parity(tmp_path):
    # Train epochs 0-1 in a 2-process world, checkpointing...
    first = run_world(tmp_path, "phase1", world=2, epochs=2,
                      ckpt=tmp_path / "ckpt")
    assert first["epoch"] == 1
    # ...then "resize" to a 1-process world resuming the same checkpoint.
    second = run_world(tmp_path, "phase2", world=1, epochs=4,
                       ckpt=tmp_path / "ckpt")
    assert second["epoch"] == 3
    # An unresized 1-process run over all 4 epochs must land on the same
    # parameters (global-batch-deterministic data + epoch-atomic resume).
    straight = run_world(tmp_path, "straight", world=1, epochs=4)
    assert abs(second["w"] - straight["w"]) < 1e-5, (second, straight)
    assert abs(second["b"] - straight["b"]) < 1e-5, (second, straight)


@pytest.fixture
def store_server(tmp_path):
    from edl_tpu.coord.client import StoreClient
    port = net.free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "edl_tpu.coord.server", "--port", str(port)],
        env=cpu_env(), stdout=open(tmp_path / "store.log", "wb"),
        stderr=subprocess.STDOUT)
    client = StoreClient(f"127.0.0.1:{port}")
    deadline = time.time() + 15
    while time.time() < deadline:
        if client.ping():
            break
        time.sleep(0.2)
    else:
        proc.kill()
        pytest.fail("store server never came up")
    yield f"127.0.0.1:{port}", client
    proc.terminate()
    proc.wait(timeout=5)


def start_launcher(store_addr, tmp_path, name, epochs, step_time):
    env = cpu_env({
        "EDL_TPU_JOB_ID": "mpjob",
        "EDL_TPU_STORE_ENDPOINTS": store_addr,
        "EDL_TPU_POD_ID": name,
        "EDL_TPU_CHECKPOINT_PATH": str(tmp_path / "ckpt"),
        "EDL_TPU_LOG_DIR": str(tmp_path / f"log_{name}"),
        "EDL_TPU_LEASE_TTL": "2.0",
        "EDL_TPU_BARRIER_STABLE": "0.5",
        "EDL_TPU_NODES_RANGE": "1:4",
    })
    return subprocess.Popen(
        [sys.executable, "-m", "edl_tpu.collective.launch", "--",
         sys.executable, "-m", DEMO,
         "--epochs", str(epochs), "--steps-per-epoch", "6",
         "--global-batch", "16", "--step-time", str(step_time),
         "--out", str(tmp_path / "launched.json")],
        env=env, stdout=open(tmp_path / f"{name}.log", "wb"),
        stderr=subprocess.STDOUT, start_new_session=True)


def test_launcher_forms_one_world_and_survives_resize(store_server, tmp_path):
    from edl_tpu.collective.barrier import read_cluster
    store_addr, client = store_server
    # step_time sized so the kill (after ckpt + first utilization poll)
    # lands MID-training: the resized world must still have epochs left
    # to publish fresh utilization from. The publisher fires at TrainLoop
    # log points — multipod_demo logs once per epoch (~4s here).
    a = start_launcher(store_addr, tmp_path, "podA", epochs=5, step_time=0.7)
    b = start_launcher(store_addr, tmp_path, "podB", epochs=5, step_time=0.7)
    try:
        def two_up():
            c = read_cluster(client, "mpjob")
            return c is not None and c.world_size == 2
        deadline = time.time() + 90
        while time.time() < deadline and not two_up():
            time.sleep(0.3)
        assert two_up(), "2-pod cluster never formed"

        # Wait until the 2-pod world has actually trained (a checkpoint
        # exists), so the resize exercises restore-on-new-world.
        ckpt = tmp_path / "ckpt"
        deadline = time.time() + 120
        while time.time() < deadline and not (
                ckpt.is_dir() and any(p.name.startswith("ckpt-")
                                      for p in ckpt.iterdir())):
            time.sleep(0.3)
        assert ckpt.is_dir() and any(p.name.startswith("ckpt-")
                                     for p in ckpt.iterdir()), \
            "no checkpoint from the 2-pod world"

        # Trainer utilization is published into leased /mpjob/util/
        # records (TrainLoop auto-installs the publisher under the
        # launcher) and surfaced by the Collector — the scheduler data
        # path (reference discovery/register.py:36-40 info field).
        from edl_tpu.coord.collector import Collector
        deadline = time.time() + 90
        util_docs = {}
        while time.time() < deadline and not util_docs:
            snap = Collector(client, job_id="mpjob").snapshot()
            util_docs = {p["pod_id"]: p["utilization"]
                         for p in snap["job"]["pods"]
                         if p["utilization"]}
            time.sleep(0.3)
        assert util_docs, "no trainer utilization ever published"
        doc = next(iter(util_docs.values()))
        assert doc["samples_seen"] > 0 and doc["step"] > 0

        t_kill = time.time()
        os.killpg(os.getpgid(b.pid), signal.SIGKILL)  # pod failure

        def resized():
            c = read_cluster(client, "mpjob")
            return (c is not None and c.world_size == 1
                    and c.pod_ids() == {"podA"})
        deadline = time.time() + 90
        while time.time() < deadline and not resized():
            time.sleep(0.3)
        assert resized(), "no stop-resume into 1-pod world"

        # The RESIZED 1-pod world keeps publishing fresh utilization
        # (records survive the resize). Freshness = publish timestamp
        # after the kill; samples_seen restores from the checkpoint so
        # it is NOT monotonic across the resize.
        deadline = time.time() + 120
        fresh = None
        while time.time() < deadline and fresh is None \
                and a.poll() is None:
            snap = Collector(client, job_id="mpjob").snapshot()
            for p in snap["job"]["pods"]:
                u = p["utilization"]
                if p["pod_id"] == "podA" and u and u["ts"] > t_kill:
                    fresh = u
            time.sleep(0.2)
        assert fresh is not None, \
            "resized world published no fresh utilization"

        rc = a.wait(timeout=240)
        assert rc == 0, open(tmp_path / "podA.log").read()
        assert client.get("/mpjob/complete") is not None

        with open(tmp_path / "launched.json") as f:
            result = json.load(f)
        assert result["epoch"] == 4 and result["world"] == 1
        # Parity with an unresized single-process run of the same recipe.
        straight = run_world(tmp_path, "straight", world=1, epochs=5,
                             steps=6, global_batch=16)
        assert abs(result["w"] - straight["w"]) < 1e-5, (result, straight)
        assert abs(result["b"] - straight["b"]) < 1e-5, (result, straight)

        # The 2-pod generation really ran one world: rank-0's log shows a
        # world of 2 and rank-1 joined it.
        logs = "".join(
            open(tmp_path / f"log_{n}" / f).read()
            for n in ("podA", "podB") if (tmp_path / f"log_{n}").is_dir()
            for f in os.listdir(tmp_path / f"log_{n}"))
        assert "world=2" in logs, "trainers never formed a 2-pod world"
    finally:
        for p in (a, b):
            if p.poll() is None:
                os.killpg(os.getpgid(p.pid), signal.SIGKILL)
        subprocess.run(["pkill", "-9", "-f", DEMO], capture_output=True)
