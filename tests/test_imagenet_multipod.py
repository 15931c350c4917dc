"""Flagship imagenet trainer under a real multi-process world.

Also the deepest integration in the suite: the flagship trainer under
the REAL elastic launcher — store server, rank claims, one-world
formation, pod kill, stop-resume — in
`test_flagship_under_launcher_survives_pod_kill`.

multipod_demo proves the one-world mechanics on a linear model; this
proves the FLAGSHIP trainer (file-backed FileSource input, BN stats,
label pipeline, benchmark log) trains correctly when two launcher-style
processes form one jax.distributed world. Because each rank feeds
`perm[rank::world]` of the same seed-per-pass global order, every global
step consumes the same sample SET as a single-process run with the
global batch — so accuracy must match up to reduction order.
"""

import pytest

pytestmark = pytest.mark.slow  # the flagship trainer under the real elastic launcher

import json
import os
import subprocess
import sys
import time

import numpy as np

from edl_tpu.utils import net

TRAINER = "edl_tpu.examples.imagenet_train"


def cpu_env(extra=None):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1"})
    env.update(extra or {})
    return env


def run_world(tmp_path, tag, world, data_dir, epochs=4, timeout=300,
              ckpt=None, schedule_epochs=0):
    port = net.free_port()
    blog_dir = tmp_path / f"blog-{tag}"
    extra = ["--ckpt-dir", str(ckpt)] if ckpt else []
    if schedule_epochs:
        extra += ["--schedule-epochs", str(schedule_epochs)]
    procs, logs = [], []
    for rank in range(world):
        env = cpu_env({
            "EDL_TPU_RANK": str(rank),
            "EDL_TPU_WORLD_SIZE": str(world),
            "EDL_TPU_COORDINATOR": f"127.0.0.1:{port}",
        })
        logs.append(open(tmp_path / f"{tag}.r{rank}.log", "wb"))
        procs.append(subprocess.Popen(
            [sys.executable, "-m", TRAINER, "--data-dir", str(data_dir),
             "--model", "ResNetTiny", "--num-classes", "8",
             "--image-size", "16", "--epochs", str(epochs),
             "--batch-size", "32", "--warmup-epochs", "1",
             "--lr-strategy", "cosine", "--lr", "0.05", "--no-augment",
             "--label-smoothing", "0",
             "--benchmark-log", str(blog_dir)] + extra,
            env=env, stdout=logs[-1], stderr=subprocess.STDOUT))
    deadline = time.time() + timeout
    try:
        for rank, p in enumerate(procs):
            rc = p.wait(timeout=max(1.0, deadline - time.time()))
            assert rc == 0, (tmp_path / f"{tag}.r{rank}.log").read_text()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for f in logs:
            f.close()
    with open(blog_dir / "log_0.json") as f:
        return json.load(f)


def make_data(tmp_path):
    """Generate shards once (single process, deterministic)."""
    data_dir = tmp_path / "data"
    rc = subprocess.run(
        [sys.executable, "-m", TRAINER, "--data-dir", str(data_dir),
         "--make-synthetic", "2", "--rows-per-file", "128",
         "--model", "ResNetTiny", "--num-classes", "8",
         "--image-size", "16", "--epochs", "0", "--batch-size", "32"],
        env=cpu_env(), capture_output=True)
    assert rc.returncode == 0, rc.stdout.decode() + rc.stderr.decode()
    return data_dir


def test_flagship_two_process_world_matches_single(tmp_path):
    data_dir = make_data(tmp_path)
    solo = run_world(tmp_path, "solo", 1, data_dir)
    duo = run_world(tmp_path, "duo", 2, data_dir)
    assert solo["world_size"] == 1 and duo["world_size"] == 2
    acc_s = solo["final"]["acc1"]
    acc_d = duo["final"]["acc1"]
    # the task is learnable; both worlds must learn it and agree
    assert acc_s > 0.8, solo["final"]
    assert acc_d > 0.8, duo["final"]
    assert abs(acc_s - acc_d) < 0.1, (solo["final"], duo["final"])
    # global throughput figure uses the world multiplier
    assert duo["max_examples_per_sec_global"] > duo["max_examples_per_sec"]


def test_two_resizes_under_one_percent_acc_loss(tmp_path):
    """The BASELINE north-star clause: a real model surviving >= 2
    elastic resizes with < 1% acc1 loss vs the unresized run.

    World sequence 2 -> 1 -> 2, each phase resuming the shared
    checkpoint with --schedule-epochs pinned to the job's total (so all
    phases ride the SAME 5-epoch cosine curve), compared against a
    straight world=1 run of the same total epochs. The per-phase
    benchmark logs also prove each phase RESUMED (it trained only its
    own epochs) — a silent restore failure would otherwise make the
    comparison vacuous.
    """
    data_dir = make_data(tmp_path)
    ckpt = tmp_path / "ckpt"
    p1 = run_world(tmp_path, "p1", 2, data_dir, epochs=2, ckpt=ckpt,
                   schedule_epochs=5)
    p2 = run_world(tmp_path, "p2", 1, data_dir, epochs=3, ckpt=ckpt,
                   schedule_epochs=5)                            # resize 1
    resized = run_world(tmp_path, "p3", 2, data_dir, epochs=5,
                        ckpt=ckpt, schedule_epochs=5)            # resize 2
    straight = run_world(tmp_path, "straight", 1, data_dir, epochs=5)
    # resumes really happened: each phase trained only its own epochs
    assert [e["epoch"] for e in p1["epochs"]] == [0, 1]
    assert [e["epoch"] for e in p2["epochs"]] == [2]
    assert [e["epoch"] for e in resized["epochs"]] == [3, 4]
    acc_r = resized["final"]["acc1"]
    acc_s = straight["final"]["acc1"]
    assert acc_s > 0.85, straight["final"]
    assert abs(acc_r - acc_s) < 0.01, (resized["final"], straight["final"])


def _pids_with_env(**want):
    """PIDs whose /proc environ contains every given EDL var (the only
    reliable way to find a pod's trainer: launchers start trainers in
    their OWN session, so killing the launcher pgid alone leaves the
    trainer alive — and cmdline is identical across pods)."""
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            env = open(f"/proc/{pid}/environ", "rb").read().decode(
                "utf-8", "replace")
        except OSError:
            continue
        if all(f"{k}={v}" in env for k, v in want.items()):
            out.append(int(pid))
    return out


def _kill_pod(launcher_proc, pod_id, job_id):
    """SIGKILL a pod: the launcher's process group AND its trainer
    session (found by environ, scoped to this job/pod only)."""
    import signal

    for pid in (launcher_proc.pid, *_pids_with_env(
            EDL_TPU_JOB_ID=job_id, EDL_TPU_POD_ID=pod_id)):
        try:
            os.killpg(os.getpgid(pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


def test_flagship_under_launcher_survives_pod_kill(tmp_path):
    """imagenet_train under `edl_tpu.collective.launch`: two launchers
    claim ranks in a real store, form one world, train with per-epoch
    checkpoints; SIGKILLing one pod (launcher + its trainer session)
    stop-resumes the survivor into a 1-pod world that finishes the job
    from the shared checkpoint."""
    from edl_tpu.coord.client import StoreClient

    data_dir = make_data(tmp_path)
    port = net.free_port()
    logs = [open(tmp_path / "store.log", "wb")]
    store = subprocess.Popen(
        [sys.executable, "-m", "edl_tpu.coord.server", "--port", str(port)],
        env=cpu_env(), stdout=logs[0], stderr=subprocess.STDOUT)
    client = StoreClient(f"127.0.0.1:{port}")
    deadline = time.time() + 15
    while time.time() < deadline and not client.ping():
        time.sleep(0.2)
    assert client.ping(), "store never came up"

    def launcher(name):
        env = cpu_env({
            "EDL_TPU_JOB_ID": "imjob",
            "EDL_TPU_STORE_ENDPOINTS": f"127.0.0.1:{port}",
            "EDL_TPU_POD_ID": name,
            "EDL_TPU_CHECKPOINT_PATH": str(tmp_path / "ckpt"),
            "EDL_TPU_LOG_DIR": str(tmp_path / f"log_{name}"),
            "EDL_TPU_LEASE_TTL": "2.0",
            "EDL_TPU_BARRIER_STABLE": "0.5",
            "EDL_TPU_NODES_RANGE": "1:4",
        })
        logs.append(open(tmp_path / f"{name}.log", "wb"))
        return subprocess.Popen(
            [sys.executable, "-m", "edl_tpu.collective.launch", "--",
             sys.executable, "-m", TRAINER, "--data-dir", str(data_dir),
             "--model", "ResNetTiny", "--num-classes", "8",
             "--image-size", "16", "--epochs", "6", "--batch-size", "32",
             "--warmup-epochs", "1", "--lr-strategy", "cosine",
             "--lr", "0.05", "--no-augment", "--label-smoothing", "0",
             "--benchmark-log", str(tmp_path / "blog")],
            env=env, stdout=logs[-1], stderr=subprocess.STDOUT,
            start_new_session=True)

    a = launcher("podA")
    b = launcher("podB")
    try:
        from edl_tpu.collective.barrier import read_cluster

        def world_is(n):
            c = read_cluster(client, "imjob")
            return c is not None and c.world_size == n

        deadline = time.time() + 120
        while time.time() < deadline and not world_is(2):
            time.sleep(0.3)
        assert world_is(2), "2-pod world never formed"

        def has_ckpt():
            ckpt = tmp_path / "ckpt"
            return ckpt.is_dir() and any(p.name.startswith("ckpt-")
                                         for p in ckpt.iterdir())

        deadline = time.time() + 180
        while time.time() < deadline and not has_ckpt():
            time.sleep(0.3)
        assert has_ckpt(), "no sealed checkpoint from the 2-pod world"

        _kill_pod(b, "podB", "imjob")  # pod failure: launcher + trainer

        rc = a.wait(timeout=360)
        assert rc == 0, open(tmp_path / "podA.log").read()
        assert client.get("/imjob/complete") is not None
        blog = json.load(open(tmp_path / "blog" / "log_0.json"))
        assert blog["epochs"][-1]["epoch"] == 5  # job finished all epochs
        assert blog["epochs"][-1]["acc1"] > 0.85, blog["epochs"][-1]
    finally:
        _kill_pod(b, "podB", "imjob")
        _kill_pod(a, "podA", "imjob")
        store.terminate()
        store.wait(timeout=5)
        for f in logs:
            f.close()
