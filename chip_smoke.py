"""Quickest proof that the system still starts on the chip.

    python chip_smoke.py                 # one chip (what the driver runs)
    python chip_smoke.py --four-chips    # the fsdp world beside one chip

Drives both pillars through the entry points a user calls, at the
widest model each supports (LM-large: d_model 2048, 8 layers;
ResNet50_vd at 224 px), with random weights from a seed:

  kernels  every Pallas kernel against its XLA expression, on the device
  train    store + launcher + lm_train: steps, async sharded checkpoint,
           SIGKILL, respawn, restore, steps; then SIGTERM (the graceful
           stop of `release_trainer`), seal, respawn, restore, steps
  serve    teacher_server on the chip, TeacherClient over TCP from here

This process never touches JAX: a chip belongs to one process at a
time, so every phase that needs it is a child that has it alone, one
after another. Any phase that fails, times out, finds no TPU or finds a
kernel off its compiled path makes the script exit non-zero. The last
line of stdout is the verdict with the device as a child reported it.
"""

from __future__ import annotations

import argparse
import ast
import glob
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
PY = sys.executable
LM_RUN = ["--batch-size", "8", "--fused-loss", "--make-synthetic", "1",
          "--rows-per-file", "512", "--epochs", "100", "--warmup-steps",
          "10", "--seed", "0"]
LM_LARGE = ["--vocab", "32768", "--d-model", "2048", "--n-heads", "16",
            "--n-layers", "8", "--d-ff", "8192", "--seq-len", "1024",
            "--bf16", *LM_RUN]
LM_TINY = ["--vocab", "512", "--d-model", "128", "--n-heads", "4",
           "--n-layers", "2", "--d-ff", "256", "--seq-len", "128", *LM_RUN]
# OLMoE-1B-7B at its published widths, one layer (benchmark/configs/
# olmoe-1b-7b-d1.json): 4 x 4096 tokens a step, 64 experts, 8 a token
LM_OLMOE = ["--vocab", "50304", "--d-model", "2048", "--n-heads", "16",
            "--n-layers", "1", "--d-ff", "1024", "--seq-len", "4096",
            "--bf16", "--arch", "olmoe", "--batch-size", "4",
            "--fused-loss", "--make-synthetic", "1", "--rows-per-file",
            "256", "--epochs", "100", "--warmup-steps", "10", "--seed", "0"]
LM_OLMOE_TINY = ["--vocab", "512", "--d-model", "64", "--n-heads", "2",
                 "--n-layers", "2", "--d-ff", "32", "--seq-len", "128",
                 "--arch", "olmoe", "--n-experts", "8", "--moe-top-k", "2",
                 *LM_RUN]
CKPT_STEPS = 5
LIMIT_S = 1140      # the whole run: the driver allows 1200 s
_procs: list[subprocess.Popen] = []


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    say(("ok   " if ok else "FAIL ") + what)
    if not ok:
        raise SmokeFailure(what)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn(cmd: list[str], log_path: str, env: dict | None = None
          ) -> subprocess.Popen:
    """Start a child in its own session, all output to ``log_path``."""
    with open(log_path, "ab") as out:
        proc = subprocess.Popen(cmd, cwd=REPO, env=env or dict(os.environ),
                                stdout=out, stderr=subprocess.STDOUT,
                                start_new_session=True)
    _procs.append(proc)
    return proc


def read(path: str) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except FileNotFoundError:
        return ""


def wait_for(probe, timeout: float, what: str, proc=None):
    """Poll ``probe`` until it returns something truthy; give up when
    ``proc`` (whose doing it is) has exited."""
    deadline = min(time.monotonic() + timeout, T0 + LIMIT_S)
    while time.monotonic() < deadline:
        got = probe()
        if got:
            return got
        if proc is not None and proc.poll() is not None:
            raise SmokeFailure(f"{what}: process exited rc={proc.poll()}")
        time.sleep(0.2)
    raise SmokeFailure(f"timed out after {timeout:.0f}s waiting for {what}")


def alive(pid: int) -> bool:
    """A process that exists and is not a zombie awaiting its parent."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def pids_matching(*needles: str, without: str = "\0") -> list[int]:
    """Live processes whose command line holds every needle, and not
    ``without``."""
    out = []
    for d in glob.glob("/proc/[0-9]*"):
        pid = int(d[6:])
        try:
            with open(d + "/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue
        if all(n in cmd for n in needles) and without not in cmd \
                and pid != os.getpid() and alive(pid):
            out.append(pid)
    return out


def kill_group(pid: int, sig: int) -> None:
    try:
        os.killpg(os.getpgid(pid), sig)
    except (ProcessLookupError, PermissionError):
        pass


def tail(path: str, n: int = 40) -> str:
    return "\n".join(read(path).splitlines()[-n:])


# -- the trainer's log --------------------------------------------------------
# Every line carries its process id ("... edl_tpu.train.loop [1234] ..."),
# so each generation of the trainer is read apart from the others.

def lines_of(log_text: str, pid: int) -> list[str]:
    tag = f"[{pid}]"
    return [ln for ln in log_text.splitlines() if tag in ln]


def steps_of(lines: list[str]) -> dict[int, float]:
    out = {}
    for ln in lines:
        m = re.search(r"epoch \d+ step (\d+): .*?loss=(\S+)", ln)
        if m:
            out[int(m.group(1))] = float(m.group(2))
    return out


def ints_of(lines: list[str], pattern: str) -> list[int]:
    return [int(m.group(1)) for ln in lines
            for m in [re.search(pattern, ln)] if m]


class Job:
    """One elastic job: a store, one launcher pod, `lm_train` under it."""

    def __init__(self, work: str, name: str, lm_args: list[str],
                 env: dict):
        self.dir = os.path.join(work, name)
        os.makedirs(self.dir)
        self.launcher_log = os.path.join(self.dir, "launcher.log")
        self.worker_log = os.path.join(self.dir, "log", "workerlog.0")
        port = free_port()
        self.store = spawn([PY, "-m", "edl_tpu.coord.server", "--host",
                            "127.0.0.1", "--port", str(port)],
                           os.path.join(self.dir, "store.log"))
        wait_for(lambda: _connects(port), 30, "the store's port",
                 proc=self.store)
        trainer = [PY, "-m", "edl_tpu.examples.lm_train", *lm_args,
                   "--data-dir", os.path.join(self.dir, "data"),
                   "--ckpt-dir", os.path.join(self.dir, "ckpt"),
                   "--ckpt-sharded", "--ckpt-steps", str(CKPT_STEPS)]
        self.launcher = spawn(
            [PY, "-m", "edl_tpu.collective.launch", "--store",
             f"127.0.0.1:{port}", "--job-id", name, "--nodes-range",
             "1:1", "--log-dir", os.path.join(self.dir, "log"), "--",
             *trainer],
            self.launcher_log,
            {**env, "EDL_TPU_LOG_EVERY": "1",
             "EDL_TPU_CHECKPOINT_KEEP": "2"})
        self.trainer_pids: list[int] = []

    def next_trainer(self, timeout: float = 120) -> int:
        """pid of the generation the launcher starts next."""
        def probe():
            pids = ints_of(read(self.launcher_log).splitlines(),
                           r"started trainer rank=0 pid=(\d+)")
            return pids[len(self.trainer_pids):]
        pid = wait_for(probe, timeout, "the launcher to start a trainer",
                       proc=self.launcher)[0]
        self.trainer_pids.append(pid)
        return pid

    def lines(self, pid: int) -> list[str]:
        return lines_of(read(self.worker_log), pid)

    def wait_steps(self, pid: int, n: int, timeout: float,
                   sealed_from: int | None = None) -> dict[int, float]:
        """Until generation ``pid`` logged ``n`` steps (and, if asked,
        sealed a checkpoint at a step >= ``sealed_from``)."""
        def probe():
            lines = self.lines(pid)
            steps = steps_of(lines)
            sealed = self.sealed(pid)
            if len(steps) >= n and (sealed_from is None or (
                    sealed and sealed[-1] >= sealed_from
                    and max(steps) >= sealed[-1] + 3)):
                return steps
            if not alive(pid):
                raise SmokeFailure(
                    f"trainer {pid} died:\n{tail(self.worker_log)}")
        return wait_for(probe, timeout, f"{n} steps of trainer {pid}",
                        proc=self.launcher)

    def sealed(self, pid: int) -> list[int]:
        return ints_of(self.lines(pid),
                       r"saved sharded checkpoint .*step=(\d+)\)")

    def live_trainers(self) -> list[int]:
        # the launcher's command line holds the trainer's too
        return pids_matching(self.dir, "examples.lm_train",
                             without="collective.launch")

    def stop(self) -> None:
        """SIGTERM the launcher as a shrink does; it releases its trainer
        into a graceful stop. Nothing of the job is left alive."""
        try:
            self.launcher.send_signal(signal.SIGTERM)
            self.launcher.wait(timeout=60)
            wait_for(lambda: not self.live_trainers(), 240,
                     "the released trainer to seal and exit")
        finally:
            for pid in [*self.live_trainers(), self.launcher.pid,
                        self.store.pid]:
                kill_group(pid, signal.SIGKILL)


def _connects(port: int) -> bool:
    try:
        socket.create_connection(("127.0.0.1", port), timeout=1).close()
        return True
    except OSError:
        return False


def check_generation(job: Job, pid: int, what: str, *, tpu: bool,
                     local_batch: int | None = None) -> dict:
    """Facts every generation must show in its own log lines."""
    lines = job.lines(pid)
    text = "\n".join(lines)
    m = re.search(r"device: platform=(\S+) kind='([^']*)' count=(\d+) "
                  r"attention=(\S+)", text)
    check(m is not None, f"{what}: trainer reported its device")
    device = {"platform": m.group(1), "kind": m.group(2),
              "count": int(m.group(3))}
    say(f"{what}: device {device} attention={m.group(4)}")
    if tpu:
        check(device["platform"] == "tpu", f"{what}: trainer ran on a TPU")
        flash = re.findall(r"flash attention (fwd|bwd) \((\d+), [^)]*\): "
                           r"(.*)", text)
        check(m.group(4) == "flash" and flash
              and all(mode.startswith("pallas kernel, compiled")
                      for _, _, mode in flash)
              and {d for d, _, _ in flash} == {"fwd", "bwd"},
              f"{what}: attention was the compiled flash kernel, fwd and "
              f"bwd ({len(flash)} traces, none blockwise or interpreted)")
        if local_batch is not None:  # bwd: traced by the step alone
            check({int(b) for d, b, _ in flash if d == "bwd"}
                  == {local_batch},
                  f"{what}: flash ran under shard_map on {local_batch} "
                  "sequences a chip")
    m = re.search(r"first-step wall \(trace\+compile\+run\) ([\d.]+)s, "
                  r"persistent compile cache (\{.*\})", text)
    check(m is not None, f"{what}: first step done")
    cache = ast.literal_eval(m.group(2))
    say(f"{what}: first step {m.group(1)}s, persistent compile cache "
        f"hits={cache['hits']} misses={cache['misses']}")
    return {"device": device, "cache": cache,
            "first_step_s": float(m.group(1))}


def check_resume(job: Job, pid: int, want_step: int, what: str) -> None:
    lines = job.lines(pid)
    restored = ints_of(lines, r"restored checkpoint .*step=(\d+)\) in")
    first = ints_of(lines, r"first-step-complete global_step=(\d+)")
    check(restored == [want_step] and first == [want_step + 1],
          f"{what}: restored sealed step {want_step}, first step after "
          f"it is global_step {want_step + 1} (log: restored={restored} "
          f"first={first})")


def check_snapshot_outlived_steps(job: Job, pid: int, sealed: int,
                                  what: str, *, tpu: bool) -> None:
    """The snapshot of step ``sealed`` waited for its writer while the
    loop ran on: the steps logged before the seal each donated the
    buffers it was fetched from. (The replay after the resume, which
    `check_losses` holds to four decimals, is what shows it untorn.)"""
    during = 0
    for ln in job.lines(pid):
        if re.search(rf"saved sharded checkpoint .*step={sealed}\)", ln):
            break
        m = re.search(r"epoch \d+ step (\d+): ", ln)
        during += bool(m and int(m.group(1)) > sealed)
    say(f"{what}: {during} donating steps between the snapshot of step "
        f"{sealed} and its seal")
    if tpu:  # a toy state is written before the next step is logged
        check(during >= 3, f"{what}: at least three steps donated their "
              "buffers while the snapshot waited for the writer")


def check_copied_bytes(job: Job, pid: int, what: str, *, tpu: bool) -> None:
    """The trainer's `ckpt plane:` line (written on every way out of the
    loop): on a chip the fetched arrays are host memory of their own and
    none is copied; on the cpu platform all of them are."""
    m = re.search(r"ckpt plane: (\{.*\})", "\n".join(job.lines(pid)))
    check(m is not None, f"{what}: trainer wrote its ckpt plane line")
    stats = ast.literal_eval(m.group(1))
    copied = stats["ckpt_copied_bytes_last"]
    say(f"{what}: ckpt_copied_bytes_last={copied} after "
        f"{stats['ckpt_saves_async']} async saves, snapshot "
        f"{stats['ckpt_snapshot_ms_last']} ms, write "
        f"{stats['ckpt_write_s_last']} s")
    check(copied == 0 if tpu else copied > 0,
          f"{what}: copied_bytes_last={copied} "
          + ("(handed to the writer as fetched)" if tpu
             else "(cpu platform: private copies)"))


def check_losses(before: dict[int, float], after: dict[int, float],
                 what: str, tol: float = 0.05, replayed: int = 0) -> None:
    check(all(math.isfinite(v) for v in [*before.values(),
                                         *after.values()]),
          f"{what}: all {len(before) + len(after)} losses finite")
    both = sorted(set(before) & set(after))
    if replayed:
        check(len(both) >= replayed,
              f"{what}: at least {replayed} steps were replayed ({both})")
    if both:  # the replayed steps: same state, same batches
        worst = max(abs(before[s] - after[s]) for s in both)
        check(worst <= tol, f"{what}: steps {both[0]}..{both[-1]} replayed "
              f"after the resume give the losses they gave before it "
              f"(max |diff| {worst:.4f})")
    else:
        last = [before[s] for s in sorted(before)[-3:]]
        first = [after[s] for s in sorted(after)[:3]]
        check(abs(sum(first) / len(first) - sum(last) / len(last)) <= 0.5,
              f"{what}: losses after the resume {first} sit with the last "
              f"ones before it {last}")


def phase_train(work: str, env: dict, *, tpu: bool, lm_args: list[str],
                mesh_args: tuple = (), local_batch: int | None = None
                ) -> dict:
    """steps -> sealed async sharded checkpoint -> SIGKILL -> respawn ->
    restore -> steps; with the graceful stop after it on one chip."""
    job = Job(work, "smoke", [*lm_args, *mesh_args], env)
    try:
        # generation 1: cold start
        g1 = job.next_trainer()
        steps1 = job.wait_steps(g1, CKPT_STEPS + 2, 600,
                                sealed_from=CKPT_STEPS)
        gen1 = check_generation(job, g1, "gen 1 (cold)", tpu=tpu,
                                local_batch=local_batch)
        say(f"gen 1 losses {fmt_losses(steps1)}")
        kill_group(g1, signal.SIGKILL)
        say(f"SIGKILL -> trainer {g1}")
        time.sleep(0.5)
        steps1 = steps_of(job.lines(g1))
        sealed = job.sealed(g1)[-1]
        say(f"gen 1 reached step {max(steps1)}, newest sealed async "
            f"sharded checkpoint at step {sealed}")
        check_snapshot_outlived_steps(job, g1, sealed, "gen 1", tpu=tpu)
        # generation 2: respawned by the launcher, restores, continues
        g2 = job.next_trainer()
        steps2 = job.wait_steps(g2, 4, 600)
        gen2 = check_generation(job, g2, "gen 2 (after SIGKILL)", tpu=tpu,
                                local_batch=local_batch)
        check_resume(job, g2, sealed, "gen 2")
        say(f"gen 2 losses {fmt_losses(steps2)}")
        # to the four decimals the trainer logs: a snapshot torn by a
        # donating step would restore another state than the one saved
        check_losses(steps1, steps2, "gen 1 -> gen 2", tol=1.5e-4,
                     replayed=3)
        check(gen2["cache"]["hits"] >= max(1, gen2["cache"]["misses"]),
              "gen 2 compiled from the persistent cache "
              f"(hits {gen2['cache']['hits']}, misses "
              f"{gen2['cache']['misses']}; cold start had "
              f"{gen1['cache']['misses']} misses)")
        result = {"device": gen1["device"], "job": job,
                  "losses": {**steps1, **steps2},
                  "first_step_cold_s": gen1["first_step_s"],
                  "first_step_cached_s": gen2["first_step_s"]}
        if mesh_args:  # the four-chip path ends here
            return result
        # graceful stop: SIGTERM to the trainer's group, as
        # process.release_trainer sends it
        kill_group(g2, signal.SIGTERM)
        t_term = time.monotonic()
        say(f"SIGTERM -> trainer {g2}")
        most = 0
        while alive(g2):
            most = max(most, len(job.live_trainers()))
            if time.monotonic() - t_term > 300:
                raise SmokeFailure("the stopped trainer never exited")
            time.sleep(0.05)
        t_gone = time.monotonic()
        started = ints_of(read(job.launcher_log).splitlines(),
                          r"started trainer rank=0 pid=(\d+)")
        check(started == [g1, g2],
              "no replacement was started while the stopped trainer lived")
        lines2 = job.lines(g2)
        stop_at = ints_of(lines2, r"graceful stop at epoch \d+ step (\d+)")
        check(len(stop_at) == 1 and job.sealed(g2)[-1] == stop_at[0],
              f"the stopped trainer sealed its live state (step "
              f"{stop_at}) and exited {t_gone - t_term:.1f}s after "
              f"SIGTERM; at most {most} live trainer process(es) "
              "meanwhile")
        check(most <= 1, "never two live trainers on the chip")
        check_copied_bytes(job, g2, "gen 2", tpu=tpu)
        g3 = job.next_trainer()
        steps3 = job.wait_steps(g3, 3, 600)
        check_generation(job, g3, "gen 3 (after SIGTERM)", tpu=tpu)
        check_resume(job, g3, stop_at[0], "gen 3")
        say(f"gen 3 losses {fmt_losses(steps3)}")
        check_losses(steps_of(lines2), steps3, "gen 2 -> gen 3")
        return result
    except SmokeFailure:
        say("trainer log tail:\n" + tail(job.worker_log, 30))
        say("launcher log tail:\n" + tail(job.launcher_log, 15))
        raise
    finally:
        job.stop()


def fmt_losses(steps: dict[int, float]) -> str:
    return " ".join(f"{s}:{v:.4f}" for s, v in sorted(steps.items()))


def phase_serve(work: str, env: dict, *, tpu: bool, model_args: list[str],
                shape: tuple[int, ...]) -> None:
    import numpy as np

    import edl_tpu.distill as distill  # wire-only: must not wake JAX
    port = free_port()
    log_path = os.path.join(work, "teacher.log")
    server = spawn([PY, "-m", "edl_tpu.distill.teacher_server",
                    *model_args, "--input-dtype", "uint8", "--serve-topk",
                    "16", "--host", "127.0.0.1", "--port", str(port)],
                   log_path, env)
    try:
        m = wait_for(lambda: re.search(
            r"serving from (\d+) x (.*) \(platform (\S+)\)",
            read(log_path)), 600, "the teacher to come up", proc=server)
        say(f"teacher up on {m.group(1)} x {m.group(2)} ({m.group(3)})")
        if tpu:
            check(m.group(3) == "tpu", "the teacher serves from a TPU")
        client = distill.TeacherClient(f"127.0.0.1:{port}", timeout=300,
                                       expand=False)
        rng = np.random.default_rng(0)
        batches = [rng.integers(0, 256, size=(n, *shape), dtype=np.uint8)
                   for n in (16, 16, 5)]
        t0 = time.monotonic()
        outs = [client.predict({"image": b}) for b in batches]
        again = client.predict({"image": batches[0]})
        say(f"4 requests answered in {time.monotonic() - t0:.2f}s "
            "(first one compiles)")
        for b, out in zip(batches, outs):
            idx, val = out["logits.idx"], out["logits.val"]
            check(idx.shape == (len(b), 16) and val.shape == (len(b), 16)
                  and idx.dtype == np.int32 and val.dtype == np.float16
                  and int(idx.min()) >= 0 and int(idx.max()) < 1000
                  and bool(np.isfinite(val.astype(np.float32)).all())
                  and bool((np.diff(val.astype(np.float32)) <= 0).all()),
                  f"batch of {len(b)}: top-16 (idx int32 in [0,1000), "
                  "val fp16 finite, descending)")
        check(np.array_equal(again["logits.idx"], outs[0]["logits.idx"])
              and np.array_equal(again["logits.val"],
                                 outs[0]["logits.val"]),
              "an equal request gets an equal answer")
        stats = client.stats()
        check(stats["served_rows"] == 53,
              f"server counted {stats['served_rows']} rows served")
        check(client.drain(), "drain acknowledged")
        client.close()
        jax_mod = sys.modules.get("jax")
        woke = bool(jax_mod) and \
            sys.modules["jax._src.xla_bridge"].backends_are_initialized()
        check(not woke, "this process (the client) never initialized a "
              f"JAX backend (jax imported: {bool(jax_mod)})")
    finally:
        kill_group(server.pid, signal.SIGTERM)
        try:
            server.wait(timeout=20)
        except subprocess.TimeoutExpired:
            kill_group(server.pid, signal.SIGKILL)


def run_child(name: str, env: dict, timeout: float) -> dict:
    """A phase that is this file's own code, in a child that owns the
    chip. Its last stdout line is its JSON result."""
    proc = subprocess.Popen([PY, os.path.abspath(__file__), "--child",
                             name], cwd=REPO, env=env,
                            stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    _procs.append(proc)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        kill_group(proc.pid, signal.SIGKILL)
        raise SmokeFailure(f"child {name} timed out after {timeout:.0f}s")
    lines = out.strip().splitlines()
    for ln in lines[:-1]:
        print("    " + ln, flush=True)
    if proc.returncode != 0:
        raise SmokeFailure(f"child {name} exited {proc.returncode} "
                           "(its reason is on stderr)")
    return json.loads(lines[-1])


# -- children (these import JAX) ----------------------------------------------

def flash_against(path: str, fa, shape, qkvdo, blk: int, kw: dict,
                  report) -> None:
    """This checkout's flash kernels against another checkout's (its
    `flash_attention.py` at `path`, e.g. the parent commit unpacked
    under `_checkout/`, which `.gitignore` lists) and both against the
    float32 blockwise scan at matmul precision highest: o, dq, dk, dv
    must differ from each other by no more than either differs from
    float32. Prints max and rms of every difference and a call's time.
    With fewer key/value heads than query heads the other checkout,
    whose kernels know one head a program, gets them repeated and its
    dk, dv summed over each group, as its callers did."""
    import importlib.util

    import jax
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location("other_flash", path)
    other = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(other)

    h, kv = qkvdo[0].shape[2], qkvdo[1].shape[2]

    def run(mod):
        repeat = mod is other and kv != h

        def f(q, k, v, do):
            if repeat:
                k, v = (jnp.repeat(x, h // kv, axis=2) for x in (k, v))
            o, lse = mod._fwd(q, k, v, blk_q=blk, blk_k=blk,
                              interpret=False, **kw)
            dq, dk, dv = mod._bwd_pallas(q, k, v, o, lse, do, blk_q=blk,
                                         blk_k=blk, dlse=None,
                                         interpret=False, **kw)
            if repeat:
                dk, dv = (x.reshape(*x.shape[:2], kv, h // kv, -1).sum(3)
                          for x in (dk, dv))
            return o, dq, dk, dv
        f = jax.jit(f)
        try:
            out = jax.block_until_ready(f(*qkvdo))
        except Exception as exc:  # noqa: BLE001 - the compiler's refusal
            return None, str(exc).splitlines()[0][:160]
        t0 = time.perf_counter()
        for _ in range(10):
            out = f(*qkvdo)
        jax.block_until_ready(out)
        return out, f"{(time.perf_counter() - t0) * 100:.3f} ms a call"

    def gap(a, b):  # (rms, max) of a - b
        d = a.astype(jnp.float32) - b.astype(jnp.float32)
        return float(jnp.sqrt(jnp.mean(d * d))), float(jnp.max(jnp.abs(d)))

    with jax.default_matmul_precision("highest"):
        q32, k32, v32, do32 = (x.astype(jnp.float32) for x in qkvdo)
        o32, lse32 = jax.jit(lambda q, k, v: fa._fwd_blockwise(
            q, k, v, blk=blk, **kw))(q32, k32, v32)
        ref = (o32, *jax.jit(lambda *a: fa._bwd_blockwise(
            *a, blk=blk, **kw))(q32, k32, v32, o32, lse32, do32))
    new, new_said = run(fa)
    old, old_said = run(other)
    if old is None:
        report(f"flash old/new {shape}", True,
               f"the other checkout's kernels do not run alone here "
               f"({old_said}); new {new_said}")
        return
    worse = []
    for name, n, o, r in zip(("o", "dq", "dk", "dv"), new, old, ref):
        (nr, nrm), (orr, orm), (no, nom) = gap(n, r), gap(o, r), gap(n, o)
        # each carries its own rounding against float32, so two good
        # kernels differ by up to both errors together; and the new one
        # may not sit further from float32 than the old one did
        if no > nr + orr or nr > 1.25 * orr:
            worse.append(name)
        print(f"     {name}: rms (max) new-f32 {nr:.3e} ({nrm:.2e}), "
              f"old-f32 {orr:.3e} ({orm:.2e}), new-old {no:.3e} "
              f"({nom:.2e})", flush=True)
    report(f"flash old/new {shape}", not worse,
           f"new {new_said}, old {old_said}; further from each other, or "
           f"new further from float32, than the roundings allow: "
           f"{worse or 'none'}")


def worst_gap(got, want) -> float:
    """max |got - want| over max |want|, both as float32."""
    import jax.numpy as jnp
    want = want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def ms_a_call(fn, args, calls: int = 10) -> float:
    """Milliseconds a call of a compiled `fn` on the host's clock."""
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def scan_against_einsums(shape, chunk: int, *, interpret: bool) -> dict:
    """The chunked scan's two kernels (`ops/ssd.py`) against its einsums
    on the same bfloat16 inputs, and both against the einsums computed
    in float32: per output (y, then the gradients of x, dt, A, B, C) the
    worst |difference| over the largest value. shape: (B, S, H, P, N)."""
    import jax
    import jax.numpy as jnp

    from edl_tpu.ops import ssd
    b, s, h, p, n = shape
    if not ssd._fits(chunk, h, p, n):
        raise SystemExit(f"the scan's kernels do not take {shape} in "
                         f"chunks of {chunk}")
    key = jax.random.split(jax.random.PRNGKey(0), 6)
    f32, bf16 = jnp.float32, jnp.bfloat16
    x, dy = (jax.random.normal(k, (b, s // chunk, chunk, h, p), f32)
             for k in key[:2])
    bm, cm = (jax.random.normal(k, (b, s // chunk, chunk, n), f32)
              / n ** 0.25 for k in key[2:4])
    # step sizes and decay rates as the initialisers spread them
    dt = jax.nn.softplus(jax.random.normal(
        key[4], (b, s // chunk, chunk, h)) - 3.0)
    a = -jnp.exp(jax.random.uniform(key[5], (h,), minval=0.0, maxval=2.7))

    def both(forward, backward, dtype):
        """y and the five gradients in the dtypes `ssd_scan` hands them
        on in."""
        def run(x, dy, bm, cm, dt, a):
            args = (x.astype(dtype), dt, a, bm.astype(dtype),
                    cm.astype(dtype))
            y, prev = forward(*args)
            grads = backward(*args, prev, dy.astype(dtype))
            return tuple(g.astype(like.dtype) for g, like in zip(
                (y, *grads), (args[0], *args)))
        return jax.jit(run)(x, dy, bm, cm, dt, a)
    kernels = both(
        lambda *t: ssd._forward_pallas(*t, interpret=interpret),
        lambda *t: ssd._backward_pallas(*t, interpret=interpret),
        bf16)
    einsums = both(ssd._forward_einsums, ssd._backward_einsums, bf16)
    with jax.default_matmul_precision("highest"):
        exact = both(ssd._forward_einsums, ssd._backward_einsums, f32)

    names = ("y", "dx", "d_dt", "d_a", "dB", "dC")
    return {"kernels_vs_einsums": dict(zip(names, map(
                worst_gap, kernels, einsums))),
            "kernels_vs_float32": dict(zip(names, map(
                worst_gap, kernels, exact))),
            "einsums_vs_float32": dict(zip(names, map(
                worst_gap, einsums, exact)))}


def scan_verdict(report, shape, chunk, *, interpret: bool) -> dict:
    """`scan_against_einsums`, held, output by output: the kernels no
    further from float32 than 1.5 times what the einsums are plus 1e-3
    of the largest value, and no further from the einsums than bfloat16
    allows (3e-2) plus the einsums' own distance from float32 (on the
    chip the einsums' dA is a few per cent off, the kernels' is not)."""
    gaps = scan_against_einsums(shape, chunk, interpret=interpret)
    far = gaps["einsums_vs_float32"]
    ok_ = all(gaps["kernels_vs_float32"][k] <= 1.5 * far[k] + 1e-3
              and gaps["kernels_vs_einsums"][k] <= 3e-2 + far[k]
              for k in far)
    report(f"ssd scan {shape} chunk {chunk}", ok_, "; ".join(
        f"{k} " + " ".join(f"{name} {v:.1e}" for name, v in row.items())
        for k, row in gaps.items()))
    return gaps


def stages_against_expressions(shape, *, interpret: bool) -> dict:
    """The mixer's two elementwise stages (`ops/ssm_stages.py`): each
    stage's kernels, forward and written-out backward, against the
    expressions they replace on the same bfloat16 inputs, and both
    against the expressions in float32 on those inputs: per output and
    gradient the worst |difference| over the largest value; and the
    milliseconds a call (forward and backward) on the host's clock.
    shape: (B, S, H, P, N)."""
    import jax
    import jax.numpy as jnp

    from edl_tpu.ops import ssm_stages as st
    b, s, h, p, n = shape
    inner, sizes = h * p, (h * p, n, n)
    if st._conv_plan(s, inner, sizes) is None \
            or st._norm_plan(s, inner) is None:
        raise SystemExit(f"the stages' kernels do not take {shape}")
    key = jax.random.split(jax.random.PRNGKey(0), 12)
    f32, bf16 = jnp.float32, jnp.bfloat16

    def lo(k, *dims):  # bfloat16 values, so that float32 starts level
        return jax.random.normal(k, dims, f32).astype(bf16)
    proj = lo(key[0], b, s, 2 * inner + 2 * n + h)
    y, x = lo(key[1], b, s, h, p), lo(key[2], b, s, h, p)
    taps = jax.random.uniform(key[3], (4, inner + 2 * n), f32, -0.5, 0.5)
    bias = 0.2 * jax.random.normal(key[4], (inner + 2 * n,), f32)
    skip = jax.random.uniform(key[5], (h,), f32, 0.5, 1.5)
    scale = jax.random.uniform(key[6], (inner,), f32, 0.5, 1.5)
    d_conv = tuple(lo(k, b, s, z) for k, z in zip(key[7:10], sizes))
    d_norm = lo(key[10], b, s, inner)

    def conv(kernels, dtype):
        def run(proj, taps, bias, dys):
            def f(proj, taps, bias):
                if kernels:
                    return st._conv_kernels(proj, taps, bias, inner, sizes,
                                            interpret)
                return st._conv_expressions(
                    proj[..., inner:inner + sum(sizes)], taps, bias, sizes)
            outs, pull = jax.vjp(f, proj.astype(dtype), taps, bias)
            # the kernels hand x out twice (`ssm_stages.conv`): the
            # second's cotangent is the first's again, so double the
            # expressions' to compare
            dys = [d.astype(dtype) for d in dys]
            if kernels:
                return outs[:3], pull((*dys, dys[0]))
            return outs, pull((2 * dys[0], *dys[1:]))
        return jax.jit(run), (proj, taps, bias, d_conv)

    def norm(kernels, dtype):
        def run(y, x, proj, skip, scale, do):
            def f(y, x, proj, skip, scale):
                if kernels:
                    return st._norm_kernels(y, x, proj, skip, scale, 1e-5,
                                            interpret)
                return st._gate_norm_expressions(
                    y, x, proj[..., :inner], skip, scale, 1e-5)
            out, pull = jax.vjp(f, y.astype(dtype), x.astype(dtype),
                                proj.astype(dtype), skip, scale)
            return (out,), pull(do.astype(dtype))
        return jax.jit(run), (y, x, proj, skip, scale, d_norm)

    res = {}
    for stage, build, names in (
            ("conv", conv, ("x", "B", "C", "d_proj", "d_taps", "d_bias")),
            ("gate_norm", norm, ("out", "dy", "dx", "d_proj", "d_skip",
                                 "d_scale"))):
        flat = {}
        for form, kernels, dtype in (("kernels", True, bf16),
                                     ("expressions", False, bf16),
                                     ("float32", False, f32)):
            fn, args = build(kernels, dtype)
            with jax.default_matmul_precision("highest"):
                outs, grads = fn(*args)
            flat[form] = (*outs, *grads)
            if dtype == bf16:
                res[f"{stage}_{form}_ms"] = ms_a_call(fn, args)
        for a, b_ in (("kernels", "expressions"), ("kernels", "float32"),
                      ("expressions", "float32")):
            res[f"{stage}_{a}_vs_{b_}"] = dict(zip(names, map(
                worst_gap, flat[a], flat[b_])))
    return res


def stages_verdict(report, shape, *, interpret: bool) -> dict:
    """`stages_against_expressions`, held like the scan's: the kernels
    no further from float32 than 1.5 times what the expressions are plus
    1e-3 of the largest value, and no further from the expressions than
    bfloat16 allows (3e-2) plus the expressions' own distance."""
    res = stages_against_expressions(shape, interpret=interpret)
    for stage in ("conv", "gate_norm"):
        far = res[f"{stage}_expressions_vs_float32"]
        ok_ = all(res[f"{stage}_kernels_vs_float32"][k] <= 1.5 * far[k] + 1e-3
                  and res[f"{stage}_kernels_vs_expressions"][k]
                  <= 3e-2 + far[k] for k in far)
        report(f"ssm {stage} {shape}", ok_, "; ".join(
            f"{k[len(stage) + 1:]} " + (f"{row:.3f}" if k.endswith("_ms")
                                        else " ".join(
                f"{name} {v:.1e}" for name, v in row.items()))
            for k, row in res.items() if k.startswith(stage)))
    return res


def reporter():
    """(report(name, ok, detail), the names that failed so far)."""
    failures = []

    def report(name, ok_, detail):
        print(f"{'ok  ' if ok_ else 'FAIL'} {name}: {detail}", flush=True)
        if not ok_:
            failures.append(name)
    return report, failures


def child_scan_rehearsal() -> dict:
    """`--rehearse-cpu`: the scan's comparison in interpret mode at a
    small shape (two head blocks, three chunks)."""
    report, failures = reporter()
    gaps = scan_verdict(report, (2, 384, 16, 64, 128), 128, interpret=True)
    stages = stages_verdict(report, (2, 2048, 8, 32, 128), interpret=True)
    return {"ok": not failures, "failed": failures, "scan": gaps,
            "stages": stages}


def child_kernels(other_flash: str = "") -> dict:
    """Each Pallas kernel against its XLA expression, on the device, at
    LM-large shapes and a 4 MiB bucket (plus a ragged one). With
    `other_flash` (another checkout's `flash_attention.py`) also the
    flash kernels of the two checkouts against each other."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from edl_tpu.ops import opt_kernels as ok
    from edl_tpu.ops import pack

    # the package re-exports the function under the module's name
    fa = importlib.import_module("edl_tpu.ops.flash_attention")

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform}")
    if not (pack._use_pallas() and ok._use_pallas()) or ok._interpret():
        raise SystemExit("on a TPU, yet a kernel family is off its "
                         "compiled Pallas path")
    report, failures = reporter()

    def diff(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    # flash attention fwd/bwd vs the XLA blockwise scan (bf16 tolerance):
    # LM-large, d_model 1024, and the benchmark's shapes (d8, OLMoE)
    # and the afmoe cell's: its global layer, and its sliding layers
    # with the window's block skipping on both sides of the band; then
    # the same two and the hybrid's layer on the key/value heads the
    # cells have (4 and 8), found by index
    for shape, window, kv in (((8, 1024, 16, 128), None, 16),
                              ((16, 1024, 16, 64), None, 16),
                              ((6, 2048, 16, 128), None, 16),
                              ((4, 4096, 16, 128), None, 16),
                              ((2, 8192, 32, 128), None, 32),
                              ((2, 8192, 32, 128), 2048, 32),
                              ((2, 8192, 32, 128), None, 4),
                              ((2, 8192, 32, 128), 2048, 4),
                              ((2, 8192, 32, 64), None, 8)):
        key = jax.random.PRNGKey(0)
        q, k, v, do = (jax.random.normal(
            jax.random.fold_in(key, i),
            (*shape[:2], kv if i in (1, 2) else shape[2], shape[3]),
            jnp.bfloat16) for i in range(4))
        kw = dict(scale=shape[-1] ** -0.5, causal=True)
        blk = fa._fit_block(shape[1], 512)
        if kv != shape[2]:  # `shape` from here on is the report's label
            shape = f"{shape} kv {kv}"
        if window:
            kw["window"] = window
            shape = f"{shape} window {window}"
        o_x, lse_x = jax.jit(lambda q, k, v: fa._fwd_blockwise(
            q, k, v, blk=blk, **kw))(q, k, v)
        o_k, lse_k = jax.jit(lambda q, k, v: fa._fwd(
            q, k, v, blk_q=blk, blk_k=blk, interpret=False, **kw))(q, k, v)
        report(f"flash fwd {shape}", diff(o_k, o_x) < 3e-2
               and diff(lse_k, lse_x) < 3e-2,
               f"max|o diff| {diff(o_k, o_x):.2e} "
               f"max|lse diff| {diff(lse_k, lse_x):.2e}")
        dlse = jnp.zeros_like(lse_x)
        g_x = jax.jit(lambda *a: fa._bwd_blockwise(
            *a, blk=blk, dlse=dlse, **kw))(q, k, v, o_x, lse_x, do)
        g_k = jax.jit(lambda *a: fa._bwd_pallas(
            *a, blk_q=blk, blk_k=blk, dlse=dlse, interpret=False,
            **kw))(q, k, v, o_x, lse_x, do)
        worst = max(diff(a, b) / (float(jnp.max(jnp.abs(
            b.astype(jnp.float32)))) + 1e-6) for a, b in zip(g_k, g_x))
        report(f"flash bwd {shape}", worst < 3e-2,
               f"max relative |dq,dk,dv diff| {worst:.2e}")
        if other_flash and not window:
            flash_against(other_flash, fa, shape, (q, k, v, do), blk, kw,
                          report)
        if q.shape[1] == 8192:  # what a layer of the afmoe cell costs
            fwd = jax.jit(lambda q, k, v: fa._fwd(
                q, k, v, blk_q=blk, blk_k=blk, interpret=False, **kw))
            bwd = jax.jit(lambda *a: fa._bwd_pallas(
                *a, blk_q=blk, blk_k=blk, dlse=None, interpret=False, **kw))
            took = [ms_a_call(fn, args) for fn, args in (
                (fwd, (q, k, v)), (bwd, (q, k, v, o_x, lse_x, do)))]
            print(f"flash {shape}: forward {took[0]:.2f} ms, backward "
                  f"{took[1]:.2f} ms a call (host clock, 10 calls)",
                  flush=True)
    # the window once more against attention with the whole masked
    # (S, S) scores in float32, at the cell's length, two heads
    from edl_tpu.parallel.ring_attention import dense_attention
    q, k, v, do = (jax.random.normal(jax.random.fold_in(key, i),
                                     (1, 8192, 2, 128), jnp.bfloat16)
                   for i in range(4))

    def pulled(fn):
        return jax.jit(lambda q, k, v: jax.vjp(
            lambda *a: fn(*a, window=2048), q, k, v)[1](do))(q, k, v)
    worst = max(diff(a, b) / (float(jnp.max(jnp.abs(
        b.astype(jnp.float32)))) + 1e-6) for a, b in zip(
        pulled(fa.flash_attention), pulled(dense_attention)))
    o_k = jax.jit(lambda *a: fa.flash_attention(*a, window=2048))(q, k, v)
    o_d = jax.jit(lambda *a: dense_attention(*a, window=2048))(q, k, v)
    report("flash window 2048 against dense masked (1, 8192, 2, 128)",
           diff(o_k, o_d) < 3e-2 and worst < 3e-2,
           f"max|o diff| {diff(o_k, o_d):.2e} max relative |dq,dk,dv diff| "
           f"{worst:.2e}")

    # the chunked scan's kernels at the hybrid cell's shape
    scan = scan_verdict(report, (2, 8192, 64, 64, 128), 256,
                        interpret=False)
    # and the mixer's two elementwise stages' (times on the host's clock)
    stages = stages_verdict(report, (2, 8192, 64, 64, 128), interpret=False)

    rng = np.random.default_rng(0)

    def arr(n, scale=1.0):
        return jnp.asarray((rng.normal(size=n) * scale).astype(np.float32))

    # What a two-plane moment may lose against the fp32 moment it
    # encodes, as a share of the plane's abs-max: payload step times
    # residual step (int8: 1/127 * 1/254; fp8-e4m3: 2^-4 * 2^-4).
    CODEC_BOUND = {"int8": 1e-4, "fp8": 5e-3}

    def state_close(got, twin, true, quant):
        """A fused update's output against its XLA twin's. A quantized
        moment is held to the fp32 moment it encodes instead, and the
        twin's own loss is printed beside it: on a TPU XLA may drop the
        twin's f32->f8->f32 round trip (excess precision), which leaves
        its residual plane empty."""
        if not isinstance(got, ok.QPlane):
            return (bool(jnp.allclose(got, twin, rtol=1e-5, atol=2e-6)),
                    "bitwise" if bool(jnp.array_equal(got, twin))
                    else f"close (max|diff| {diff(got, twin):.1e})")
        top = float(jnp.max(jnp.abs(true))) + 1e-30
        lost = diff(ok.dequant_plane(got, quant), true) / top
        twin_lost = diff(ok.dequant_plane(twin, quant), true) / top
        same = all(bool(jnp.array_equal(x, y)) for x, y in zip(got, twin))
        return (lost <= CODEC_BOUND[quant],
                f"{'bitwise' if same else 'differs'} (loses {lost:.1e} of "
                f"abs-max, twin loses {twin_lost:.1e})")

    # 4 MiB of fp32 = 8192 rows of 128 lanes; the ragged one ends
    # mid-block and mid-tile
    for n in (8192 * 128, (8192 + 37) * 128):
        x = arr(n, 0.02)
        q_x, s_x = jax.jit(pack._pack_xla)(x)
        q_k, s_k = jax.jit(pack.pack_int8)(x)
        off = int(jnp.sum(q_x != q_k))
        report(f"pack_int8 n={n}", float(s_x) == float(s_k)
               and diff(q_k, q_x) <= 1 and off <= n // 10000,
               f"scale equal, {off} of {n} payload bytes differ (by <= 1)")
        p, g = arr(n), arr(n, 0.02)
        for quant in ok.QUANT_MODES:
            if quant == "off":
                m, v = arr(n, 0.01), jnp.abs(arr(n, 1e-3))
            else:
                m = ok.quant_plane(arr(n, 0.01), quant)
                v = ok.quant_plane(jnp.abs(arr(n, 1e-3)), ok.V_QUANT)
            sg = dict(mu=0.9, wd=1e-4)
            ad = dict(b1=0.9, b2=0.999, eps=1e-8, wd=0.01)
            lr, c1, c2 = (jnp.float32(x) for x in (0.1, 0.1, 0.001))
            k_s = ok.sgdm_bucket(p, g, m, lr, quant=quant, **sg)
            k_a = ok.adam_bucket(p, g, m, v, lr, c1, c2, quant=quant, **ad)
            if quant == "off":
                x_s = t_s = ok._sgdm_xla_fp32(p, g, m, lr, **sg)
                x_a = t_a = ok._adam_xla_fp32(p, g, m, v, lr, c1, c2, **ad)
            else:
                m32 = ok.dequant_plane(m, quant)
                v32 = ok.dequant_plane(v, ok.V_QUANT)
                t_s = ok._sgdm_xla_fp32(p, g, m32, lr, **sg)
                t_a = ok._adam_xla_fp32(p, g, m32, v32, lr, c1, c2, **ad)
                o = ok._sgdm_xla_q(p, g, *m, lr, quant=quant, **sg)
                x_s = (o[0], ok.QPlane(*o[1:]))
                o = ok._adam_xla_q(p, g, *m, *v, lr, c1, c2, quant=quant,
                                   **ad)
                x_a = (o[0], ok.QPlane(*o[1:5]), ok.QPlane(*o[5:]))
            for name, got, twin, true, quants in (
                    ("sgdm", k_s, x_s, t_s, (None, quant)),
                    ("adam", k_a, x_a, t_a, (None, quant, ok.V_QUANT))):
                res = [state_close(*abc, qq)
                       for *abc, qq in zip(got, twin, true, quants)]
                report(f"fused {name} {quant} n={n}",
                       all(r[0] for r in res),
                       "p,moments: " + ", ".join(r[1] for r in res))
    return {"ok": not failures, "failed": failures, "scan": scan,
            "stages": stages,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": jax.device_count()}}


# -- the four-chip path -------------------------------------------------------

def four_chips(work: str, env: dict, *, tpu: bool, lm_args: list[str]
               ) -> dict:
    """fsdp over four chips in one process (steps, async sharded save,
    SIGKILL, resume) against the same seed and global batch on one."""
    sharded = phase_train(work, env, tpu=tpu, lm_args=lm_args,
                          mesh_args=("--mesh", "fsdp"), local_batch=2)
    job = sharded["job"]
    text = read(job.worker_log)
    n_params = int(re.search(r"params=(\d+) ", text).group(1))
    total = 12 * n_params  # fp32 params + two fp32 Adam moments
    # as built by gen 1, as built and as restored by gen 2
    placed = [ast.literal_eval(m) for m in re.findall(
        r"state bytes per device: (\{.*\})", text)]
    say(f"state bytes per device {placed}; whole state {total}")
    share = [b / total for held in placed for b in held.values()]
    # a quarter of what fsdp shards, plus what its rules replicate (the
    # token embedding table: parallel/sharding.py "vocab_table")
    check(len(placed) == 3 and all(len(held) == 4 for held in placed)
          and 0.25 <= min(share) and max(share) <= 0.4
          and max(share) - min(share) < 0.01,
          "parameters and Adam state are spread, as built and as "
          "restored: four distinct devices, each holding "
          f"{min(share):.3f}..{max(share):.3f} of the bytes")
    check(sharded["device"]["count"] == 4, "one process drove four chips")
    # what it is compared with: the same steps on one chip, which this
    # child is given through the runtime's own chip-visibility variables
    one_dir = os.path.join(work, "one-chip")
    os.makedirs(one_dir)
    n_steps = max(sharded["losses"])
    log_path = os.path.join(one_dir, "trainer.log")
    one_env = {**env, "EDL_TPU_LOG_EVERY": "1", "TPU_VISIBLE_CHIPS": "0",
               "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
               "TPU_PROCESS_BOUNDS": "1,1,1"}
    if not tpu:
        one_env["JAX_NUM_CPU_DEVICES"] = "1"
    ref = spawn([PY, "-m", "edl_tpu.examples.lm_train", *lm_args,
                 "--data-dir", os.path.join(one_dir, "data")],
                log_path, one_env)
    def enough_steps():
        steps = steps_of(read(log_path).splitlines())
        return steps if len(steps) >= n_steps else None

    try:
        steps = wait_for(enough_steps, 900, f"{n_steps} steps on one chip",
                         proc=ref)
    except SmokeFailure:
        say("one-chip log tail:\n" + tail(log_path))
        raise
    finally:
        kill_group(ref.pid, signal.SIGKILL)
    m = re.search(r"device: platform=(\S+) kind='([^']*)' count=(\d+)",
                  read(log_path))
    check(m is not None and int(m.group(3)) == 1
          and (not tpu or m.group(1) == "tpu"),
          f"the comparison child saw one chip ({m and m.groups()}) through "
          "TPU_VISIBLE_CHIPS")
    say(f"four chips {fmt_losses(sharded['losses'])}")
    say(f"one chip   {fmt_losses(steps)}")
    worst = max(abs(steps[s] - v) for s, v in sharded["losses"].items())
    check(worst <= 0.05, "per-step losses on four chips agree with one "
          f"chip (max |diff| {worst:.4f} over {n_steps} steps)")
    return sharded


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fsdp world on four chips and its "
                         "one-chip comparison")
    ap.add_argument("--arch", choices=("gpt2", "olmoe"), default="gpt2",
                    help="olmoe: only the train phase (save, SIGKILL, "
                         "resume, SIGTERM) with lm_train's --arch olmoe at "
                         "the published widths, one layer")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny shapes on the CPU: checks this script's "
                         "control flow, never prints a verdict")
    ap.add_argument("--child", default="", help=argparse.SUPPRESS)
    ap.add_argument("--other-flash", default="", metavar="PATH",
                    help="with --child kernels: another checkout's "
                         "edl_tpu/ops/flash_attention.py, whose kernels "
                         "are run beside this checkout's")
    args = ap.parse_args()
    if args.child == "kernels":
        print(json.dumps(child_kernels(args.other_flash)))
        return 0
    if args.child == "scan-rehearsal":
        print(json.dumps(child_scan_rehearsal()))
        return 0
    tpu = not args.rehearse_cpu
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearse_cpu:
        env.update(JAX_PLATFORMS="cpu",
                   JAX_NUM_CPU_DEVICES="4" if args.four_chips else "1")
    lm_args = LM_LARGE if tpu else LM_TINY
    if args.arch == "olmoe":
        lm_args = LM_OLMOE if tpu else LM_OLMOE_TINY
    work = tempfile.mkdtemp(prefix="chip-smoke-")
    say(f"work dir {work} ({shutil.disk_usage(work).free >> 30} GiB free); "
        "compile cache: " + (env.get("JAX_COMPILATION_CACHE_DIR")
                             or "in the checkout (.jax_cache)"))
    try:
        if args.four_chips:
            device = four_chips(work, env, tpu=tpu,
                                lm_args=lm_args)["device"]
        elif args.arch == "olmoe":
            say("phase train")
            device = phase_train(work, env, tpu=tpu,
                                 lm_args=lm_args)["device"]
        else:
            say("phase kernels")
            res = run_child("kernels" if tpu else "scan-rehearsal", env,
                            600)
            check(res["ok"], "every Pallas kernel matches its XLA "
                  f"expression on the chip (failed: {res['failed']})"
                  if tpu else "the scan's and the stages' kernels match "
                  "their expressions in interpret mode")
            say("phase train")
            device = phase_train(work, env, tpu=tpu,
                                 lm_args=lm_args)["device"]
            say("phase serve")
            phase_serve(
                work, env, tpu=tpu,
                model_args=["--model", "ResNet50_vd", "--num-classes",
                            "1000", "--input-shape",
                            "224,224,3" if tpu else "32,32,3"],
                shape=(224, 224, 3) if tpu else (32, 32, 3))
    except SmokeFailure as exc:
        say(f"FAILED: {exc}")
        return 1
    finally:
        for proc in _procs:
            if proc.poll() is None:
                kill_group(proc.pid, signal.SIGKILL)
        for pid in pids_matching(work):
            kill_group(pid, signal.SIGKILL)
        shutil.rmtree(work, ignore_errors=True)
    if not tpu:
        say("rehearsal passed (CPU: no verdict)")
        return 0
    check(device["platform"] == "tpu"
          and device["count"] == (4 if args.four_chips else 1),
          f"device as the trainer reported it: {device}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


T0 = time.monotonic()
if __name__ == "__main__":
    sys.exit(main())
