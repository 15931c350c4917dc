"""What run.py hands a driver, and what a driver hands back."""

from __future__ import annotations

import dataclasses
import json
import math
import os
import subprocess
import time

from benchmark.harness.job import Job, lm_args
from benchmark.harness.procs import PY, BenchFailure, kill_group, say, spawn
from benchmark.harness.shards import make_shards

# The reference children's compile cache, beside the trainer's and never
# the same directory: a child's programs (the reference's, and the
# trainer's step once more under a key of its own) pushed the trainer's
# step out of a capped directory (PERF.md section 6), and with no cache
# at all a child compiled them anew in every run, 240 s of JoyAI's 346.
REF_CACHE = ".jax_cache_ref"


@dataclasses.dataclass
class Cell:
    root: str            # the checkout
    work: str            # scratch directory of this run, inside it
    name: str
    chips: int
    config: dict
    config_path: str
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool       # CPU rehearsal: runs to the end, prints no result
    t0: float            # monotonic seconds at which run.py started

    @property
    def tokens_per_step(self) -> int:
        return self.config["run"]["global_batch"] * \
            self.config["run"]["seq_len"]

    def child_env(self) -> dict:
        """Environment of every child: the checkout on the path, the
        compile cache where the caller says or at one fixed place in the
        checkout, and the traffic's own settings."""
        env = dict(os.environ)
        env["PYTHONPATH"] = self.root + os.pathsep * bool(
            env.get("PYTHONPATH")) + env.get("PYTHONPATH", "")
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(self.root, ".jax_cache"))
        # the TPU runtime's own logs default to /tmp/tpu_logs
        env.setdefault("TPU_LOG_DIR", os.path.join(self.work, "tpu_logs"))
        env["EDL_TPU_LOG_EVERY"] = str(self.traffic["log_every"])
        env.update(self.traffic.get("env", {}))
        if self.trace:
            env["EDL_TPU_PROFILE_START"] = str(
                self.traffic["profile"]["start_step"])
            env["EDL_TPU_PROFILE_STEPS"] = str(
                self.traffic["profile"]["steps"])
        return env

    def reference_env(self) -> dict:
        """`child_env` for a reference child that compiles minutes of
        programs: they go to a directory of the child's own in the
        checkout, whatever the caller gave the trainer."""
        return {**self.child_env(), "JAX_COMPILATION_CACHE_DIR":
                os.path.join(self.root, REF_CACHE)}

    @property
    def trace_dir(self) -> str:
        return os.path.join(self.work, "trace")

    @property
    def data_dir(self) -> str:
        return os.path.join(self.work, "data")


def start_training(cell: Cell, extra_flags: list[str]) -> tuple[Job, int, dict]:
    """Shards from the seed, then store + launcher + trainer; returns once
    the first generation has logged its device."""
    shards = cell.traffic["shards"]
    make_shards(cell.data_dir, shards["files"], shards["rows_per_file"],
                cell.config["run"]["seq_len"], cell.config["vocab_size"],
                cell.seed)
    say(f"wrote {shards['files']} shards of {shards['rows_per_file']} rows")
    flags = [*lm_args(cell.config, cell.data_dir),
             *cell.traffic.get("trainer_flags", []), *extra_flags]
    if cell.trace:
        flags += ["--profile", cell.trace_dir]
    job = Job(cell.root, os.path.join(cell.work, "job"), flags,
              cell.child_env(), cell.chips)
    try:
        for attempt in range(3):
            pid = job.next_trainer()
            try:
                return job, pid, job.device(pid, cell.rehearse)
            except BenchFailure as e:
                # On the four-chip host four of ten trainers got SIGTERM
                # from their launcher 14 s into the runtime's start, and
                # the launcher started another (my chip runs, PR 22; most
                # likely its 10 s lease expired while the host stalled,
                # PERF.md §6; the steady traffic asks for a longer one).
                # An elastic job goes on, and so does the run: the lost
                # time shows in `setup_s`.
                say(f"generation {attempt + 1} ended before its device "
                    f"line ({str(e).splitlines()[0]}); waiting for the "
                    "launcher's next")
        raise BenchFailure("three trainers in a row ended before their "
                           "device line")
    except BaseException:  # the caller never gets a job to kill
        job.kill()
        raise


def windows(lines: list[tuple[float, int, float]], profiled=None
            ) -> list[tuple[tuple, tuple]]:
    """The log windows between consecutive step lines, each as (the line
    that opens it, the line that closes it). ``profiled`` is (the
    profiler's first step, the stamp of its 'trace written' line) in a
    traced run: the windows the profiler touched are left out, because
    starting it and writing its file stall the host (0.7 s on one chip,
    seconds for a 78 MB trace of four; my chip runs, PR 22), and a
    reader of the loop must not read the profiler."""
    pairs = list(zip(lines, lines[1:]))
    if profiled:
        first_step, t_written = profiled
        pairs = [(a, b) for a, b in pairs
                 if b[1] < first_step or a[0] > t_written]
    return pairs


def rate_over(pairs: list[tuple[tuple, tuple]], tokens_per_step: int
              ) -> float:
    """Tokens of the optimizer steps inside the windows over the windows'
    time on this process's clock. Over (first line, last line) of a
    measured window it is the whole window's rate, every stall inside
    it included: what a user pays for."""
    return tokens_per_step * sum(b[1] - a[1] for a, b in pairs) / sum(
        b[0] - a[0] for a, b in pairs)


def bad_steps(lines: list[tuple[float, int, float]], log_every: int) -> int:
    """Steps the log shows as failed: every logged non-finite loss stands
    for the `log_every` steps of its line."""
    return log_every * sum(not math.isfinite(v) for _, _, v in lines)


def reference_child(cell: Cell, checker: str, step: int, env: dict,
                    timeout: float) -> dict:
    """The last line of what the module ``checker`` prints, started once
    the trainer has ended (a chip belongs to one process) through
    `procs.spawn`: `run.py` ends it with everything else it started, and
    the kernel ends it when `run.py` is killed. Its standard error is
    kept in the run's work directory, and its phase lines are repeated
    here: the last says what it compiled and what it read from its
    cache."""
    if cell.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    out_path = os.path.join(cell.work, "reference.out")
    err_path = os.path.join(cell.work, "reference.err")
    for attempt in range(8):
        for path in (out_path, err_path):
            open(path, "wb").close()
        child = spawn([PY, "-m", checker, cell.config_path, cell.data_dir,
                       str(step)], err_path, env, cell.root,
                      stdout_path=out_path)
        try:
            child.wait(timeout)
        except subprocess.TimeoutExpired:
            kill_group(child.pid)
            child.wait()
            with open(err_path, errors="replace") as f:
                raise BenchFailure(f"the reference child had not ended "
                                   f"after {timeout:.0f}s and was killed:"
                                   "\n" + f.read()[-2000:])
        with open(err_path, errors="replace") as f:
            stderr = f.read()
        # a killed trainer's chips can stay busy for a while after it
        if "Device or resource busy" not in stderr:
            break
        say(f"the chip is still busy (attempt {attempt + 1}); waiting")
        time.sleep(10)
    if child.returncode != 0:
        raise BenchFailure("the reference child failed:\n" + stderr[-2000:])
    for phase in stderr.splitlines():
        if phase.startswith("[check"):  # the last says what it compiled
            say("the reference child: " + phase)
    with open(out_path) as f:
        return json.loads(f.read().strip().splitlines()[-1])


def reference_check(cell: Cell, step: int, logged_loss: float) -> dict:
    """`correct` for a trained LM: the trainer's logged loss at ``step``
    against the plain float32 reference on the same parameters and batch,
    and the program's forward pass against it token by token, both
    computed by a child that gets the chip after the trainer has ended."""
    ref = reference_child(cell, "benchmark.reference.check_lm", step,
                          cell.child_env(), 300)
    limits = cell.config["reference"]
    diff = abs(ref["loss"] - logged_loss)
    say(f"reference on {ref['platform']}: plain float32 loss "
        f"{ref['loss']:.5f}, trainer logged {logged_loss:.4f} at step "
        f"{step}: |diff| {diff:.5f} (tolerance "
        f"{limits['loss_tolerance']}); program's forward against the "
        f"plain one, token by token: rms "
        f"{ref['token_loss_rms_diff']:.5f} (tolerance "
        f"{limits['token_loss_rms_tolerance']})")
    return {"ok": diff <= limits["loss_tolerance"]
            and ref["token_loss_rms_diff"]
            <= limits["token_loss_rms_tolerance"], "diff": diff, **ref}
