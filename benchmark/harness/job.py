"""One elastic training job as its users start it: a coordination store,
one launcher pod, and `lm_train` under it (copied from `chip_smoke.py`'s
`Job`, which PERF.md §6 found sound). The parent that builds it never
touches JAX: the trainer is the one process that holds the chip(s).
"""

from __future__ import annotations

import glob
import os
import signal

from benchmark.harness import logs
from benchmark.harness.procs import (PY, BenchFailure, LogTail, Refused,
                                     alive, connects, free_port, kill_group,
                                     pids_matching, say, spawn, wait_for,
                                     wait_gone)


def lm_args(config: dict, data_dir: str) -> list[str]:
    """`lm_train`'s flags for a configuration file. The trainer's own
    seed (parameter draw, loader order) is the file's: `lm_train` folds
    it into its init program as a constant, so every new value compiles
    that program again (27 s at d8, PERF.md §6). The run's `--seed`
    makes the shards."""
    run = config["run"]
    return ["--vocab", str(config["vocab_size"]),
            "--d-model", str(config["n_embd"]),
            "--n-heads", str(config["n_head"]),
            "--n-layers", str(config["n_layer"]),
            "--d-ff", str(config["n_inner"]),
            "--seq-len", str(run["seq_len"]),
            "--batch-size", str(run["global_batch"]),
            "--mesh", run["mesh"], "--lr", str(run["lr"]),
            "--warmup-steps", str(run["warmup_steps"]),
            "--epochs", str(run["epochs"]),
            *run["flags"], "--seed", str(run["trainer_seed"]),
            "--data-dir", data_dir]


class Job:
    def __init__(self, root: str, work: str, trainer_args: list[str],
                 env: dict, chips: int):
        # what only this job's trainers have on their command line
        self.host = os.path.join(root, "benchmark", "hosts",
                                 "lm_train_host.py")
        self.needle = trainer_args[trainer_args.index("--data-dir") + 1]
        self.chips = chips
        self.mem_dir = os.path.join(work, "mem")
        os.makedirs(self.mem_dir)
        log_dir = os.path.join(work, "log")
        self.launcher_log = os.path.join(work, "launcher.log")
        env = {**env, "EDL_BENCH_MEM_DIR": self.mem_dir}
        port = free_port()
        self.store = spawn([PY, "-m", "edl_tpu.coord.server", "--host",
                            "127.0.0.1", "--port", str(port)],
                           os.path.join(work, "store.log"), env, root)
        wait_for(lambda: connects(port), 30, "the store's port",
                 proc=self.store, poll=0.05)
        self.launcher = spawn(
            [PY, "-m", "edl_tpu.collective.launch", "--store",
             f"127.0.0.1:{port}", "--job-id", "bench", "--nodes-range",
             "1:1", "--log-dir", log_dir, "--", PY, self.host,
             *trainer_args],
            self.launcher_log, env, root)
        self.launcher_tail = LogTail(self.launcher_log)
        self.worker_tail = LogTail(os.path.join(log_dir, "workerlog.0"))
        self.trainer_pids: list[int] = []

    # -- generations ---------------------------------------------------------

    def next_trainer(self, timeout: float = 120) -> int:
        """pid of the generation the launcher starts next."""
        def probe():
            pids = [p for _, p in
                    logs.started_trainers(self.launcher_tail.lines)]
            return pids[len(self.trainer_pids):]
        pid = wait_for(probe, timeout, "the launcher to start a trainer",
                       proc=self.launcher)[0]
        self.trainer_pids.append(pid)
        return pid

    def lines(self, pid: int) -> logs.Lines:
        return logs.of_pid(self.worker_tail.lines, pid)

    def wait_line(self, pid: int, find, timeout: float, what: str):
        """Until ``find(lines of pid)`` is truthy; fails when the trainer
        dies first."""
        def probe():
            got = find(self.lines(pid))
            if got:
                return got
            if not alive(pid):
                raise BenchFailure(f"trainer {pid} died waiting for {what}:"
                                   f"\n{self.worker_tail.text()}")
        return wait_for(probe, timeout, what, proc=self.launcher)

    def device(self, pid: int, rehearse: bool) -> dict:
        """The device as the trainer logged it; a run that is not on the
        TPU, or on fewer chips than the cell asks for, is refused here."""
        dev = self.wait_line(pid, logs.device, 180, "the device line")
        say(f"trainer {pid}: {dev}")
        if not rehearse and (dev["platform"] != "tpu"
                             or dev["count"] < self.chips):
            raise Refused(f"no TPU: refused (the trainer found "
                          f"{dev['platform']} x{dev['count']}, the cell "
                          f"needs tpu x{self.chips})")
        return dev

    def live_trainers(self) -> list[int]:
        # the launcher's command line holds the trainer's too
        return pids_matching(self.host, self.needle,
                             without="collective.launch")

    def memory_peak_bytes(self) -> int:
        """Fullest chip's peak over every generation of the trainer."""
        peaks = [0]
        for path in glob.glob(os.path.join(self.mem_dir, "mem.[0-9]*")):
            if path.endswith(".tmp"):
                continue
            with open(path) as f:
                peaks.append(int(f.read() or 0))
        return max(peaks)

    def kill(self, graceful: bool = False) -> None:
        """Launcher first, so that it respawns nothing; then whatever
        trainer lives; then the store. Waits until all have ended.
        ``graceful`` gives the trainer SIGTERM and 30 s first: its loop
        leaves at a step boundary and the runtime lets go of the chips
        itself. A four-chip trainer killed in the middle of a step left
        them busy for the next process (my chip run, PR 22). Where a
        checkpoint directory is set SIGTERM also seals the state, which
        takes longer than the run is worth."""
        kill_group(self.launcher.pid)
        trainers = sorted({*self.live_trainers(), *self.trainer_pids})
        if graceful:
            for pid in trainers:
                kill_group(pid, signal.SIGTERM)
            try:
                wait_gone(trainers, 30)
            except BenchFailure:
                say("the trainer did not leave on SIGTERM; killing it")
        pids = [self.launcher.pid, *trainers, self.store.pid]
        for pid in pids:
            kill_group(pid)
        wait_gone(pids)
        self.launcher_tail.close()
        self.worker_tail.close()
