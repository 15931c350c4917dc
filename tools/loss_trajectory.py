"""The first logged losses of a benchmark configuration's trainer, one
checkout beside another on one seed (this repo, and a parent commit
unpacked with `git archive` into a directory `.gitignore` lists).

Starts `lm_train` from each checkout in turn with the configuration's
own flags on the same seeded shards, logging every step, ends it after
``steps`` step lines, and prints one JSON line: every checkout's losses
and the largest difference from the first's. The parent process stays
off JAX: a chip belongs to one process at a time. The same checkout
given twice says what two runs of one program differ by.

    chiprun -- python tools/loss_trajectory.py benchmark/configs/<file>.json <seed> <steps> . _checkout/parent
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import logs  # noqa: E402
from benchmark.harness.job import lm_args  # noqa: E402
from benchmark.harness.shards import make_shards  # noqa: E402


def losses(checkout: str, args: list[str], steps: int, log_path: str,
           timeout: float = 900) -> list[float]:
    checkout = os.path.abspath(checkout)
    env = {**os.environ, "PYTHONPATH": checkout, "EDL_TPU_LOG_EVERY": "1"}
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(checkout, ".jax_cache"))
    with open(log_path, "w") as out:
        proc = subprocess.Popen(
            [sys.executable, "-m", "edl_tpu.examples.lm_train", *args],
            cwd=checkout, env=env, stdout=out, stderr=subprocess.STDOUT)
        try:
            deadline = time.monotonic() + timeout
            while True:
                with open(log_path) as f:
                    got = logs.steps([(0.0, ln) for ln in f])
                if len(got) >= steps:
                    return [loss for _, _, loss in got[:steps]]
                if proc.poll() is not None or time.monotonic() > deadline:
                    with open(log_path) as f:
                        raise RuntimeError(
                            f"{checkout}: {len(got)} of {steps} step lines"
                            f"\n{f.read()[-3000:]}")
                time.sleep(0.5)
        finally:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()


def main(argv: list[str]) -> int:
    config_path, seed, steps, checkouts = (argv[0], int(argv[1]),
                                           int(argv[2]), argv[3:])
    with open(config_path) as f:
        config = json.load(f)
    work = os.path.join(ROOT, ".bench_work", f"trajectory-{os.getpid()}")
    data_dir = os.path.join(work, "data")
    make_shards(data_dir, 4, 2048, config["run"]["seq_len"],
                config["vocab_size"], seed)
    args = lm_args(config, data_dir)
    runs = [losses(c, args, steps, os.path.join(work, f"run{i}.log"))
            for i, c in enumerate(checkouts)]
    line = {"config": os.path.basename(config_path), "seed": seed,
            "steps": steps, "checkouts": checkouts, "losses": runs,
            "max_abs_diff_from_first": [
                max(abs(a - b) for a, b in zip(runs[0], r)) for r in runs]}
    print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "loss_trajectory.jsonl"),
              "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
