"""Each driver end to end on the CPU at a tiny size: the control flow of
a run (store, launcher, trainer, window, kill, respawn, reference) is
walked, and the run must end in "no TPU: refused", never in a number.
About a minute together."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(*args, devices=1):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS":
           f"--xla_force_host_platform_device_count={devices}"}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell, seconds, trace, config, devices", [
    ("lm_d8.steady", 6, 1, "tiny_lm.json", 1),
    ("lm_d8.save_kill_resume", 15, 0, "tiny_lm.json", 1),
    # the four-chip job on four virtual CPU devices
    ("lm_full.fsdp4_steady", 6, 0, "tiny_lm_fsdp.json", 4),
])
def test_cpu_rehearsal_is_refused(cell, seconds, trace, config, devices):
    out = run("--workload", cell, "--seed", "7", "--seconds", str(seconds),
              "--trace", str(trace), "--rehearse",
              "benchmark/tests/" + config, devices=devices)
    assert out.returncode == 3, out.stderr[-3000:]
    assert "no TPU: refused" in out.stderr
    assert out.stdout.strip() == ""
    assert "correct=True" in out.stderr, out.stderr[-3000:]

