"""What PR 55 changed under every `steady_ref` cell: the reference child
keeps what it compiles in a directory of its own (`cell.REF_CACHE`), so
that it compiles once a checkout and not once a run, and every child the
harness starts goes through `procs.spawn`, which ends it with `run.py`
whatever ends `run.py`. On the CPU, at the tiny configurations."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from benchmark.harness import cell as cl
from benchmark.harness import procs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ONE_DEVICE = {"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
              "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
# what a checker prints that is not a reading of the program
NOT_A_READING = ("programs",)


@pytest.mark.parametrize("tiny", ["joyai", "sdar", "trinity",
                                  "granite_hybrid", "olmoe"])
def test_a_second_run_of_the_child_compiles_nothing(tiny, tmp_path,
                                                    stand_in_cell):
    """A checkout of the test's own, so the first child starts cold: it
    compiles and keeps; the second reads every program and prints the
    same readings; a third, on shards of another seed, reads them too
    (the seed is data, never a constant of a program). Nothing lands in
    the directory the caller named for the trainer."""
    from benchmark.drivers.train_steady_ref import reference_check
    from benchmark.harness.shards import make_shards
    with open(os.path.join(ROOT, "benchmark", "tests",
                           f"tiny_{tiny}.json")) as f:
        config = json.load(f)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    trainers = tmp_path / "trainers_cache"
    # the caller's cap is the trainer's directory's, not the children's:
    # under it every entry pushes the last one out
    env = {**os.environ, **ONE_DEVICE, "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(trainers),
           "JAX_COMPILATION_CACHE_MAX_SIZE": "100000"}
    got = []
    for data, seed in (("a", 2290055100), ("a", 2290055100),
                       ("b", 2147483699)):
        make_shards(str(tmp_path / data), 1, 8, config["run"]["seq_len"],
                    config["vocab_size"], seed)
        cell = stand_in_cell(tmp_path, config, path, tmp_path / data, env,
                             root=tmp_path)
        got.append(reference_check(cell, 1, 0.0))
    cold, warm, other = (g["programs"] for g in got)
    assert cold["compiled"] > 0 and cold["read"] == 0
    assert warm == {"compiled": 0, "read": cold["compiled"]}
    assert other == warm
    for key in got[0]:
        if key not in NOT_A_READING:
            assert got[0][key] == got[1][key] or (
                got[0][key] != got[0][key] and got[1][key] != got[1][key])
    assert got[2]["reference_loss" if "reference_loss" in got[2]
                  else "loss"] != got[0].get("reference_loss",
                                             got[0]["loss"])
    assert len(os.listdir(tmp_path / cl.REF_CACHE)) == cold["compiled"]
    assert not trainers.exists()


@pytest.mark.parametrize("tiny, checker", [
    ("joyai", "check_joyai"), ("trinity", "check_trinity_mini"),
    ("sdar", "check_sdar"), ("granite_hybrid", "check_granite_hybrid"),
    ("olmoe", "check_olmoe")])
def test_the_short_draw_is_the_draw_at_the_cells_length(tiny, checker):
    """`trainer_draw.seeded_variables` traces the initialiser on
    `DRAW_TOKENS` tokens; the draw it took the place of traced it on the
    configuration's `seq_len`. Every leaf of one is the other's, bit for
    bit (the same on the chip at the cells' sizes: PERF.md section 6,
    PR 55)."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from benchmark.reference import trainer_draw
    from edl_tpu.models.transformer import Transformer
    with open(os.path.join(ROOT, "benchmark", "tests",
                           f"tiny_{tiny}.json")) as f:
        config = json.load(f)
    run = config["run"]
    assert run["seq_len"] > trainer_draw.DRAW_TOKENS
    program = Transformer(importlib.import_module(
        "benchmark.reference." + checker).program_config(config))
    at_length = jax.jit(lambda: meta.unbox(program.init(
        jax.random.PRNGKey(run["trainer_seed"]),
        jnp.zeros((1, run["seq_len"]), jnp.int32), train=False)))()
    short = trainer_draw.seeded_variables(program, config)
    old, names = jax.tree.flatten(at_length)
    new, new_names = jax.tree.flatten(short)
    assert names == new_names and len(old) > 10
    for a, b in zip(old, new):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))
    assert trainer_draw.seeded_params(program, config).keys() \
        == at_length["params"].keys()


def _checker(tmp_path, name: str, body: str):
    """A stand-in cell whose checker is the module ``name`` with
    ``body``."""
    from types import SimpleNamespace
    (tmp_path / f"{name}.py").write_text(body)
    env = {**os.environ, "PYTHONPATH": str(tmp_path)}
    return SimpleNamespace(root=str(tmp_path), work=str(tmp_path),
                           config_path="c", data_dir="d", rehearse=True), env


def test_the_last_line_of_the_child_is_the_result(tmp_path, capfd):
    cell, env = _checker(tmp_path, "fine", (
        "import json, sys\n"
        "print('[check +   0.1s] done: compiled 3 programs and read 4 "
        "from x', file=sys.stderr)\n"
        "print('noise')\n"
        "print(json.dumps({'loss': 1.5, 'argv': sys.argv[1:]}))\n"))
    got = cl.reference_child(cell, "fine", 7, env, 30)
    assert got == {"loss": 1.5, "argv": ["c", "d", "7"]}
    assert "compiled 3 programs and read 4" in capfd.readouterr().err


def test_a_child_that_fails_or_outlasts_its_time_fails_the_run(tmp_path):
    cell, env = _checker(tmp_path, "broken",
                         "import sys\nsys.exit('the cause')\n")
    with pytest.raises(procs.BenchFailure, match="the cause"):
        cl.reference_child(cell, "broken", 1, env, 30)
    cell, env = _checker(tmp_path, "slow", (
        "import sys, time\nprint('begun', file=sys.stderr, flush=True)\n"
        "time.sleep(60)\n"))
    before = len(procs._procs)
    with pytest.raises(procs.BenchFailure, match="(?s)not ended.*begun"):
        cl.reference_child(cell, "slow", 1, env, 3)
    assert procs._procs[before].poll() is not None  # killed and reaped


def _alive_with(marker: str) -> list[tuple[int, str]]:
    """(pid, command line) of every live process that carries ``marker``
    in its environment; a zombie has none left to read."""
    found = []
    for d in os.listdir("/proc"):
        if not d.isdigit() or int(d) == os.getpid():
            continue
        try:
            with open(f"/proc/{d}/environ", "rb") as f:
                if marker.encode() not in f.read():
                    continue
            with open(f"/proc/{d}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue
        if procs.alive(int(d)):
            found.append((int(d), cmd))
    return found


def _none_left(marker: str, within: float) -> list:
    deadline = time.monotonic() + within
    while time.monotonic() < deadline and _alive_with(marker):
        time.sleep(0.1)
    left = _alive_with(marker)
    for pid, _ in left:
        os.kill(pid, signal.SIGKILL)
    return left


def test_a_child_of_spawn_ends_with_the_process_that_started_it(tmp_path):
    """`stop_all` ends what `spawn` started, but a `run.py` that is
    killed says nothing to anybody: the kernel ends its children (the
    request every child of `spawn` makes before its exec)."""
    marker = f"EDL_BENCH_TEST_MARK_{os.getpid()}_spawn"
    stand_in = subprocess.Popen(
        [sys.executable, "-c", (
            "import os, sys, time; sys.path.insert(0, %r); "
            "from benchmark.harness import procs; "
            "procs.spawn([sys.executable, '-c', 'import time; "
            "time.sleep(60)'], %r, dict(os.environ), %r); "
            "print('started', flush=True); time.sleep(60)"
        ) % (ROOT, str(tmp_path / "log"), str(tmp_path))],
        env={**os.environ, marker: "1"}, stdout=subprocess.PIPE, text=True)
    assert stand_in.stdout.readline().strip() == "started"
    assert len(_alive_with(marker)) == 2
    stand_in.kill()
    stand_in.wait()
    assert _none_left(marker, 5) == []


def test_a_run_killed_while_its_reference_child_runs_leaves_nothing():
    """The check ends a `run.py` that is over its time with SIGKILL. The
    trainer, the launcher and the store are gone by then (the driver
    ends them before the reference starts); the child that holds the
    chip must not outlive the run."""
    marker = f"EDL_BENCH_TEST_MARK_{os.getpid()}_run"
    run = subprocess.Popen(
        [sys.executable, "benchmark/run.py", "--workload",
         "joyai_d5e16v8.steady", "--seed", "2147483656", "--seconds", "6",
         "--trace", "0", "--rehearse", "benchmark/tests/tiny_joyai.json"],
        cwd=ROOT, env={**os.environ, **ONE_DEVICE, marker: "1"},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 600
        while not any("benchmark.reference.check_joyai" in cmd
                      for _, cmd in _alive_with(marker)):
            assert run.poll() is None, "the run ended before its child"
            assert time.monotonic() < deadline
            time.sleep(0.2)
        time.sleep(2)  # into its imports
    finally:
        run.kill()
        run.wait()
    assert _none_left(marker, 5) == []
