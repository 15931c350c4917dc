"""The resume of the save cell (`drivers/train_kill_resume.kill_and_resume`)
against a scripted job, and what a failed run leaves (`harness/failed.py`
through `run.main`). No JAX, no child process: the script's launcher and
workers write a tick a poll."""

import json
import os
import shutil
import time

import pytest

from benchmark import run as bench_run
from benchmark.drivers import train_kill_resume as tkr
from benchmark.harness import logs
from benchmark.harness.procs import BenchFailure

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KILLED = 1445


def started(pid):
    return ("2026-09-26 19:54:54,822 INFO edl_tpu.collective.process [1428] "
            f"started trainer rank=0 pid={pid} log=/w/job/log/workerlog.0")


def worker(pid, text):
    return f"2026-09-26 19:55:13,270 INFO edl_tpu.train.loop [{pid}] {text}"


def resumed(pid, step=61):
    return [worker(pid, f"restored checkpoint /w/ckpt/ckpt-1 (epoch=-1 "
                        f"step={step - 1}) in 9.115s"),
            worker(pid, f"first-step-complete global_step={step} "
                        "restore_s=9.115"),
            worker(pid, "first-step wall (trace+compile+run) 4.650s, "
                        "persistent compile cache {'hits': 13, 'misses': 0}")]


LEASE = ("2026-09-26 19:55:20,000 ERROR edl_tpu.collective.register [1428] "
         "pod p lost its rank lease")


class Tail:
    def __init__(self):
        self.lines = []

    def text(self, n=40):
        return "\n".join(ln for _, ln in self.lines[-n:])


class ScriptedJob:
    """What `kill_and_resume` takes of a `Job`. Every poll (a call of
    `live_trainers`) plays the script's next tick: (launcher's lines,
    workers' lines, the trainers alive from then on)."""

    def __init__(self, ticks):
        self.ticks = list(ticks)
        self.trainer_pids = [KILLED]
        self.launcher_tail, self.worker_tail = Tail(), Tail()
        self.launcher_tail.lines.append((time.monotonic(), started(KILLED)))
        self.launcher = self  # never exits
        self.live = []

    def poll(self):
        return None

    def live_trainers(self):
        if self.ticks:
            launcher, workers, self.live = self.ticks.pop(0)
            now = time.monotonic()
            self.launcher_tail.lines += [(now, ln) for ln in launcher]
            self.worker_tail.lines += [(now, ln) for ln in workers]
        time.sleep(0.002)  # the stamps of two ticks differ
        return list(self.live)

    def lines(self, pid):
        return logs.of_pid(self.worker_tail.lines, pid)

    def next_trainer(self, timeout=120):
        pid = [p for _, p in logs.started_trainers(
            self.launcher_tail.lines)][len(self.trainer_pids)]
        self.trainer_pids.append(pid)
        return pid


@pytest.fixture
def play(monkeypatch):
    def play(ticks, deadline_s=5.0):
        job = ScriptedJob(ticks)
        monkeypatch.setattr(tkr, "POLL", 0.001)
        monkeypatch.setattr(tkr, "kill_group", lambda pid: None)
        t_kill = time.monotonic()
        out = tkr.kill_and_resume(job, KILLED, deadline_s)
        return job, t_kill, out
    return play


def stamp(tail, needle):
    return next(t for t, ln in tail.lines if needle in ln)


def test_the_plain_resume_reads_as_it_did(play):
    job, t_kill, out = play([
        ([], [], []),
        ([started(1692)], [], [1692]),
        ([], [worker(1692, "persistent XLA compilation cache at /c")],
         [1692]),
        ([], resumed(1692), [1692])])
    assert out["pid"] == 1692 and job.trainer_pids == [KILLED, 1692]
    assert out["generations_lost"] == 0 and out["lost"] == []
    assert out["most_live"] == 1
    assert out["resumed"]["global_step"] == 61
    assert out["restored"] == [(60, 9.115)]
    assert out["first_step"]["first_step_s"] == 4.65
    first_line = stamp(job.worker_tail, "compilation cache at")
    done = stamp(job.worker_tail, "first-step-complete")
    assert out["respawn_s"] == pytest.approx(first_line - t_kill, abs=0.01)
    assert out["resume_s"] == pytest.approx(done - t_kill, abs=0.01)
    assert out["respawn_s"] < out["resume_s"]


def test_a_lost_generation_is_said_and_counted_and_the_next_resumes(
        play, capsys):
    job, t_kill, out = play([
        ([started(1692)], [], [1692]),
        ([], [worker(1692, "persistent XLA compilation cache at /c")],
         [1692]),
        ([LEASE], [], []),              # the launcher ended it
        ([], [], []), ([], [], []),
        ([started(1801)], [], [1801]),
        ([], [worker(1801, "persistent XLA compilation cache at /c")],
         [1801]),
        ([], resumed(1801), [1801])])
    assert out["pid"] == 1801
    assert job.trainer_pids == [KILLED, 1692, 1801]
    assert out["generations_lost"] == 1
    assert [g["pid"] for g in out["lost"]] == [1692]
    assert out["most_live"] == 1
    # the launcher's part ends on the lost one's first line; the resume
    # spans both generations
    assert out["respawn_s"] == pytest.approx(
        stamp(job.worker_tail, "[1692]") - t_kill, abs=0.01)
    assert out["resume_s"] == pytest.approx(
        stamp(job.worker_tail, "first-step-complete") - t_kill, abs=0.01)
    assert out["resume_s"] > stamp(job.launcher_tail, "pid=1801") - t_kill
    said = [ln for ln in capsys.readouterr().err.splitlines()
            if "generation lost" in ln]
    assert len(said) == 1
    assert "trainer 1692" in said[0] and "lost its rank lease" in said[0]
    assert "pid=1801" not in said[0]


def test_three_lost_in_a_row_fail_the_run(play):
    ticks = []
    for pid in (1692, 1801, 1910):
        ticks += [([started(pid)], [worker(pid, "imports done")], [pid]),
                  ([LEASE], [], []), ([], [], []), ([], [], [])]
    with pytest.raises(BenchFailure) as e:
        play(ticks + [([started(2020)], resumed(2020), [2020])])
    text = str(e.value)
    assert "the resumed first step" in text
    assert "generations lost since: 3" in text
    for pid in (1692, 1801, 1910):
        assert f"trainer {pid} ended before its first step" in text
    assert "[1910] imports done" in text  # the workers' last lines, as ever


def test_a_resume_that_never_comes_times_out_as_it_did(play):
    with pytest.raises(BenchFailure) as e:
        play([([started(1692)], [worker(1692, "imports done")], [1692])],
             deadline_s=0.2)
    assert "timed out" in str(e.value)
    assert "waiting for the resumed first step" in str(e.value)
    assert "generations lost since: 0" in str(e.value)


def test_two_trainers_alive_at_once_are_seen_and_not_correct(play):
    def checks(out):
        return tkr.checks_of(out, [60], [(65, 11.25, 11.25)], 0, True)
    _, _, out = play([
        ([started(1692)], [], [1692]),
        ([], [], [1692, 1700]),
        ([], resumed(1692), [1692])])
    assert out["most_live"] == 2
    assert checks(out)["one_trainer_at_a_time"] is False
    assert [k for k, ok in checks(out).items() if not ok] == [
        "one_trainer_at_a_time"]
    _, _, plain = play([([started(1692)], resumed(1692), [1692])])
    assert all(checks(plain).values())
    # a resume from a step that was not sealed is not one
    assert not tkr.checks_of(plain, [120], [(65, 1.0, 1.0)], 0,
                             True)["follows_a_seal"]


# -- what a failed run leaves -------------------------------------------------

@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A directory `run.main` takes for its checkout: the benchmark's data
    files and an `edl_tpu/` to find."""
    (tmp_path / "edl_tpu").mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in ("configs", "traffic"):
        shutil.copytree(os.path.join(ROOT, "benchmark", d),
                        tmp_path / "benchmark" / d)
    shutil.copy(os.path.join(ROOT, "benchmark", "peaks.json"),
                tmp_path / "benchmark")
    monkeypatch.setattr(bench_run, "ROOT", str(tmp_path))
    return tmp_path


def write_a_run(cell):
    for rel, size in (("job/launcher.log", 3000), ("job/store.log", 100),
                      ("job/log/workerlog.0", 5000),
                      ("job/tpu_logs/tpu_driver.INFO", 700),
                      ("trace/spans-1692.jsonl", 900),
                      ("trace/plugins/profile/t/host.xplane.pb", 4000),
                      ("data/shard-0.npy", 4000), ("ckpt/ckpt-1/a", 4000)):
        path = os.path.join(cell.work, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write("".join(f"{rel} line {i}\n" for i in range(size // 20)))


ARGS = ["--workload", "lm_d8.save_kill_resume", "--seconds", "1"]


def test_a_failed_run_leaves_its_logs_and_not_its_data(
        checkout, monkeypatch, capsys):
    def fails(cell):
        write_a_run(cell)
        raise BenchFailure("timed out after 90s waiting for the resumed "
                           "first step")
    monkeypatch.setattr(tkr, "run", fails)
    assert bench_run.main([*ARGS, "--seed", "2147483655"]) == 1
    kept = checkout / ".bench_failed" / "lm_d8.save_kill_resume.2147483655"
    found = sorted(os.path.relpath(os.path.join(d, n), kept)
                   for d, _, names in os.walk(kept) for n in names)
    assert found == ["job/launcher.log", "job/log/workerlog.0",
                     "job/store.log", "job/tpu_logs/tpu_driver.INFO",
                     "trace/spans-1692.jsonl"]
    assert not os.path.exists(checkout / ".bench_work"
                              / "lm_d8.save_kill_resume")
    captured = capsys.readouterr()
    err = captured.err.rstrip().splitlines()
    assert captured.out == ""
    assert err[-1].endswith(str(kept))
    assert err[-2] == "job/launcher.log line 149"
    assert "failed: timed out after 90s" in captured.err


def test_the_newest_four_failed_runs_stay_and_a_run_is_capped(
        checkout, monkeypatch):
    from benchmark.harness import failed

    def fails(cell):
        write_a_run(cell)
        raise BenchFailure("no")
    monkeypatch.setattr(tkr, "run", fails)
    monkeypatch.setattr(failed, "MAX_BYTES", 6000)
    for seed in range(1, 7):
        assert bench_run.main([*ARGS, "--seed", str(seed)]) == 1
        time.sleep(0.01)
    base = checkout / ".bench_failed"
    assert sorted(os.listdir(base)) == [
        f"lm_d8.save_kill_resume.{s}" for s in (3, 4, 5, 6)]
    sizes = {os.path.relpath(os.path.join(d, n), base): os.path.getsize(
        os.path.join(d, n)) for d, _, names in os.walk(base) for n in names}
    run6 = {k: v for k, v in sizes.items() if k.startswith(
        "lm_d8.save_kill_resume.6/")}
    assert sum(run6.values()) <= 6000
    # the logs that name a cause go first, and a file that does not fit
    # keeps its end
    worker = base / "lm_d8.save_kill_resume.6" / "job/log/workerlog.0"
    assert worker.read_text().endswith("line 249\n")
    assert len(worker.read_text()) < 5000


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_a_run_that_returns_leaves_nothing(checkout, monkeypatch, capsys,
                                           platform):
    def returns(cell):
        write_a_run(cell)
        return {"correct": True, "attempted": 4, "failed": 0,
                "device": {"platform": platform, "kind": "TPU v5 lite",
                           "count": 1, "memory_peak_bytes": 1},
                "values": {"setup_s": 1.0, "saving_tokens_per_s": 2.0},
                "evidence": {}}
    monkeypatch.setattr(tkr, "run", returns)
    rc = bench_run.main([*ARGS, "--seed", "5"])
    out = capsys.readouterr().out
    if platform == "tpu":
        assert rc == 0 and json.loads(out.splitlines()[-1])["correct"]
    else:
        assert rc == 3 and out == ""
    assert not os.path.exists(checkout / ".bench_failed")
    assert not os.path.exists(checkout / ".bench_work"
                              / "lm_d8.save_kill_resume")
