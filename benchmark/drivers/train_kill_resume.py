"""The same job with periodic async sharded checkpoints, killed once and
resumed, then measured while it goes on saving.

Set-up holds the kill: the first generation runs until its first
checkpoint is sealed, gets SIGKILL on the next step line (no write is in
flight: the same state in every run), and the launcher starts the
generation that restores that checkpoint. So the time from the kill to
the resumed first step is inside `setup_s`, the one metric the contract
judges by its median alone: `resume_s` spread by 4 to 9 % between runs
(my chip runs, PR 22), which no bound of at most 0.1 may cover, and
where the resume was measured after the window nothing bounded it.

The window opens on the resumed generation's first save boundary (a
step line that follows a save) and closes on the first save boundary at
least `--seconds` later, so it holds whole save periods only and every
save's stall is inside the throughput."""

from __future__ import annotations

import time

from benchmark.harness import cell as cl
from benchmark.harness import logs
from benchmark.harness.procs import BenchFailure, kill_group, say, wait_for


ATTEMPTS = 3  # generations after the kill, as `cell.start_training` allows
POLL = 0.05


def _lost_line(job, gen: dict) -> str:
    """One line for a generation that ended before its first step: its
    pid, when the launcher started the next, and what the launcher wrote
    between the two `started trainer` lines."""
    between = logs.after_start(job.launcher_tail.lines, gen["pid"])
    return (f"generation lost after the kill: trainer {gen['pid']} ended "
            "before its first step, the launcher started the next "
            f"{gen['replaced_after_s']:.1f}s after it; the launcher "
            "meanwhile: " + (" | ".join(between) or "(no line)"))


def kill_and_resume(job, pid: int, deadline_s: float) -> dict:
    """SIGKILL trainer ``pid`` now; wait for the first generation the
    launcher starts after it that finishes a first step. A generation
    that the launcher replaces before that is lost, as one is at the
    start of a job (`cell.start_training`): it is said, counted, and the
    next is waited for, `ATTEMPTS` generations in all, the first with
    ``deadline_s`` from the kill and each later one from its own `started
    trainer` line. Stamps are the log tail's; the polls here only watch."""
    known = len(job.trainer_pids)  # of the launcher's trainers, from 0
    t_kill = time.monotonic()
    kill_group(pid)
    say(f"SIGKILL -> trainer {pid}")
    most_live = 0
    lost: list[dict] = []
    gens: list[tuple[float, int]] = []  # this attempt's trainer, the next

    def failure(what) -> BenchFailure:
        return BenchFailure(
            f"{what} ({time.monotonic() - t_kill:.0f}s after the kill, "
            f"generations lost since: {len(lost)})\n" + "".join(
                _lost_line(job, g) + "\n" for g in lost)
            + job.worker_tail.text())

    def first_step_or_next():
        nonlocal most_live, gens
        most_live = max(most_live, len(job.live_trainers()))
        gens = logs.started_trainers(
            job.launcher_tail.lines)[known + len(lost):]
        return gens and (logs.first_step_complete(job.lines(gens[0][1]))
                         or len(gens) > 1)

    t_from = t_kill
    while True:
        try:
            done = wait_for(first_step_or_next,
                            t_from + deadline_s - time.monotonic(),
                            "the resumed first step", proc=job.launcher,
                            poll=POLL)
        except BenchFailure as e:
            raise failure(e) from None
        (t_started, new), *later = gens
        if done is not True:
            break
        t_from = later[0][0]
        lost.append({"pid": new, "replaced_after_s": t_from - t_started})
        say(_lost_line(job, lost[-1]))
        if len(lost) == ATTEMPTS:
            raise failure(f"the resumed first step: {ATTEMPTS} trainers in "
                          "a row ended before it")
    while len(job.trainer_pids) <= known + len(lost):
        job.next_trainer(1)  # `job.trainer_pids` holds every generation
    lines = job.lines(new)
    # the launcher's part: the first trainer it started, lost or not
    first_lines = [ls for ls in (job.lines(p) for p in
                                 job.trainer_pids[known:]) if ls][0]
    out = {"pid": new, "resumed": done, "resume_s": done["t"] - t_kill,
           "respawn_s": first_lines[0][0] - t_kill, "most_live": most_live,
           "generations_lost": len(lost), "lost": lost,
           "restored": logs.restored(lines),
           "first_step": logs.first_step_wall(lines) or {}}
    say(f"resume: {out['resume_s']:.2f}s (first line of a trainer "
        f"{out['respawn_s']:.2f}s after the kill, generations lost "
        f"{len(lost)}, trainer {new}: restore {done['restore_s']}s, first "
        f"step {out['first_step'].get('first_step_s')}s)")
    return out


def checks_of(resume: dict, sealed1: list[int], replayed: list[tuple],
              bad: int, reference_ok: bool) -> dict:
    """What `correct` holds the cell to, all of the generation that
    resumed but the one that is of the whole wait."""
    return {
        # the new generation's first step follows a step the old sealed
        "follows_a_seal": bool(resume["restored"])
        and resume["restored"][0][0] in sealed1
        and resume["resumed"]["global_step"] == resume["restored"][0][0] + 1,
        "replay_equal": bool(replayed) and all(
            abs(a - b) < 5e-5 for _, a, b in replayed),
        # over the whole wait, lost generations included: the launcher
        # has ended one trainer before it starts the next
        "one_trainer_at_a_time": resume["most_live"] <= 1,
        "finite": bad == 0, "reference": reference_ok}


def run(cell: cl.Cell) -> dict:
    tr = cell.traffic
    every = tr["ckpt_steps"]
    job, g1, device = cl.start_training(cell, [
        "--ckpt-dir", f"{cell.work}/ckpt", "--ckpt-steps", str(every)])
    try:
        job.wait_line(g1, logs.first_step_complete, 900, "the first step")
        t_sealed = job.wait_line(g1, logs.sealed, 300,
                                 "the first sealed checkpoint")[0][0]
        job.wait_line(g1, lambda ls: [s for s in logs.steps(ls)
                                      if s[0] > t_sealed], 60,
                      "a step line after the seal")
        resume = kill_and_resume(job, g1, tr["resume_deadline_s"])
        steps1 = logs.steps(job.lines(g1))
        sealed1 = [n for _, n in logs.sealed(job.lines(g1))]
        g2 = resume["pid"]

        def boundaries(ls):  # step lines that follow a save
            return [s for s in logs.steps(ls) if s[1] % every == 0]
        t_start = job.wait_line(g2, boundaries, 180,
                                "a save boundary")[0][0]
        setup_s = t_start - cell.t0
        say(f"window opens (set-up {setup_s:.2f}s) on the resumed "
            "generation's first save boundary")
        periods = job.wait_line(
            g2, lambda ls: [b for b in boundaries(ls)
                            if b[0] >= t_start + cell.seconds]
            and boundaries(ls), cell.seconds + 180,
            "the save boundary that closes the window")
        t_end = periods[-1][0]
        profiled = None
        if cell.trace:
            written = job.wait_line(g2, logs.trace_written, 120,
                                    "the profiler to write its trace")
            profiled = (tr["profile"]["start_step"], written)
        steps2 = [s for s in logs.steps(job.lines(g2)) if s[0] <= t_end]
        peak = job.memory_peak_bytes()
    finally:
        job.kill()
    say(f"{len(periods) - 1} whole save periods in {t_end - t_start:.2f}s: "
        f"{[round(b[0] - a[0], 2) for a, b in zip(periods, periods[1:])]}")
    before = {n: v for _, n, v in steps1}
    replayed = [(n, v, before[n]) for _, n, v in steps2 if n in before]
    bad = cl.bad_steps(steps1, 1) + cl.bad_steps(steps2, 1)
    ref = cl.reference_check(cell, steps1[0][1], steps1[0][2])
    checks = checks_of(resume, sealed1, replayed, bad, ref["ok"])
    say(f"sealed before the kill {sealed1}; restored {resume['restored']}; "
        f"replayed {replayed}; checks {checks}")
    return {
        "correct": all(checks.values()),
        # the saves inside the window, and the one kill
        "attempted": len(periods), "failed": bad,
        "device": {**device, "memory_peak_bytes": peak},
        "values": {tr["throughput_metric"]: cl.rate_over(
            [(periods[0], periods[-1])], cell.tokens_per_step),
            "setup_s": setup_s},
        "evidence": {
            "quiet_windows": cl.windows(
                [s for s in steps2 if s[0] >= t_start], profiled),
            "resume": resume, "newest_generation": resume["first_step"]},
    }
