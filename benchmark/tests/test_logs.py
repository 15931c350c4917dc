"""The log-line parsers on lines the program wrote on the chip (PR 22's
first run of the kill-and-resume cell: trainer 1445, killed, then
1692). Stamps here are line numbers: the parsers only carry them."""

import os

from benchmark.harness import logs
from benchmark.harness.cell import rate_over, windows
from benchmark.layer_metrics import (ckpt_restore_s, ckpt_stall_ms,
                                     compile_cache_misses, first_step_s,
                                     loop_stall_share, respawn_s, resume_s)

HERE = os.path.dirname(os.path.abspath(__file__))


def lines(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return [(float(i), ln.rstrip("\n")) for i, ln in enumerate(f)]


WORKER = lines("worker_kill_resume.log")
LAUNCHER = lines("launcher_kill_resume.log")


def test_generations_are_read_apart():
    assert [p for _, p in logs.started_trainers(LAUNCHER)] == [1445, 1692]
    g1, g2 = logs.of_pid(WORKER, 1445), logs.of_pid(WORKER, 1692)
    assert [s for _, s, _ in logs.steps(g1)] == list(range(5, 60, 5))
    assert [(s, v) for _, s, v in logs.steps(g2)] == [(35, 11.3135),
                                                      (40, 11.3075)]


def test_device_and_first_step():
    g1, g2 = logs.of_pid(WORKER, 1445), logs.of_pid(WORKER, 1692)
    assert logs.device(g1) == {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1, "attention": "flash"}
    first = logs.first_step_complete(g1)
    assert (first["global_step"], first["restore_s"]) == (1, None)
    resumed = logs.first_step_complete(g2)
    assert (resumed["global_step"], resumed["restore_s"]) == (31, 9.115)
    assert logs.first_step_wall(g2) == {"first_step_s": 4.65, "hits": 13,
                                        "misses": 0}


def test_checkpoints():
    g1, g2 = logs.of_pid(WORKER, 1445), logs.of_pid(WORKER, 1692)
    assert [s for _, s in logs.sealed(g1)] == [15, 30]
    assert logs.restored(g2) == [(30, 9.115)]
    assert logs.sealed(g2) == [] and logs.restored(g1) == []


def test_trace_written_is_the_stamp_of_the_profilers_last_line():
    lines_ = [(1.0, "... profiler: tracing steps 40..45 -> /x"),
              (2.5, "... profiler: trace written to /x")]
    assert logs.trace_written(lines_) == 2.5
    assert logs.trace_written(lines_[:1]) is None


def test_rate_and_windows_from_step_lines():
    stamped = [(0.0, 10, 1.0), (2.0, 15, 1.0), (3.0, 20, 1.0)]
    assert rate_over([(stamped[0], stamped[-1])], 100) == 10 * 100 / 3.0
    assert windows(stamped) == [(stamped[0], stamped[1]),
                                (stamped[1], stamped[2])]


def test_whole_window_rate_holds_the_stall_and_the_share_names_it():
    # four windows of 10 steps: three of 2 s, one late by 1 s
    stamped = [(0.0, 10, 1.0), (2.0, 20, 1.0), (5.0, 30, 1.0),
               (7.0, 40, 1.0), (9.0, 50, 1.0)]
    assert rate_over([(stamped[0], stamped[-1])], 100) == 40 * 100 / 9.0
    ev = {"quiet_windows": windows(stamped)}
    share = loop_stall_share.read(None, ev)
    assert abs(share - 100.0 * (1 - 8.0 / 9.0)) < 1e-9
    assert loop_stall_share.read(None, {"quiet_windows":
                                        windows(stamped[:2])}) is None


def test_windows_the_profiler_touched_are_left_out():
    # the profiler starts at step 20 and has written its file at 6.5 s:
    # the window that ends on its first step, the one it stalled (2 -> 5 s)
    # and the one in which it wrote its file are left out
    stamped = [(0.0, 10, 1.0), (2.0, 20, 1.0), (5.0, 30, 1.0),
               (7.0, 40, 1.0), (9.0, 50, 1.0), (11.0, 60, 1.0)]
    quiet = windows(stamped, profiled=(20, 6.5))
    assert [(a[1], b[1]) for a, b in quiet] == [(40, 50), (50, 60)]
    assert loop_stall_share.read(None, {"quiet_windows": quiet}) == 0.0
    assert rate_over(quiet, 100) == 20 * 100 / 4.0


def test_ckpt_stall_is_saving_window_minus_plain_window():
    class Cell:
        traffic = {"ckpt_steps": 15}
    steps = [(0.0, 30, 1.0), (1.6, 35, 1.0), (3.2, 40, 1.0),
             (17.4, 45, 1.0), (19.0, 50, 1.0)]
    ev = {"quiet_windows": windows(steps)}
    assert abs(ckpt_stall_ms.read(Cell, ev) - 12600.0) < 1e-6
    assert ckpt_stall_ms.read(Cell, {}) is None


def test_resume_readers_give_the_one_resume():
    ev = {"resume": {"resume_s": 44.0, "respawn_s": 15.5,
                     "resumed": {"restore_s": 9.1},
                     "first_step": {"first_step_s": 4.6, "misses": 0}},
          "newest_generation": {"first_step_s": 4.6, "misses": 0}}
    got = {m.__name__.rsplit(".", 1)[1]: m.read(None, ev) for m in (
        resume_s, respawn_s, ckpt_restore_s, first_step_s,
        compile_cache_misses)}
    assert got == {"resume_s": 44.0, "respawn_s": 15.5,
                   "ckpt_restore_s": 9.1, "first_step_s": 4.6,
                   "compile_cache_misses": 0}
    assert all(m.read(None, {}) is None for m in (
        resume_s, respawn_s, ckpt_restore_s, first_step_s,
        compile_cache_misses))
