"""Elastic trainer demo — the end-to-end probe for the launcher.

Capability of the reference's `edl_demo.py` + fit_a_line fault-tolerant job
(example/demo/collective/ + example/fit_a_line/train_ft.py): a tiny linear
regression that reads the launcher's TrainerEnv, trains its data shard with
checkpoint/resume, and survives stop-resume resizes. Runs on CPU; with a
multi-pod world it shards data by rank (orchestration-level elasticity —
the same TrainLoop drives pjit models on real TPU meshes).

  python -m edl_tpu.examples.elastic_demo --epochs 5 --steps-per-epoch 20

`--scaler` turns the demo into the full controller-driven elasticity
loop on one host: an in-process store + JobServer + JobClient spawn
launcher pods running THIS trainer, while a leader-elected
`ScalerController` (edl_tpu/scaler) scrapes the trainers' published
utilization and resizes the job through `/resize` — every decision
journaled. The closed loop the reference's scheduler pillar describes,
runnable on a laptop:

  python -m edl_tpu.examples.elastic_demo --scaler --nodes-range 1:2

`--serve-scaler` runs the OTHER elasticity loop — the serving plane: a
teacher pool behind the discovery registry, an open-loop load
generator, and a `ServingPolicy` holding a latency SLO by growing the
pool on sustained breach and DRAINING it on sustained idleness
(`run_serve_scaler_demo`):

  python -m edl_tpu.examples.elastic_demo --serve-scaler
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

from edl_tpu.collective.job_env import TrainerEnv
from edl_tpu.models.linear import LinearRegression, mse_loss
from edl_tpu.train.loop import LoopConfig, TrainLoop
from edl_tpu.train.state import TrainState
from edl_tpu.train.step import make_train_step
from edl_tpu.utils.config import from_env
from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.examples.elastic_demo")

TRUE_W, TRUE_B = 3.0, -1.5


def make_data(epoch: int, rank: int, world: int, steps: int, batch: int):
    """Seed-per-pass + shard-by-rank (reference pass_id_as_seed recipe)."""
    rng = np.random.default_rng(1000 + epoch)
    n = steps * batch * max(1, world)
    x = rng.normal(size=(n, 1)).astype(np.float32)
    y = TRUE_W * x + TRUE_B + 0.01 * rng.normal(size=(n, 1)).astype(
        np.float32)
    shard = slice(rank * steps * batch, (rank + 1) * steps * batch)
    xs, ys = x[shard], y[shard]
    for i in range(steps):
        s = slice(i * batch, (i + 1) * batch)
        yield {"x": xs[s], "y": ys[s]}


def run_scaler_demo(args) -> int:
    """Controller-driven elasticity end-to-end on this host: store +
    JobServer + JobClient-spawned launcher pods + ScalerController, all
    wired to each other; returns non-zero if the job never completes,
    a resize the JobServer served escaped the decision journal (the
    served resize_log and the journal's applied resizes must match),
    or the scaler never observed fresh utilization while the node
    range left it room to act (the silently-doing-nothing failure)."""
    import os
    import shutil
    import subprocess
    import tempfile
    import threading
    import time

    from edl_tpu.collective import register as reg
    from edl_tpu.collective.job_server import JobClient, JobServer, JobState
    from edl_tpu.coord.server import StoreServer
    from edl_tpu.scaler.controller import ScalerConfig, ScalerController
    from edl_tpu.scaler.policy import ThroughputPolicy

    # the spawned pods are CPU trainers (the orchestration is the
    # demo), one device each
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["JAX_NUM_CPU_DEVICES"] = "1"

    job_id = "scaler_demo"
    lo, hi = (int(x) for x in args.nodes_range.split(":"))
    tmp = tempfile.mkdtemp(prefix="edl-scaler-demo-")
    journal_path = args.journal or os.path.join(tmp, "scaler.jsonl")
    srv = StoreServer(port=0, host="127.0.0.1", sweep_interval=0.2).start()
    store_ep = f"127.0.0.1:{srv.port}"
    state = JobState(job_id, lo, hi, desired=lo)
    server = JobServer(state, port=0).start()
    trainer_cmd = [
        sys.executable, "-m", "edl_tpu.collective.launch",
        "--store", store_ep, "--job-id", job_id,
        "--nodes-range", f"{lo}:{hi}",
        "--checkpoint-path", os.path.join(tmp, "ckpt"),
        "--log-dir", os.path.join(tmp, "log"), "--",
        sys.executable, "-m", "edl_tpu.examples.elastic_demo",
        "--epochs", str(args.epochs),
        "--steps-per-epoch", str(args.steps_per_epoch),
        "--batch", str(args.batch),
        # pace the trainers a little by default: an instant run would
        # complete before the scaler ever observes a utilization record
        "--step-time", str(args.step_time or 0.05),
        "--ckpt-steps", str(args.ckpt_steps or 10)]
    client = JobClient(f"127.0.0.1:{server.port}", trainer_cmd, poll=0.5)
    client_thread = threading.Thread(target=client.run, daemon=True,
                                     name="scaler-demo-jobclient")
    config = ScalerConfig(interval=args.scaler_interval,
                          cooldown_s=args.scaler_cooldown,
                          downtime_s=args.scaler_downtime,
                          staleness_s=10.0)
    controller = ScalerController(
        srv.store, [job_id],
        ThroughputPolicy(gain_threshold=config.gain_threshold,
                         cooldown_s=config.cooldown_s,
                         horizon_s=max(config.cooldown_s, 30.0)),
        config=config, job_server=f"127.0.0.1:{server.port}",
        journal_path=journal_path, owner="scaler-demo")
    log.info("scaler demo: store=%s job_server=:%d nodes=%d:%d "
             "journal=%s", store_ep, server.port, lo, hi, journal_path)
    complete = False
    try:
        client_thread.start()
        controller.start()
        deadline = time.time() + args.scaler_timeout
        while time.time() < deadline:
            if srv.store.get(reg.complete_key(job_id)) is not None:
                complete = True
                break
            time.sleep(0.5)
    finally:
        controller.stop()
        client.stop()
        client_thread.join(timeout=15)
        for p in client.procs:  # belt and braces: no orphan launchers
            if p.poll() is None:
                p.kill()
        server.stop()
        srv.stop()

    entries = []
    try:
        with open(journal_path, encoding="utf-8") as f:
            entries = [json.loads(line) for line in f if line.strip()]
    except OSError:
        pass
    resizes = [e for e in entries if e["action"] == "resize"]
    # Cross-check the docstring's promise: every resize the JobServer
    # actually served must have a matching journal entry (same applied
    # values, same order). `final_desired` moving off the initial `lo`
    # with an empty journal is the same escape.
    served = [s["to"] for s in state.resize_log]
    journaled = [e["applied"] if e.get("applied") is not None
                 else e["desired"] for e in resizes]
    escaped = served != journaled or \
        state.desired != (served[-1] if served else lo)
    # A scaler that silently does nothing (e.g. every record filtered
    # as pre-resize) never sees fresh utilization: with room to act
    # (hi > lo) that is a failure, not a quiet pass.
    fresh_seen = any(e.get("fresh") for e in entries)
    silent = hi > lo and not fresh_seen
    summary = {"complete": complete, "decisions": len(entries),
               "resizes": [{"tick": e["seq"], "from": e["current"],
                            "to": e["desired"], "reason": e["reason"]}
                           for e in resizes],
               "served_resizes": state.resize_log,
               "journal_matches_served": not escaped,
               "fresh_utilization_seen": fresh_seen,
               "final_desired": state.desired,
               "journal": journal_path if args.journal else None}
    log.info("scaler demo done: complete=%s decisions=%d resizes=%d "
             "served=%d journal_matches_served=%s fresh_seen=%s",
             complete, len(entries), len(resizes), len(served),
             not escaped, fresh_seen)
    if escaped:
        log.error("resize escaped the decision journal: served=%s "
                  "journaled=%s final_desired=%d", served, journaled,
                  state.desired)
    if silent:
        log.error("scaler never observed fresh utilization (nodes %d:%d"
                  ") — the closed loop is not closing", lo, hi)
    # machine-readable, as the ckpt_stats= line is
    print("scaler_summary=" + json.dumps(summary), flush=True)
    if args.journal is None:
        shutil.rmtree(tmp, ignore_errors=True)
    else:
        shutil.rmtree(os.path.join(tmp, "ckpt"), ignore_errors=True)
    return 0 if complete and not escaped and not silent else 1


def run_serve_scaler_demo(args) -> int:
    """Serving elasticity end-to-end on this host: an in-process store,
    a `TeacherPoolActuator` spawning real `TeacherServer`s (sleepy
    predict_fn standing in for chip time) with registrars publishing
    latency/queue stats, an open-loop load generator, and a
    `ScalerController` running a `ServingPolicy` — the closed loop from
    student traffic to pool size. Three load phases: cruise (SLO met),
    a 4x step (sustained p95 breach -> grow), then near-idle
    (utilization under the low-water mark -> DRAINED shrink).

    Self-audits on exit and returns non-zero unless:

      - at least one grow AND one shrink were journaled and applied,
      - every actuated pool resize has a matching journal entry,
      - at least one shrink completed as a graceful DRAIN (deregister
        -> in-flight work done -> stop), with zero hard kills,
      - the pool's latency SLO was met again by the end of the run.

    Prints a machine-readable ``serve_summary=`` line.
    """
    import os
    import shutil
    import tempfile
    import threading
    import time

    import numpy as np

    from edl_tpu.coord.registry import ServiceRegistry
    from edl_tpu.coord.server import StoreServer
    from edl_tpu.distill.registrar import DISTILL_ROOT, TeacherRegistrar
    from edl_tpu.distill.teacher_server import TeacherClient, TeacherServer
    from edl_tpu.scaler.controller import ScalerConfig, ScalerController
    from edl_tpu.scaler.policy import ThroughputPolicy
    from edl_tpu.scaler.serving import (LocalTeacher, ServingConfig,
                                        ServingPolicy, TeacherPoolActuator)

    service = "serve_demo_teacher"
    tmp = tempfile.mkdtemp(prefix="edl-serve-scaler-")
    journal_path = args.journal or os.path.join(tmp, "serving.jsonl")
    srv = StoreServer(port=0, host="127.0.0.1", sweep_interval=0.2).start()
    per_row_s = 0.002      # the fake chip: 2 ms per row
    request_rows = 8

    def spawn(index: int) -> LocalTeacher:
        def predict(feeds):
            rows = next(iter(feeds.values())).shape[0]
            time.sleep(rows * per_row_s)
            return {"logits": np.zeros((rows, 4), np.float32)}
        server = TeacherServer(predict, port=0, host="127.0.0.1",
                               max_batch=32, max_wait=0.001).start()
        registrar = TeacherRegistrar(
            srv.store, service, f"127.0.0.1:{server.port}",
            ttl=2.0, stats_interval=0.25, probe_timeout=10.0)
        registrar.start()
        return LocalTeacher(server, registrar)

    serve_cfg = ServingConfig(
        slo_p95_ms=200.0, queue_high=4.0, util_low=0.25,
        breach_ticks=2, idle_ticks=3, cooldown_s=2.0,
        min_teachers=1, max_teachers=3, drain_deadline_s=15.0)
    actuator = TeacherPoolActuator(
        spawn, min_teachers=serve_cfg.min_teachers,
        max_teachers=serve_cfg.max_teachers,
        drain_deadline_s=serve_cfg.drain_deadline_s, service=service)
    controller = ScalerController(
        srv.store, [], ThroughputPolicy(),
        config=ScalerConfig(interval=0.5, min_tick_s=0.2,
                            staleness_s=5.0),
        services=[service], serving_policy=ServingPolicy(serve_cfg),
        serving_actuate=actuator.actuate, serving_config=serve_cfg,
        journal_path=journal_path, owner="serve-scaler-demo",
        scope="serve_demo")

    # open-loop-ish load generator: requests/sec follows the phase plan;
    # endpoints are re-read from the registry so a drained teacher stops
    # receiving traffic the moment it deregisters
    phase = {"rate": 20.0}
    stop = threading.Event()

    def load_loop() -> None:
        registry = ServiceRegistry(srv.store, root=DISTILL_ROOT)
        clients: dict[str, TeacherClient] = {}
        endpoints: list[str] = []
        rr, last_refresh = 0, 0.0
        feed = {"image": np.zeros((request_rows, 4), np.float32)}
        while not stop.is_set():
            now = time.monotonic()
            if now - last_refresh > 0.3 or not endpoints:
                endpoints = [m.server for m in
                             registry.get_service(service)]
                for ep in list(clients):
                    if ep not in endpoints:
                        clients.pop(ep).close()
                last_refresh = now
            if not endpoints:
                time.sleep(0.05)
                continue
            ep = endpoints[rr % len(endpoints)]
            rr += 1
            try:
                client = clients.get(ep)
                if client is None:
                    # lifecycle: long-lived(pooled per-endpoint client; closed on dict eviction above and drained at loop end)
                    client = TeacherClient(ep, timeout=30.0,
                                           max_inflight=64)
                    clients[ep] = client
                client.predict_async(feed)
            except Exception:  # noqa: BLE001 — teacher went away
                clients.pop(ep, None)
            time.sleep(1.0 / max(phase["rate"], 1e-6))
        for client in clients.values():
            client.close()

    load_thread = threading.Thread(target=load_loop, daemon=True,
                                   name="serve-demo-load")
    final_ok = False
    try:
        actuator.resize(1)   # the initial pool, before any decisions
        controller.start()
        load_thread.start()
        # phase 1 — cruise: ~160 rows/s against 500 rows/s capacity
        time.sleep(args.serve_phase_s)
        # phase 2 — 4x step: ~640 rows/s > one teacher's capacity; the
        # backlog drives p95 over the SLO and the pool must grow
        phase["rate"] = 80.0
        time.sleep(2.5 * args.serve_phase_s)
        # phase 3 — near-idle: the pool must DRAIN back down
        phase["rate"] = 4.0
        time.sleep(3.0 * args.serve_phase_s)
        # final check: SLO met at the end (use the live rollup)
        roll = controller._service_collector.service_rollup(service)
        final_ok = (roll["latency_ms_p95"] is None
                    or roll["latency_ms_p95"] <= serve_cfg.slo_p95_ms)
    finally:
        stop.set()
        load_thread.join(timeout=10)
        controller.stop()
        actuator.wait_drains(timeout=serve_cfg.drain_deadline_s + 5)
        actuator.close()
        srv.stop()

    entries = []
    try:
        with open(journal_path, encoding="utf-8") as f:
            entries = [json.loads(line) for line in f if line.strip()]
    except OSError:
        pass
    serving = [e for e in entries if e.get("kind") == "serving"]
    resizes = [e for e in serving if e["action"] == "resize"]
    grows = [e for e in resizes if e["desired"] > e["current"]]
    shrinks = [e for e in resizes if e["desired"] < e["current"]]
    # every actuated resize must be journaled: the actuator's log minus
    # the initial pre-controller resize(1) is exactly the journal's
    journaled = [e["applied"] for e in resizes]
    actuated = [r["to"] for r in actuator.resize_log[1:]]
    drained = [d for d in actuator.drain_log if d["drained"]]
    hard_killed = [d for d in actuator.drain_log if d["hard_killed"]]
    ok = (len(grows) >= 1 and len(shrinks) >= 1
          and journaled == actuated
          and len(drained) >= 1 and not hard_killed
          and final_ok)
    summary = {"ok": ok, "decisions": len(serving),
               "grows": len(grows), "shrinks": len(shrinks),
               "resizes": [{"tick": e["seq"], "from": e["current"],
                            "to": e["desired"], "reason": e["reason"]}
                           for e in resizes],
               "journal_matches_actuated": journaled == actuated,
               "drained": len(drained),
               "hard_killed": len(hard_killed),
               "drain_log": actuator.drain_log,
               "final_slo_met": final_ok,
               "journal": journal_path if args.journal else None}
    log.info("serve-scaler demo done: %s", summary)
    if not ok:
        log.error("serve-scaler audit failed: grows=%d shrinks=%d "
                  "journal_matches=%s drained=%d hard_killed=%d "
                  "final_slo_met=%s", len(grows), len(shrinks),
                  journaled == actuated, len(drained),
                  len(hard_killed), final_ok)
    print("serve_summary=" + json.dumps(summary), flush=True)
    if args.journal is None:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0 if ok else 1


def run_serve_load_demo(args) -> int:
    """Continuous batching + admission control end-to-end on this host
    (r23): real `TeacherServer`s with sleepy predict_fns standing in
    for chip time, probed by the open-loop generator
    (`edl_tpu.distill.loadgen`) — arrivals never wait on completions,
    so overload shows up as latency/shed instead of being absorbed by
    a self-throttling client.

    Two self-audited phases:

      A. **batching A/B** — one teacher per mode at the same offered
         rates (low and mid load, well under capacity): continuous
         must sustain the same throughput as the r6 window Batcher
         with at least 1.5x lower p95 (the window's coalesce delay is
         pure latency when the device is idle; continuous dispatches
         the moment the pipeline has room).

      B. **overload + chaos** — two continuous teachers with the
         overload-shed rule armed, offered 2x pool capacity on a
         high/normal/low mix, one teacher HARD-killed mid-phase (no
         deregistration, no drain — the loadgen's failover path).
         Degradation must be per class: the high class holds >= 90%
         SLO attainment and (almost) never sheds, shedding
         concentrates on low, and completions keep flowing after both
         the first shed and the kill (the graceful-recovery audit).

    Prints a machine-readable ``serve_load_summary=`` line and returns
    non-zero unless every gate holds.
    """
    import threading
    import time

    from edl_tpu.distill.admission import AdmissionConfig
    from edl_tpu.distill.loadgen import LoadStats, run_open_loop
    from edl_tpu.distill.teacher_server import TeacherServer

    phase_s = args.serve_phase_s
    failures: list[str] = []

    def gate(cond: bool, what: str) -> None:
        if not cond:
            failures.append(what)
        log.info("%s %s", "ok  " if cond else "FAIL", what)

    # -- phase A: window vs continuous at equal offered load ------------

    def sleepy(per_row_s: float, base_s: float):
        def predict(feeds):
            rows = next(iter(feeds.values())).shape[0]
            time.sleep(base_s + per_row_s * rows)
            return {"logits": np.zeros((rows, 4), np.float32)}
        return predict

    ab: dict[str, dict] = {}
    for mode in ("window", "continuous"):
        # fast fake chip (~0.3 ms/row): service time is small against
        # the 20 ms coalesce window, so the window's cost is visible
        server = TeacherServer(
            sleepy(0.0003, 0.001), port=0, host="127.0.0.1",
            max_batch=64, max_wait=0.02,
            admission=AdmissionConfig(batching=mode)).start()
        runs = {}
        try:
            for load, rps in (("low", 25.0), ("mid", 100.0)):
                stats = run_open_loop(
                    [f"127.0.0.1:{server.port}"], duration_s=phase_s,
                    rps=rps, rows=4, seed=11)
                runs[load] = stats.summary()
        finally:
            server.stop()
        ab[mode] = runs
    for load in ("low", "mid"):
        w, c = ab["window"][load], ab["continuous"][load]
        gate(w["error"] == 0 and c["error"] == 0
             and w["shed"] == 0 and c["shed"] == 0,
             f"A/{load}: clean run (no shed, no errors)")
        gate(abs(w["rps_sustained"] - c["rps_sustained"])
             <= 0.15 * max(w["rps_sustained"], c["rps_sustained"]),
             f"A/{load}: equal sustained throughput "
             f"(window {w['rps_sustained']} vs continuous "
             f"{c['rps_sustained']} rps)")
        gate(c["p95_ms"] * 1.5 <= w["p95_ms"],
             f"A/{load}: continuous p95 >=1.5x lower "
             f"({c['p95_ms']:.1f} vs {w['p95_ms']:.1f} ms)")

    # -- phase B: 2x overload + chaos teacher-kill ----------------------

    # slower chip (36 ms device batches): pool capacity ~2 * 222 rows/s
    # = ~55 rps of 8-row requests; offered 111 rps is a 2x overload.
    # SLO 500 ms ~= 3x the saturated pipeline latency: breached by
    # queue collapse, not by the kill transient's tail
    slo_ms = 500.0
    adm = AdmissionConfig(batching="continuous", shed_ms=150.0)
    servers = [TeacherServer(sleepy(0.004, 0.004), port=0,
                             host="127.0.0.1", max_batch=8,
                             admission=adm).start() for _ in range(2)]
    live = [f"127.0.0.1:{s.port}" for s in servers]
    by_ep = dict(zip(live, servers))
    killed: dict = {}
    kill_at = 1.5 * phase_s

    def chaos_kill(i: int, t: float) -> None:
        del i
        if t >= kill_at and not killed:
            ep = live.pop()
            killed["ep"], killed["t"] = ep, t
            # hard kill: stop() RSTs live connections; no drain, no
            # deregistration — the loadgen must fail over on its own
            threading.Thread(target=by_ep[ep].stop, daemon=True,
                             name="serve-load-chaos").start()

    stats = LoadStats()
    try:
        run_open_loop(lambda: list(live), duration_s=3.0 * phase_s,
                      rps=111.0, rows=8,
                      mix={"high": 0.1, "normal": 0.15, "low": 0.75},
                      seed=12, stats=stats, on_arrival=chaos_kill)
    finally:
        for server in servers:
            try:
                server.stop()
            except Exception:  # noqa: BLE001 — one already chaos-killed
                pass
    over = stats.summary(slo_ms=slo_ms)
    cls = over["by_class"]
    sheds = {c: v["shed"] for c, v in cls.items()}
    low_share = sheds.get("low", 0) / max(sum(sheds.values()), 1)
    first_shed = stats.first_event("shed")
    gate(killed and over["shed"] >= 1 and first_shed is not None,
         f"B: overload shed happened ({over['shed']} rejects)")
    gate(cls["high"]["attainment"] is not None
         and cls["high"]["attainment"] >= 0.9,
         f"B: high class holds >=90% SLO attainment "
         f"(got {cls['high']['attainment']})")
    gate(cls["high"]["shed_pct"] <= 5.0,
         f"B: high class (almost) never sheds "
         f"(got {cls['high']['shed_pct']}%)")
    gate(low_share >= 0.7 and cls["low"]["shed_pct"] >= 30.0,
         f"B: shedding concentrates on low (low share "
         f"{low_share:.2f}, low shed {cls['low']['shed_pct']}%)")
    gate(first_shed is not None and stats.ok_after(first_shed) > 0,
         "B: completions resume after the first shed")
    gate(bool(killed) and stats.ok_after(killed.get("t", 0.0)) > 0,
         "B: completions resume after the chaos kill (failover)")
    gate(over["error"] <= 0.05 * max(over["offered"], 1),
         f"B: errors bounded to the kill's in-flight "
         f"({over['error']}/{over['offered']})")

    ok = not failures
    summary = {"ok": ok, "failures": failures,
               "ab": {m: {load: {k: r[k] for k in
                                 ("rps_offered", "rps_sustained",
                                  "p50_ms", "p95_ms")}
                          for load, r in runs.items()}
                      for m, runs in ab.items()},
               "overload": {**{k: over[k] for k in
                               ("rps_offered", "rps_sustained",
                                "offered", "ok", "shed", "error")},
                            "slo_ms": slo_ms,
                            "low_share_of_shed": round(low_share, 3),
                            "by_class": cls}}
    if not ok:
        log.error("serve-load audit failed: %s", failures)
    print("serve_load_summary=" + json.dumps(summary), flush=True)
    return 0 if ok else 1


def run_p2p_demo(args) -> int:
    """Peer-to-peer state migration end-to-end on one host: in-process
    store + JobServer (store-attached, so /resize publishes migration
    epochs) + JobClient-spawned launcher pods running THIS trainer, with
    a scripted shrink and grow driven through /resize. Self-audits that
    the p2p plane actually carried the resizes:

      - at least one pod ADOPTED a resize in place (no respawn),
      - at least one pod restored FROM PEERS with bytes over the wire,
      - /resize published a migration epoch per applied resize,

    and exits 1 when any of it silently degraded to the disk recipe.
    Prints a machine-readable ``p2p_summary=`` line
    (``elastic_downtime_p2p_s`` is the worst surviving-pod training gap,
    beside ``resize_bytes_from_peers``)."""
    import os
    import shutil
    import tempfile
    import threading
    import time

    from edl_tpu.collective import migration as mig
    from edl_tpu.collective import register as reg
    from edl_tpu.collective.barrier import read_cluster
    from edl_tpu.collective.job_server import (JobClient, JobServer,
                                               JobState, request_resize)
    from edl_tpu.coord.server import StoreServer

    # the pods are CPU trainers (the orchestration is the demo)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["JAX_NUM_CPU_DEVICES"] = "1"
    # fast membership plumbing so the measured gaps are the migration
    # plane's, not the default 10s leases (children inherit these)
    os.environ.setdefault("EDL_TPU_BARRIER_STABLE", "0.5")
    os.environ.setdefault("EDL_TPU_LEASE_TTL", "3.0")
    os.environ["EDL_TPU_RESIZE_P2P"] = "1"

    job_id = "p2p_demo"
    lo, hi = (int(x) for x in args.nodes_range.split(":"))
    if hi < 2:
        hi = 2
    tmp = tempfile.mkdtemp(prefix="edl-p2p-demo-")
    srv = StoreServer(port=0, host="127.0.0.1", sweep_interval=0.2).start()
    store_ep = f"127.0.0.1:{srv.port}"
    state = JobState(job_id, lo, hi, desired=hi, store=srv.store)
    server = JobServer(state, port=0).start()
    # long enough that training spans both scripted resizes
    epochs = max(args.epochs, 30)
    steps = max(args.steps_per_epoch, 20)
    step_time = args.step_time or 0.06
    trainer_cmd = [
        sys.executable, "-m", "edl_tpu.collective.launch",
        "--store", store_ep, "--job-id", job_id,
        "--nodes-range", f"{lo}:{hi}",
        "--checkpoint-path", os.path.join(tmp, "ckpt"),
        "--log-dir", os.path.join(tmp, "log"), "--",
        sys.executable, "-m", "edl_tpu.examples.elastic_demo",
        "--epochs", str(epochs), "--steps-per-epoch", str(steps),
        "--batch", str(args.batch), "--step-time", str(step_time),
        "--ckpt-steps", str(args.ckpt_steps or 10)]
    client = JobClient(f"127.0.0.1:{server.port}", trainer_cmd, poll=0.5)
    client_thread = threading.Thread(target=client.run, daemon=True,
                                     name="p2p-demo-jobclient")

    acks: dict[tuple, dict] = {}   # (pod_id, ts) -> ack doc

    def sample_acks() -> None:
        records, _ = srv.store.get_prefix(mig.ack_prefix(job_id))
        for rec in records:
            try:
                doc = json.loads(rec.value)
                acks[(doc["pod_id"], doc["ts"])] = doc
            except (ValueError, KeyError):
                continue

    def wait_for(pred, timeout, what) -> bool:
        deadline = time.time() + timeout
        while time.time() < deadline:
            sample_acks()
            if pred():
                return True
            time.sleep(0.25)
        log.error("p2p demo: timeout waiting for %s", what)
        return False

    def world() -> int:
        c = read_cluster(srv.store, job_id)
        return c.world_size if c is not None else 0

    phases_ok = True
    complete = False
    t_shrink = t_grow = None
    try:
        client_thread.start()
        # Phase 1: full world up, at least one donor advertising a
        # sealed snapshot (training + checkpointing live).
        phases_ok &= wait_for(
            lambda: world() == hi and mig.live_donors(srv.store, job_id),
            args.p2p_timeout, "world up with live donors")
        if phases_ok:
            # Phase 2: shrink. Survivors must ADOPT in place.
            t_shrink = time.time()
            request_resize(f"127.0.0.1:{server.port}", lo)
            phases_ok &= wait_for(
                lambda: world() == lo and any(
                    d["mode"] == "adopted" and d["ts"] > t_shrink
                    for d in acks.values()),
                args.p2p_timeout, "shrink adopted in place")
        if phases_ok:
            # Phase 3: grow. The new pod must restore FROM PEERS.
            time.sleep(2.0)  # let survivors seal fresh versions
            t_grow = time.time()
            request_resize(f"127.0.0.1:{server.port}", hi)
            phases_ok &= wait_for(
                lambda: world() == hi and any(
                    d["mode"] == "peers" and d["ts"] > t_grow
                    for d in acks.values()),
                args.p2p_timeout, "grow restored from peers")
        # Let the job finish (proves the migrated world still trains).
        if phases_ok:
            complete = wait_for(
                lambda: srv.store.get(reg.complete_key(job_id))
                is not None,
                args.p2p_timeout + epochs * steps * step_time,
                "job completion")
        sample_acks()
    finally:
        client.stop()
        client_thread.join(timeout=15)
        for p in client.procs:  # belt and braces: no orphan launchers
            if p.poll() is None:
                p.kill()
        server.stop()
        srv.stop()

    adoptions = [d for d in acks.values() if d["mode"] == "adopted"]
    peer_restores = [d for d in acks.values() if d["mode"] == "peers"]
    disk_restores = [d for d in acks.values() if d["mode"] == "disk"]
    bytes_from_peers = sum(d.get("bytes_from_peers") or 0
                           for d in peer_restores)
    gaps = [d["downtime_s"] for d in adoptions
            if d.get("downtime_s") is not None]
    ok = (phases_ok and complete and len(adoptions) >= 1
          and len(peer_restores) >= 1 and bytes_from_peers > 0
          and state._migration_epoch >= 2)
    summary = {
        "ok": ok, "complete": complete,
        "adoptions": len(adoptions),
        "peer_restores": len(peer_restores),
        "disk_restores": len(disk_restores),
        "resize_bytes_from_peers": bytes_from_peers,
        # worst surviving-pod training gap across the scripted resizes:
        # the p2p analogue of the kill->first-step stop-resume downtime
        "elastic_downtime_p2p_s": round(max(gaps), 4) if gaps else None,
        "adoption_gaps_s": [round(g, 4) for g in sorted(gaps)],
        "peer_restore_s": [d.get("restore_s") for d in peer_restores],
        "migration_epochs_published": state._migration_epoch,
        "served_resizes": state.resize_log}
    from edl_tpu.obs import trace as obs_trace
    if obs_trace.enabled():
        # the traced-resize acceptance surface: one causally-linked
        # trace per resize, phases summing against the measured
        # downtime — viewable via `python -m edl_tpu.obs trace <dir>`.
        # Only traces started by THIS run count (the sink dir persists
        # across runs by design).
        spans = obs_trace.load_spans(obs_trace.sink_dir())
        resizes = [r for r in obs_trace.resize_phase_summary(spans)
                   if t_shrink is None or r["t0"] >= t_shrink - 60.0]
        summary["trace_dir"] = obs_trace.sink_dir()
        summary["resize_traces"] = [
            {"trace_id": r["trace_id"], "spans": r["spans"],
             "phases": r["phases"], "downtime_s": r["downtime_s"]}
            for r in resizes]
    log.info("p2p demo done: %s", summary)
    if not ok:
        log.error("p2p audit failed: the resize path fell back to the "
                  "disk recipe (adoptions=%d peer_restores=%d bytes=%d "
                  "epochs=%d complete=%s)", len(adoptions),
                  len(peer_restores), bytes_from_peers,
                  state._migration_epoch, complete)
    print("p2p_summary=" + json.dumps(summary), flush=True)
    shutil.rmtree(tmp, ignore_errors=True)
    return 0 if ok else 1


def run_reform_demo(args) -> int:
    """Multi-host resize WITHOUT restart, end-to-end on one host: the
    reform-state-machine loop (collective/reform.py). Pods run with TWO
    virtual CPU devices and a local dp mesh sized by the elastic world
    (``--local-mesh-by-world``), so every resize is a true device-world
    change for every survivor: the surviving OS process quiesce-seals
    its live state, re-forms its mesh, restores reshaped state from
    peers over the tensor wire, re-jits (under the in-process jit cache
    + the persistent compilation cache), steps, and acks — generation-
    fenced. Scripted shrink + grow through /resize; self-audits:

      - at least TWO in-place reforms completed (result "in-place"
        with the full phase ladder in the adoption ack),
      - at least one pod rode BOTH resizes on the SAME pid — a
        multi-process resize with zero process restarts,
      - at least one reform restored its reshaped state FROM PEERS
        with bytes over the wire (disk is only the typed fallback),
      - the job still completes on the final world.

    Prints ``reform_summary=``: `elastic_downtime_multihost_s` is the
    best (compile-cache-warm) survivor gap — the steady-state cost of a
    device-world change; `_cold_s` is the worst (first sight of a new
    shape pays exactly one compile).
    """
    import os
    import shutil
    import tempfile
    import threading
    import time

    from edl_tpu.collective import migration as mig
    from edl_tpu.collective import register as reg
    from edl_tpu.collective.barrier import read_cluster
    from edl_tpu.collective.job_server import (JobClient, JobServer,
                                               JobState, request_resize)
    from edl_tpu.coord.server import StoreServer

    # the pods are CPU trainers; TWO virtual devices each so the local
    # mesh can genuinely change size across resizes
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["JAX_NUM_CPU_DEVICES"] = "2"
    os.environ.setdefault("EDL_TPU_BARRIER_STABLE", "0.5")
    os.environ.setdefault("EDL_TPU_LEASE_TTL", "3.0")
    os.environ["EDL_TPU_RESIZE_P2P"] = "1"
    # reforms pay seal + restore + re-jit: give the launcher's adoption
    # fence room beyond the default 10s on a busy 1-core host
    os.environ.setdefault("EDL_TPU_ADOPT_TIMEOUT", "30")

    job_id = "reform_demo"
    lo, hi = (int(x) for x in args.nodes_range.split(":"))
    if hi < 2:
        hi = 2
    tmp = tempfile.mkdtemp(prefix="edl-reform-demo-")
    srv = StoreServer(port=0, host="127.0.0.1", sweep_interval=0.2).start()
    store_ep = f"127.0.0.1:{srv.port}"
    state = JobState(job_id, lo, hi, desired=hi, store=srv.store)
    server = JobServer(state, port=0).start()
    epochs = max(args.epochs, 30)
    steps = max(args.steps_per_epoch, 20)
    step_time = args.step_time or 0.05
    trainer_cmd = [
        sys.executable, "-m", "edl_tpu.collective.launch",
        "--store", store_ep, "--job-id", job_id,
        "--nodes-range", f"{lo}:{hi}",
        "--checkpoint-path", os.path.join(tmp, "ckpt"),
        "--log-dir", os.path.join(tmp, "log"), "--",
        sys.executable, "-m", "edl_tpu.examples.elastic_demo",
        "--epochs", str(epochs), "--steps-per-epoch", str(steps),
        "--batch", str(args.batch), "--step-time", str(step_time),
        "--local-mesh-by-world",
        "--ckpt-steps", str(args.ckpt_steps or 10)]
    client = JobClient(f"127.0.0.1:{server.port}", trainer_cmd, poll=0.5)
    client_thread = threading.Thread(target=client.run, daemon=True,
                                     name="reform-demo-jobclient")

    acks: dict[tuple, dict] = {}   # (pod_id, ts) -> ack doc

    def sample_acks() -> None:
        records, _ = srv.store.get_prefix(mig.ack_prefix(job_id))
        for rec in records:
            try:
                doc = json.loads(rec.value)
                acks[(doc["pod_id"], doc["ts"])] = doc
            except (ValueError, KeyError):
                continue

    def wait_for(pred, timeout, what) -> bool:
        deadline = time.time() + timeout
        while time.time() < deadline:
            sample_acks()
            if pred():
                return True
            time.sleep(0.25)
        log.error("reform demo: timeout waiting for %s", what)
        return False

    def world() -> int:
        c = read_cluster(srv.store, job_id)
        return c.world_size if c is not None else 0

    def reform_acks(after: float) -> list[dict]:
        return [d for d in acks.values()
                if d["mode"] == "adopted" and d["ts"] > after
                and (d.get("reform") or {}).get("result") == "in-place"]

    phases_ok = True
    complete = False
    t_shrink = t_grow = None
    try:
        client_thread.start()
        phases_ok &= wait_for(
            lambda: world() == hi and mig.live_donors(srv.store, job_id),
            args.p2p_timeout, "world up with live donors")
        if phases_ok:
            # shrink: every survivor's local mesh GROWS (world hi -> lo
            # frees devices per pod) — a device-world change they must
            # reform through in place
            t_shrink = time.time()
            request_resize(f"127.0.0.1:{server.port}", lo)
            phases_ok &= wait_for(
                lambda: world() == lo and reform_acks(t_shrink),
                args.p2p_timeout, "shrink reformed in place")
        if phases_ok:
            time.sleep(1.5)  # survivors seal fresh versions
            # grow: survivors reform BACK to an already-seen shape (the
            # compile-cache-hot path) while the new pod restores from
            # peers through a full respawn
            t_grow = time.time()
            request_resize(f"127.0.0.1:{server.port}", hi)
            phases_ok &= wait_for(
                lambda: world() == hi and reform_acks(t_grow) and any(
                    d["mode"] == "peers" and d["ts"] > t_grow
                    for d in acks.values()),
                args.p2p_timeout, "grow reformed + peer-restored")
        if phases_ok:
            complete = wait_for(
                lambda: srv.store.get(reg.complete_key(job_id))
                is not None,
                args.p2p_timeout + epochs * steps * step_time,
                "job completion")
        sample_acks()
    finally:
        client.stop()
        client_thread.join(timeout=15)
        for p in client.procs:  # belt and braces: no orphan launchers
            if p.poll() is None:
                p.kill()
        server.stop()
        srv.stop()

    reforms = [d for d in acks.values()
               if d["mode"] == "adopted"
               and (d.get("reform") or {}).get("result") == "in-place"]
    peer_reforms = [d for d in reforms
                    if d["reform"].get("restore") == "peers"]
    disk_reforms = [d for d in reforms
                    if d["reform"].get("restore") == "disk"]
    respawn_restores = [d for d in acks.values() if d["mode"] == "peers"]
    # zero-restart proof: one pod rode >=2 generations on ONE pid while
    # the world was multi-process
    by_pod: dict[str, set] = {}
    for d in reforms:
        by_pod.setdefault(d["pod_id"], set()).add(
            (d.get("pid"), d.get("generation")))
    survivors = [pod for pod, gens in by_pod.items()
                 if len({g for _, g in gens}) >= 2
                 and len({p for p, _ in gens}) == 1]
    bytes_from_peers = sum(d.get("bytes_from_peers") or 0
                           for d in reforms + respawn_restores)
    gaps = sorted(d["downtime_s"] for d in reforms
                  if d.get("downtime_s") is not None)
    # respawned-pod gap: the stop-resume price a NON-surviving process
    # pays on the same resize
    respawn_gaps = sorted(d["ts"] - t_grow for d in respawn_restores
                          if t_grow is not None and d["ts"] > t_grow)
    ok = (phases_ok and complete and len(reforms) >= 2
          and len(survivors) >= 1 and len(peer_reforms) >= 1
          and bytes_from_peers > 0)
    last_reform = max(reforms, key=lambda d: d["ts"])["reform"] \
        if reforms else None
    summary = {
        "ok": ok, "complete": complete,
        "reforms_in_place": len(reforms),
        "reform_restores_peers": len(peer_reforms),
        "reform_restores_disk": len(disk_reforms),
        "respawn_peer_restores": len(respawn_restores),
        "zero_restart_survivors": survivors,
        "resize_bytes_from_peers": bytes_from_peers,
        # best gap = compile-cache-warm reform (the steady state);
        # worst = first sight of a new shape (exactly one compile)
        "elastic_downtime_multihost_s": round(gaps[0], 4) if gaps
        else None,
        "elastic_downtime_multihost_cold_s": round(gaps[-1], 4) if gaps
        else None,
        "reform_gaps_s": [round(g, 4) for g in gaps],
        "respawn_downtime_s": round(respawn_gaps[0], 4)
        if respawn_gaps else None,
        "last_reform": last_reform,
        "migration_epochs_published": state._migration_epoch,
        "served_resizes": state.resize_log}
    log.info("reform demo done: %s", summary)
    if not ok:
        log.error("reform audit failed: reforms=%d survivors=%s "
                  "peer_reforms=%d bytes=%d complete=%s", len(reforms),
                  survivors, len(peer_reforms), bytes_from_peers,
                  complete)
    print("reform_summary=" + json.dumps(summary), flush=True)
    shutil.rmtree(tmp, ignore_errors=True)
    return 0 if ok else 1


def run_spot_demo(args) -> int:
    """Spot-capacity riding end-to-end on one host: the live elastic
    world (store + JobServer + launcher pods running THIS trainer)
    receives a spot preemption NOTICE and must ride it as a SCHEDULED
    quiesce-seal-donate shrink inside the notice window — never a
    surprise kill, never lost progress. The window comes from
    ``EDL_TPU_SPOT_NOTICE_S`` (a live CPU-jax world needs a generous
    one; a real fleet gets 30-120s from its provider).

    The script: bring the full world up with live donors (sealed
    snapshots advertised), stamp the notice deadline, then issue the
    scheduled shrink through /resize — exactly what the fleet
    scheduler's preemptive policy does when a notice lands
    (scaler/fleet_policy.py). Self-audits, exit 1 on any miss:

      - the shrink COMPLETED before the deadline (world at the target
        and a survivor's in-place adoption acked) — the notice was
        ridden, so the provider's reclaim at the deadline finds the
        capacity already donated and has nothing to kill;
      - zero lost progress: the survivor adopted IN PLACE (same
        process, in-memory state carried — mode "adopted", no respawn)
        and nothing fell back to the disk recipe after the notice;
      - the job still completes on the shrunk world.

    Prints ``spot_summary=`` with the ride margin (deadline minus
    completion) — the live counterpart of the fleet simulator's
    ``notices_ridden`` column and the chaos soak's I7 invariant.
    """
    import os
    import shutil
    import tempfile
    import threading
    import time

    from edl_tpu.collective import migration as mig
    from edl_tpu.collective import register as reg
    from edl_tpu.collective.barrier import read_cluster
    from edl_tpu.collective.job_server import (JobClient, JobServer,
                                               JobState, request_resize)
    from edl_tpu.coord.server import StoreServer
    from edl_tpu.utils.config import env_float

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["JAX_NUM_CPU_DEVICES"] = "1"
    os.environ.setdefault("EDL_TPU_BARRIER_STABLE", "0.5")
    os.environ.setdefault("EDL_TPU_LEASE_TTL", "3.0")
    os.environ["EDL_TPU_RESIZE_P2P"] = "1"

    notice_s = env_float("EDL_TPU_SPOT_NOTICE_S", 60.0)
    if notice_s <= 0:
        log.error("spot demo: EDL_TPU_SPOT_NOTICE_S=0 means notices "
                  "are ignored — nothing to demonstrate")
        return 1
    job_id = "spot_demo"
    lo, hi = (int(x) for x in args.nodes_range.split(":"))
    if hi < 2:
        hi = 2
    tmp = tempfile.mkdtemp(prefix="edl-spot-demo-")
    srv = StoreServer(port=0, host="127.0.0.1", sweep_interval=0.2).start()
    store_ep = f"127.0.0.1:{srv.port}"
    state = JobState(job_id, lo, hi, desired=hi, store=srv.store)
    server = JobServer(state, port=0).start()
    epochs = max(args.epochs, 30)
    steps = max(args.steps_per_epoch, 20)
    step_time = args.step_time or 0.06
    trainer_cmd = [
        sys.executable, "-m", "edl_tpu.collective.launch",
        "--store", store_ep, "--job-id", job_id,
        "--nodes-range", f"{lo}:{hi}",
        "--checkpoint-path", os.path.join(tmp, "ckpt"),
        "--log-dir", os.path.join(tmp, "log"), "--",
        sys.executable, "-m", "edl_tpu.examples.elastic_demo",
        "--epochs", str(epochs), "--steps-per-epoch", str(steps),
        "--batch", str(args.batch), "--step-time", str(step_time),
        "--ckpt-steps", str(args.ckpt_steps or 10)]
    client = JobClient(f"127.0.0.1:{server.port}", trainer_cmd, poll=0.5)
    client_thread = threading.Thread(target=client.run, daemon=True,
                                     name="spot-demo-jobclient")

    acks: dict[tuple, dict] = {}   # (pod_id, ts) -> ack doc

    def sample_acks() -> None:
        records, _ = srv.store.get_prefix(mig.ack_prefix(job_id))
        for rec in records:
            try:
                doc = json.loads(rec.value)
                acks[(doc["pod_id"], doc["ts"])] = doc
            except (ValueError, KeyError):
                continue

    def wait_for(pred, timeout, what) -> bool:
        deadline = time.time() + timeout
        while time.time() < deadline:
            sample_acks()
            if pred():
                return True
            time.sleep(0.25)
        log.error("spot demo: timeout waiting for %s", what)
        return False

    def world() -> int:
        c = read_cluster(srv.store, job_id)
        return c.world_size if c is not None else 0

    phases_ok = True
    complete = False
    t_notice = deadline = t_rode = None
    try:
        client_thread.start()
        # Phase 1: full world with sealed snapshots advertised — the
        # precondition for donating capacity without losing anything.
        phases_ok &= wait_for(
            lambda: world() == hi and mig.live_donors(srv.store, job_id),
            args.p2p_timeout, "world up with live donors")
        if phases_ok:
            # Phase 2: the NOTICE. From here the world has notice_s
            # seconds to quiesce-seal-donate down to the post-reclaim
            # capacity; the scheduled shrink through /resize IS the
            # riding maneuver (what PreemptiveFairSharePolicy issues
            # when a notice lands in the fleet).
            t_notice = time.time()
            deadline = t_notice + notice_s
            log.info("spot notice: %d node(s) reclaimed in %.0fs — "
                     "scheduled shrink %d -> %d", hi - lo, notice_s,
                     hi, lo)
            request_resize(f"127.0.0.1:{server.port}", lo)

            def rode() -> bool:
                return world() == lo and any(
                    d["mode"] == "adopted" and d["ts"] > t_notice
                    for d in acks.values())

            phases_ok &= wait_for(rode, notice_s,
                                  "sealed shrink inside the notice "
                                  "window")
            t_rode = time.time()
            if phases_ok and t_rode > deadline:
                phases_ok = False
                log.error("spot demo: shrink finished %.1fs AFTER the "
                          "deadline — the provider's reclaim would "
                          "have hard-killed live pods",
                          t_rode - deadline)
        if phases_ok:
            complete = wait_for(
                lambda: srv.store.get(reg.complete_key(job_id))
                is not None,
                args.p2p_timeout + epochs * steps * step_time,
                "job completion on the shrunk world")
        sample_acks()
    finally:
        client.stop()
        client_thread.join(timeout=15)
        for p in client.procs:  # belt and braces: no orphan launchers
            if p.poll() is None:
                p.kill()
        server.stop()
        srv.stop()

    adoptions = [d for d in acks.values() if d["mode"] == "adopted"
                 and t_notice is not None and d["ts"] > t_notice]
    disk_restores = [d for d in acks.values() if d["mode"] == "disk"
                     and t_notice is not None and d["ts"] > t_notice]
    gaps = [d["downtime_s"] for d in adoptions
            if d.get("downtime_s") is not None]
    rode_notice = (phases_ok and t_rode is not None
                   and deadline is not None and t_rode <= deadline)
    # zero lost progress = the survivors carried their in-memory state
    # (in-place adoption, no respawn) and nothing degraded to the disk
    # recipe after the notice; completion proves the world still trains
    ok = (rode_notice and complete and len(adoptions) >= 1
          and not disk_restores)
    summary = {
        "ok": ok, "complete": complete,
        "rode_notice": rode_notice,
        "notice_window_s": notice_s,
        "ride_margin_s": round(deadline - t_rode, 3)
        if rode_notice else None,
        "adoptions_after_notice": len(adoptions),
        "disk_restores_after_notice": len(disk_restores),
        "spot_downtime_s": round(max(gaps), 4) if gaps else None,
        "served_resizes": state.resize_log}
    log.info("spot demo done: %s", summary)
    if not ok:
        log.error("spot audit failed: rode=%s adoptions=%d disk=%d "
                  "complete=%s", rode_notice, len(adoptions),
                  len(disk_restores), complete)
    print("spot_summary=" + json.dumps(summary), flush=True)
    shutil.rmtree(tmp, ignore_errors=True)
    return 0 if ok else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=5)
    parser.add_argument("--steps-per-epoch", type=int, default=20)
    parser.add_argument("--batch", type=int, default=32)
    parser.add_argument("--step-time", type=float, default=0.0,
                        help="artificial per-step delay (resize-window test)")
    parser.add_argument("--ckpt-steps", type=int, default=None,
                        help="also checkpoint every N steps (default "
                             "$EDL_TPU_CKPT_STEPS, else epoch-end only)")
    parser.add_argument("--ckpt-sync", action="store_true",
                        help="synchronous saves (default async "
                             "snapshot-then-write)")
    # controller-driven elasticity (see module docstring)
    parser.add_argument("--scaler", action="store_true",
                        help="run the closed loop: store + JobServer + "
                             "launcher pods + utilization-driven scaler")
    parser.add_argument("--nodes-range", default="1:2",
                        help="--scaler: min:max pods on this host")
    parser.add_argument("--scaler-interval", type=float, default=1.0)
    parser.add_argument("--scaler-cooldown", type=float, default=8.0)
    parser.add_argument("--scaler-downtime", type=float, default=1.5,
                        help="measured elastic_downtime_s to amortize")
    parser.add_argument("--scaler-timeout", type=float, default=300.0)
    parser.add_argument("--journal", default=None,
                        help="--scaler: keep the decision journal here")
    # serving elasticity demo (see run_serve_scaler_demo)
    parser.add_argument("--serve-scaler", action="store_true",
                        help="run the serving loop: store + teacher "
                             "pool + load generator + SLO-driven "
                             "scaler, self-audited grow + drained "
                             "shrink")
    parser.add_argument("--serve-phase-s", type=float, default=5.0,
                        help="--serve-scaler/--serve-load: base "
                             "load-phase seconds")
    # continuous-batching + admission-control dryrun (run_serve_load_demo)
    parser.add_argument("--serve-load", action="store_true",
                        help="run the serving load dryrun: open-loop "
                             "generator vs window/continuous batching "
                             "A/B, then 2x overload + chaos teacher "
                             "kill with per-class shed/attainment "
                             "audits")
    # peer-to-peer migration demo (see run_p2p_demo)
    parser.add_argument("--resize-p2p", action="store_true",
                        help="run the live-migration loop: store + "
                             "JobServer + pods, scripted shrink/grow, "
                             "self-audited p2p adoption + peer restore")
    parser.add_argument("--p2p-timeout", type=float, default=120.0,
                        help="--resize-p2p: per-phase timeout seconds")
    # reform state-machine demo (see run_reform_demo)
    parser.add_argument("--resize-reform", action="store_true",
                        help="run the multi-host-resize-without-restart "
                             "loop: 2-device pods whose local mesh is "
                             "sized by the elastic world, scripted "
                             "shrink/grow, self-audited in-place "
                             "reforms with zero process restarts")
    # spot-capacity riding demo (see run_spot_demo)
    parser.add_argument("--spot", action="store_true",
                        help="run the spot-riding loop: live world + "
                             "preemption notice ridden as a scheduled "
                             "quiesce-seal-donate shrink inside "
                             "$EDL_TPU_SPOT_NOTICE_S; exit 1 unless "
                             "it lands before the deadline with zero "
                             "lost progress")
    parser.add_argument("--local-mesh-by-world", action="store_true",
                        help="trainer mode for --resize-reform: local "
                             "dp mesh sized by the elastic world, "
                             "reform state machine wired (per-pod ckpt "
                             "subdirs)")
    args = parser.parse_args(argv)
    if sum((args.scaler, args.resize_p2p, args.serve_scaler,
            args.serve_load, args.resize_reform, args.spot)) > 1:
        parser.error("--scaler, --serve-scaler, --serve-load, "
                     "--resize-p2p, --resize-reform and --spot are "
                     "separate demos")
    if args.spot:
        return run_spot_demo(args)
    if args.serve_load:
        return run_serve_load_demo(args)
    if args.serve_scaler:
        return run_serve_scaler_demo(args)
    if args.resize_p2p:
        return run_p2p_demo(args)
    if args.resize_reform:
        return run_reform_demo(args)
    if args.scaler:
        return run_scaler_demo(args)

    env = TrainerEnv.from_environ()
    log.info("trainer up: rank=%d world=%d cluster_v=%d", env.rank,
             env.world_size, env.cluster_version)

    model = LinearRegression(features=1)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 1)))["params"]
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.sgd(0.05))

    # --local-mesh-by-world: the reform-state-machine trainer shape.
    # The local dp mesh is a FUNCTION of the elastic world (world 1 ->
    # all local devices, world w -> ndev // w), so a resize is a true
    # device-world change for every survivor: the reform_mesh hook
    # returns the new mesh and the TrainLoop walks quiesce ->
    # mesh-reform -> peer-restore -> re-jit in place (no respawn).
    # Each pod checkpoints under its own subdir — per-pod version
    # counters are per-lineage, and the reform restore is self-scoped.
    reform_kwargs: dict = {}
    if args.local_mesh_by_world:
        from jax.sharding import Mesh
        from edl_tpu.parallel import mesh as mesh_lib

        def _mesh_for(world: int) -> "Mesh":
            devices = jax.devices()
            n = len(devices) if world <= 1 \
                else max(1, len(devices) // world)
            return Mesh(np.array(devices[:n]), ("dp",))

        mesh_holder = {"mesh": _mesh_for(env.world_size)}

        def reform_mesh(rank, world, cluster):
            new = _mesh_for(world)
            if new.devices.size == mesh_holder["mesh"].devices.size:
                return None  # device world unchanged: fast adoption
            mesh_holder["mesh"] = new
            return new

        # place the INITIAL state exactly the way a reform re-places it
        # (replicated NamedSharding on the live mesh): the jit cache
        # then keys identically when a later reform revisits this
        # shape — the compile-cache-hit path the re-jit phase banks on
        state = mesh_lib.replicate_host_tree(mesh_holder["mesh"], state)
        reform_kwargs = {
            "mesh": mesh_holder["mesh"], "batch_axes": ("dp",),
            "place_state": lambda t: mesh_lib.replicate_host_tree(
                mesh_holder["mesh"], t),
            "reform_mesh": reform_mesh}

    def loss_fn(state, params, batch):
        pred = state.apply_fn({"params": params}, batch["x"])
        return mse_loss(pred, batch["y"]), {}

    step = make_train_step(loss_fn, donate=False)
    if args.step_time > 0:
        import time
        raw_step = step

        def step(s, b):  # noqa: F811 — wrapped for the resize-window test
            time.sleep(args.step_time)
            return raw_step(s, b)

    ckpt_kw = {}
    if args.ckpt_steps is not None:
        ckpt_kw["ckpt_every_steps"] = args.ckpt_steps
    if args.ckpt_sync:
        ckpt_kw["ckpt_async"] = False

    def on_reform(rank, world, cluster):
        # Live migration: a resize that keeps this pod re-enters the
        # epoch in place — re-derive the data shard for the new world
        # (make_data reads env at each data_fn call).
        env.rank, env.world_size = rank, world
        env.cluster_version = cluster.version

    ckpt_dir = env.checkpoint_path or None
    if ckpt_dir and args.local_mesh_by_world and env.pod_id:
        import os
        ckpt_dir = os.path.join(ckpt_dir, env.pod_id)
    loop = TrainLoop(step, state, config=from_env(
        LoopConfig, num_epochs=args.epochs,
        ckpt_dir=ckpt_dir,
        log_every_steps=args.steps_per_epoch, **ckpt_kw),
        on_reform=on_reform, **reform_kwargs)
    status = loop.run(lambda epoch: make_data(
        epoch, env.rank, env.world_size, args.steps_per_epoch, args.batch))

    w = float(np.asarray(loop.state.params["Dense_0"]["kernel"])[0, 0])
    b = float(np.asarray(loop.state.params["Dense_0"]["bias"])[0])
    log.info("done: epoch=%d step=%d w=%.3f b=%.3f", status.epoch,
             status.step, w, b)
    # machine-readable. A graceful SIGTERM stop never reaches here: loop.run raises
    # SystemExit(143) after its donor linger (the launcher must not
    # read a stopped trainer as "training complete").
    print("ckpt_stats=" + json.dumps(loop.ckpt_stats()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
