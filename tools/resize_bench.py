"""Resize restore-path sweep: state size x {disk, p2p} x grow/shrink.

Companion to the state-migration plane (collective/migration.py): for
each state size it saves a dp-sharded state from a SOURCE mesh, then
times re-assembling it onto a LARGER (grow) and SMALLER (shrink) target
mesh through each transport:

- ``disk``      — the stop-resume recipe: chunk files + index on disk,
                  `restore_sharded`'s mmap region reads;
- ``disk-rep``  — the legacy replicated recipe: one flax msgpack blob,
                  full deserialize (what small-model jobs pay);
- ``p2p``       — a live donor serving the SAME chunks from memory over
                  the binary tensor wire, assembled by the SAME
                  resharding planner (`restore_from_peers`).

The reported seconds are the restore TERM of the resize downtime (the
part `TrainLoop.try_restore` owns); surviving pods under p2p skip even
this by adopting in place — see `elastic_downtime_p2p_s` in bench.py.
Bytes are what the transport actually moved. Run on any host:

  python tools/resize_bench.py --sizes-mb 8 64 256

With ``EDL_TPU_TRACE`` set (obs plane), each p2p row also gets a
phase-breakdown column derived from the restore's spans — how much of
the restore term was chunk transfer (``migrate.fetch``) vs planner/
assembly, and how many chunks crossed the wire.

``--worlds`` adds the MULTI-PROCESS world axis: real subprocess worlds
(launcher pods under a JobServer) driven through scripted grow/shrink
resizes, one row per (direction, transport):

- ``stop-resume``     — a restarted process's full price: respawn +
                        re-import + re-jit + peer/disk restore (the
                        grown pod of the reform demo);
- ``p2p-adopt``       — a survivor whose device set is unchanged
                        adopts in place (``elastic_demo --resize-p2p``);
- ``in-place-reform`` — a survivor whose device world CHANGED walks
                        the reform state machine (quiesce-seal ->
                        mesh-reform -> peer-restore -> re-jit) without
                        leaving its process (``--resize-reform``);
                        warm = shape already compiled, cold = first
                        sight of the shape (exactly one compile).

Each demo self-audits and this tool refuses to print rows from a
failed run. Sequential by design — the bench host has one core.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# virtual CPU devices for the mesh sweep — before any jax import
os.environ.setdefault("EDL_TPU_TEST_DEVICES", "8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_NUM_CPU_DEVICES",
                      os.environ["EDL_TPU_TEST_DEVICES"])
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ["EDL_TPU_TEST_DEVICES"]).strip()

from edl_tpu.obs import trace  # noqa: E402 — stdlib-only, jax-free


def _mesh(n: int):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def build_state(size_mb: float, mesh):
    """A layer-ish pytree of the requested footprint, dp-sharded over
    the mesh (first axis divisible by every mesh size in the sweep)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_leaves = 16
    rows = 64
    floats = int(size_mb * 2**20 / 4)
    cols = max(1, floats // (n_leaves * rows))
    rng = np.random.default_rng(0)
    sharding = NamedSharding(mesh, P("dp"))
    return {f"layer_{i}": jax.device_put(
        rng.normal(size=(rows, cols)).astype(np.float32), sharding)
        for i in range(n_leaves)}


def target_like(state, mesh):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P("dp"))
    return {k: jax.device_put(np.zeros(v.shape, np.float32), sharding)
            for k, v in state.items()}


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _phase_breakdown() -> str | None:
    """Per-phase split of the last p2p restore, read from the obs span
    ring (None when tracing is off): wire share of the restore term +
    chunk count — the column ROADMAP item 2's multi-host budget reads."""
    if not trace.enabled():
        return None
    restores = trace.finished("resize.restore_peers")
    if not restores:
        return None
    total = restores[-1].get("dur", 0.0)
    fetches = [s for s in trace.finished("migrate.fetch")
               if s["tid"] == restores[-1]["tid"]]
    wire = sum(s.get("dur", 0.0) for s in fetches)
    if total <= 0:
        return None
    # fetches run on the restore THREAD POOL, so their summed seconds
    # legitimately exceed the wall-clock span when reads overlap —
    # report the sum with a Σ so the column reads as thread-seconds
    return (f"wire Σ{100 * wire / total:.0f}% of wall "
            f"({len(fetches)} chunks)")


def sweep_size(size_mb: float, src_n: int, directions, trials: int):
    import jax
    import numpy as np
    from flax import serialization

    from edl_tpu.coord.store import InMemStore
    from edl_tpu.collective import migration as mig
    from edl_tpu.train import sharded_checkpoint as sc

    rows = []
    src_mesh = _mesh(src_n)
    state = build_state(size_mb, src_mesh)
    nbytes = sum(np.asarray(v).nbytes for v in state.values())

    d = tempfile.mkdtemp(prefix="edl-resize-bench-")
    try:
        sc.save_sharded(d, state)
        host = jax.device_get(state)
        blob = serialization.to_bytes(host)

        # a live donor serving the same snapshot from memory
        snap = sc.snapshot_shards(state)
        server = mig.MigrationServer(host="127.0.0.1")
        server.publish({"version": 0, "status": {}, "process_index": 0,
                        "leaves": snap["leaves"],
                        "chunks": dict(snap["chunks"])})
        store = InMemStore()
        store.put(mig.donor_key("bench", "donor0"), json.dumps(
            {"pod_id": "donor0", "addr": "127.0.0.1",
             "port": server.port, "version": 0}))
        try:
            for direction, tgt_n in directions:
                tgt_mesh = _mesh(tgt_n)
                target = target_like(state, tgt_mesh)

                disk_s = []
                for _ in range(trials):
                    t0 = time.perf_counter()
                    out = sc.restore_sharded(d, target)
                    jax.block_until_ready(out)
                    disk_s.append(time.perf_counter() - t0)

                p2p_s, wire_bytes, phases = [], 0, "-"
                for _ in range(trials):
                    trace.clear_ring()
                    t0 = time.perf_counter()
                    out, _, stats = mig.restore_from_peers(
                        store, "bench", target)
                    jax.block_until_ready(out)
                    p2p_s.append(time.perf_counter() - t0)
                    wire_bytes = stats["bytes_from_peers"]
                    phases = _phase_breakdown() or phases

                rows.append((size_mb, "disk", direction,
                             f"{src_n}->{tgt_n}", _median(disk_s), nbytes,
                             "-"))
                rows.append((size_mb, "p2p", direction,
                             f"{src_n}->{tgt_n}", _median(p2p_s),
                             wire_bytes, phases))

            # legacy replicated baseline: full msgpack deserialize (no
            # mesh direction — the blob is the whole state)
            rep_s = []
            for _ in range(trials):
                t0 = time.perf_counter()
                serialization.from_bytes(host, blob)
                rep_s.append(time.perf_counter() - t0)
            rows.append((size_mb, "disk-rep", "-", "-", _median(rep_s),
                         len(blob), "-"))
        finally:
            server.stop()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return rows


def _run_demo(flag: str) -> dict | None:
    """Run one elastic_demo mode in a subprocess; parsed summary or
    None on failure (the demos self-audit and exit nonzero)."""
    import re
    import subprocess
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)           # each demo sets its own world
    env.pop("JAX_NUM_CPU_DEVICES", None)
    env["JAX_PLATFORMS"] = "cpu"
    tag = {"--resize-p2p": "p2p_summary",
           "--resize-reform": "reform_summary"}[flag]
    proc = subprocess.run(
        [sys.executable, "-m", "edl_tpu.examples.elastic_demo", flag],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    m = re.search(tag + r"=(\{.*\})", proc.stdout)
    if not m or proc.returncode != 0:
        print(f"{flag} demo failed (rc={proc.returncode}) — rows "
              "omitted", file=sys.stderr)
        print(proc.stdout[-1500:], file=sys.stderr)
        return None
    return json.loads(m.group(1))


def sweep_worlds() -> None:
    """The multi-process world axis: grow/shrink across real subprocess
    worlds, downtime per transport (see module docstring)."""
    print("\nmulti-process world axis: measured resize downtime per "
          "transport\n(real launcher-pod subprocess worlds; each demo "
          "self-audits)\n")
    print("| direction | transport | survivor restarts | downtime s "
          "| notes |")
    print("|-----------|-----------|-------------------|-----------:"
          "|-------|")
    p2p = _run_demo("--resize-p2p")
    reform = _run_demo("--resize-reform")
    if p2p is not None:
        gaps = p2p.get("adoption_gaps_s") or []
        for direction, gap in zip(("shrink", "grow"), gaps):
            print(f"| {direction} | p2p-adopt | 0 | {gap:9.4f} "
                  "| device set unchanged |")
    if reform is not None:
        gaps = reform.get("reform_gaps_s") or []
        warm = reform.get("elastic_downtime_multihost_s")
        for gap in gaps:
            label = "warm (cached shape)" if gap == warm \
                else "cold (one compile)"
            print(f"| shrink/grow | in-place-reform | 0 | {gap:9.4f} "
                  f"| {label}; restore "
                  f"{(reform.get('last_reform') or {}).get('restore')} "
                  "|")
        respawn = reform.get("respawn_downtime_s")
        if respawn is not None:
            print(f"| grow | stop-resume | 1 | {respawn:9.4f} "
                  "| respawn + re-import + re-jit + peer restore |")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="tools/resize_bench.py")
    parser.add_argument("--sizes-mb", type=float, nargs="+",
                        default=[8, 64, 256])
    parser.add_argument("--src-devices", type=int, default=4)
    parser.add_argument("--grow-devices", type=int, default=8)
    parser.add_argument("--shrink-devices", type=int, default=2)
    parser.add_argument("--trials", type=int, default=3)
    parser.add_argument("--worlds", action="store_true",
                        help="also run the multi-process world axis "
                             "(subprocess worlds; ~3-4 min)")
    parser.add_argument("--worlds-only", action="store_true",
                        help="skip the single-host sweep")
    args = parser.parse_args(argv)

    if args.worlds_only:
        sweep_worlds()
        return 0

    import jax
    n_dev = len(jax.devices())
    for need in (args.src_devices, args.grow_devices,
                 args.shrink_devices):
        if need > n_dev:
            print(f"need {need} devices, have {n_dev} "
                  f"(set EDL_TPU_TEST_DEVICES)", file=sys.stderr)
            return 2
    directions = [("grow", args.grow_devices),
                  ("shrink", args.shrink_devices)]

    print(f"restore term of the resize downtime (median of "
          f"{args.trials}); src mesh = {args.src_devices} devices\n")
    print("| state | path | direction | mesh | restore s | MB moved "
          "| phases (spans) |")
    print("|------:|------|-----------|------|----------:|---------:"
          "|----------------|")
    for size in args.sizes_mb:
        for row in sweep_size(size, args.src_devices, directions,
                              args.trials):
            size_mb, path, direction, mesh, secs, nbytes, phases = row
            print(f"| {size_mb:.0f}MB | {path} | {direction} | {mesh} "
                  f"| {secs:9.4f} | {nbytes / 2**20:8.1f} | {phases} |")
    if args.worlds:
        sweep_worlds()
    return 0


if __name__ == "__main__":
    sys.exit(main())
