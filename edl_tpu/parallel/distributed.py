"""Multi-host world formation from the launcher's env contract.

Replaces the reference's NCCL world bootstrap (Paddle fleet reads
PADDLE_TRAINER_* env and broadcasts ncclUniqueId over sockets,
utils/edl_process.py:42-47): a trainer started by
`edl_tpu.collective.launch` calls `init_from_env()` once (e.g.
`examples/multipod_demo.py`, the launcher's one-world trainer); on a
multi-pod cluster this runs `jax.distributed.initialize` against the
rank-0 pod's coordinator endpoint, after which `jax.devices()` spans all
hosts and every mesh built on it gets its collectives compiled over
ICI/DCN by XLA — there is no per-op communication library to configure.

On CPU (tests/CI) the cross-process data plane is the gloo TCP
collectives backend, selected automatically; on TPU, ICI/DCN needs no
selection.
"""

from __future__ import annotations

import os

import jax

from jax._src import xla_bridge

from edl_tpu.collective.job_env import TrainerEnv
from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.parallel.distributed")

_initialized = False


def force_platform_from_env() -> None:
    """Apply JAX_PLATFORMS / JAX_NUM_CPU_DEVICES through jax.config.

    JAX reads both variables itself at import; a trainer whose launcher
    (or test) exported them after jax was imported applies the same
    contract here, before the backend initializes. No-op once a backend
    exists or when the vars are unset.
    """
    if xla_bridge.backends_are_initialized():
        # config.update("jax_platforms") after backend init silently
        # resets the backend cache (an 8-device CPU test world would
        # collapse to the default device count)
        return
    plat = os.environ.get("JAX_PLATFORMS")
    ndev = os.environ.get("JAX_NUM_CPU_DEVICES", "").strip()
    if plat:
        jax.config.update("jax_platforms", plat)
    if ndev:
        try:
            jax.config.update("jax_num_cpu_devices", int(ndev))
        except ValueError:
            log.warning("ignoring malformed JAX_NUM_CPU_DEVICES=%r", ndev)


# One fixed path inside the checkout (git-ignored): the directory is
# part of the cache key, so a path that moves between runs never hits.
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_cache_counts: dict = {}   # empty until the listener is registered
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}


def _count_cache_event(event: str, **_) -> None:
    key = _CACHE_EVENTS.get(event)
    if key:
        _cache_counts[key] += 1


def enable_compilation_cache() -> str:
    """Turn XLA's persistent compilation cache on; returns its directory.

    The elastic-downtime lever: a stop-resume re-formation re-jits every
    program from scratch, and for a world whose shape (and therefore
    compiled programs) did NOT change, that recompile dominates
    kill->first-step time. With the cache on a persistent path, the
    re-formed trainer loads the previous generation's executables
    instead of rebuilding them. Thresholds drop to 0 so even quick
    compiles persist — an elastic restart replays ALL of them at once.

    The directory is placed from outside: where JAX_COMPILATION_CACHE_DIR
    is set JAX reads it and nothing is set here; otherwise the one fixed
    path inside the checkout.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not _cache_counts:
        _cache_counts.update(hits=0, misses=0)
        jax.monitoring.register_event_listener(_count_cache_event)
        log.info("persistent XLA compilation cache at %s", cache_dir)
    return cache_dir


def compilation_cache_counts() -> dict:
    """Persistent-cache hits and misses of this process so far."""
    return dict(_cache_counts)


def init_from_env(env: TrainerEnv | None = None) -> TrainerEnv:
    """Join the multi-host world described by the EDL_TPU_* env (no-op for
    single-pod jobs or repeat calls). Returns the parsed TrainerEnv."""
    global _initialized
    env = env or TrainerEnv.from_environ()
    enable_compilation_cache()  # re-formed worlds skip unchanged re-jits
    if env.world_size > 1 and not _initialized:
        force_platform_from_env()
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # Multi-process CPU needs an explicit inter-process collectives
            # implementation; TPU rides ICI/DCN without one.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        log.info("joining world: rank=%d/%d coordinator=%s",
                 env.rank, env.world_size, env.coordinator)
        jax.distributed.initialize(
            coordinator_address=env.coordinator,
            num_processes=env.world_size,
            process_id=env.rank)
        _initialized = True
    return env


def slice_topology(env: TrainerEnv | None = None,
                   devices: list | None = None):
    """Derive the job's ICI×DCN SliceTopology.

    Priority: the env contract (EDL_TPU_SLICES > 1 — the operator pinned
    the slice count on the job, e.g. a GKE multi-slice JobSet) beats
    hardware auto-detect (`jax.devices()` slice_index, present on TPU
    multi-slice), which beats the flat single-slice default. The env
    path lets CPU worlds and single-slice dev boxes EMULATE multi-slice
    for tests/dryruns; the detect path needs no configuration at all.
    """
    from edl_tpu.parallel.mesh import SliceTopology, detect_slice_topology

    env = env or TrainerEnv.from_environ()
    if devices is None:
        devices = jax.devices()
    if env.n_slices > 1:
        if len(devices) % env.n_slices != 0:
            raise ValueError(
                f"{len(devices)} devices not divisible by "
                f"EDL_TPU_SLICES={env.n_slices}")
        return SliceTopology(env.n_slices, len(devices) // env.n_slices)
    detected = detect_slice_topology(devices)
    return detected if detected.is_multi_slice else SliceTopology(
        1, len(devices))


def make_mesh_from_env(spec=None, env: TrainerEnv | None = None,
                       devices: list | None = None):
    """The mesh a launched trainer should train on: hybrid ICI×DCN when
    the world is (or is declared) multi-slice, flat otherwise. Elastic
    resizes re-form correctly because MeshSpec resolves against
    (n_slices, chips_per_slice), not a flat device count."""
    from edl_tpu.parallel import mesh as mesh_lib

    topo = slice_topology(env, devices)
    if topo.is_multi_slice:
        return mesh_lib.make_hybrid_mesh(spec, topo, devices=devices)
    return mesh_lib.make_mesh(spec, devices=devices)


def reform_world(env: TrainerEnv) -> TrainerEnv:
    """Tear down the collective layer and re-form it with a NEW topology
    — the mesh-re-formation primitive of the reform state machine
    (collective/reform.py): a surviving process keeps running, drops
    only `jax.distributed`, and rejoins the re-formed world under its
    new (rank, world, coordinator). The persistent compilation cache is
    (re)enabled first so the re-formed world's unchanged programs skip
    their re-jits — a genuinely-new shape costs exactly one compile.

    Single-process worlds (world_size <= 1) only tear down; there is
    nothing to rejoin — the caller rebuilds its local mesh and the
    in-process jit cache carries the re-jit story.

    Failures (a coordinator that never comes up, a runtime that cannot
    re-initialize) surface as the typed ``EdlError`` the reform
    machine's mesh-reform phase downgrades on — never a bare crash.
    """
    from edl_tpu.utils.exceptions import EdlError
    global _initialized
    enable_compilation_cache()
    try:
        if _initialized:
            jax.distributed.shutdown()
            _initialized = False
        if env.world_size > 1:
            log.info("re-forming world: rank=%d/%d coordinator=%s",
                     env.rank, env.world_size, env.coordinator)
            jax.distributed.initialize(
                coordinator_address=env.coordinator,
                num_processes=env.world_size,
                process_id=env.rank)
            _initialized = True
    except Exception as exc:  # noqa: BLE001 — typed for the reform
        # machine's mesh-reform downgrade (stop-resume), never a crash
        raise EdlError(f"mesh re-formation failed: {exc}") from exc
    return env


def is_initialized() -> bool:
    return _initialized


def shutdown() -> None:
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False
