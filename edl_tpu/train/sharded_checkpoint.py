"""Per-shard checkpoint serialization with resharding restore.

The sharded half of the checkpoint story (capability target: the
reference's fleet checkpoints, doc/fault_tolerance.md:1-67, scaled to
states that never fit one host): at save, every process writes only the
array shards it owns (deduplicated by replica id) plus a chunk index; at
restore, each device's shard is assembled from whichever saved chunks
intersect it — saved-mesh and restore-mesh shapes are independent, so an
fsdp x tp state saved on 8 devices re-places onto 4 (or 32) by the
target's sharding rules. Chunk reads go through numpy memory-maps, so
restore materializes per-target-shard regions, never the full array.
The planner is sharding-GENERIC: MoE expert tables (leading "expert"
logical axis -> ep, sharding.DEFAULT_RULES) are ordinary sharded
leaves here, so an ep resize (4 -> 2 experts-per-chip doubling, or
back) reshards expert tables through this same path — from disk or,
via ``restore_from_index`` with a peer-fetch loader, from donor
memory (collective/migration.py) with zero process restarts.

Save splits into two halves so the async checkpoint plane
(train/checkpoint.py `save_async`) can run them on different threads:
``snapshot_shards`` pulls this process's unique chunks to host (the only
part that must block the step loop), ``write_snapshot`` does the disk
I/O. ``save_sharded`` composes them, so sync and async saves produce
bitwise-identical files.

Restore reads regions through a per-file handle cache (each chunk is
np.load'ed once per restore, not once per intersecting region) and, when
``threads > 1``, prefetches every region on a thread pool before
assembly — elastic re-formation wants the restore off the downtime
budget as much as the save off the step loop.

Integrity: ``write_snapshot`` records a crc32 per chunk in the index;
restore verifies each chunk file once on first load (disk) — and the
migration plane verifies peer-fetched chunks against the donor
manifest's same numbers — raising the typed ``EdlCheckpointCorrupt``
so callers fall back (previous sealed version / another donor) instead
of loading garbage. ``EDL_TPU_CKPT_VERIFY=0`` disables.

The numpy-only file halves (chunk naming, crc, write, merge, region
assembly) live in ``train/ckpt_io.py`` so jax-free consumers — the
chaos plane's corruptor and soak workers — speak the same format; this
module re-exports them for compatibility and keeps the jax halves.

Layout inside a checkpoint directory:
  leaf{i}-o{start}_{start}...npy   one file per unique array chunk
  index.{process}.json             that process's chunk table + leaf specs

The format is self-describing; `is_sharded_dir` lets a manager
auto-detect it next to the replicated msgpack format.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any

import jax
import numpy as np

from edl_tpu.train import ckpt_io
from edl_tpu.train.ckpt_io import (  # noqa: F401 — compat re-exports
    ChunkFiles as _ChunkFiles,
    checksum_map,
    chunk_crc32,
    is_sharded_dir,
    merge_leaf_tables,
    read_region as _read_region,
    verify_enabled,
    write_snapshot,
)
from edl_tpu.utils import config
from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.train.sharded_checkpoint")

_INDEX_RE = ckpt_io._INDEX_RE
_chunk_name = ckpt_io.chunk_name
_slices_to_offset_shape = ckpt_io.slices_to_offset_shape
_merged_index = ckpt_io.read_merged_index


def _leaf_key(path) -> str:
    return jax.tree_util.keystr(path)


def may_alias_device(x) -> bool:
    """Can the host array that ``np.asarray(x)`` / ``jax.device_get(x)``
    returns be a VIEW of memory a later (donating) step overwrites?

    The checkpoint plane's one aliasing predicate: a caller that defers
    its write past the next train step copies exactly the arrays this
    says True for and hands the others on as fetched. A ``jax.Array`` in
    device memory of a non-``cpu`` platform is fetched into a host
    buffer the transfer itself allocated, which no step can touch. On
    the ``cpu`` platform the fetch of an aligned buffer is zero-copy,
    and a host memory kind (``pinned_host``, ``unpinned_host``) is
    host-addressable on any platform, so both count as aliasing; so does
    a numpy leaf, which ``np.asarray`` returns as the very object its
    owner may still write to.
    """
    if not isinstance(x, jax.Array):
        return True
    kind = x.sharding.memory_kind
    return (kind is not None and "host" in kind) or any(
        d.platform == "cpu" for d in x.devices())


def snapshot_shards(state: Any) -> dict:
    """Host snapshot of this process's unique shards of ``state``.

    The device->host pull half of ``save_sharded`` — the only part that
    must run on the training thread (and the only part whose duration
    the step loop pays under async saves). Returns ``{"leaves": table,
    "chunks": [(fname, array), ...], "may_alias": {fname, ...}}``. The
    chunks named in ``may_alias`` (`may_alias_device` of their source)
    can be views of live buffers — a caller that defers the write past
    the next train step must copy those first; the others are host
    memory of their own.
    """
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    chunks_out: list[tuple[str, np.ndarray]] = []
    may_alias: set[str] = set()
    table = []
    for i, (path, leaf) in enumerate(leaves):
        key = _leaf_key(path)
        if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
            shape = tuple(leaf.shape)
            dtype = str(leaf.dtype)
            chunks = []
            for shard in leaf.addressable_shards:
                if shard.replica_id != 0:
                    continue
                offset, size = _slices_to_offset_shape(shard.index, shape)
                fname = _chunk_name(i, offset)
                # One blocking fetch at a time: starting every shard's
                # transfer first (copy_to_host_async) made the fetch of
                # a 7.35 GB state 1.3-2.3 s LONGER on a v5e (PERF.md,
                # PR 25).
                chunks_out.append((fname, np.asarray(shard.data)))
                if may_alias_device(shard.data):
                    may_alias.add(fname)
                chunks.append({"offset": list(offset), "shape": list(size),
                               "file": fname})
        else:  # host scalar / numpy leaf — process 0 owns it whole
            arr = np.asarray(leaf)
            shape, dtype = tuple(arr.shape), str(arr.dtype)
            chunks = []
            if jax.process_index() == 0:
                offset = tuple(0 for _ in shape)
                fname = _chunk_name(i, offset)
                chunks_out.append((fname, arr))
                if may_alias_device(leaf):
                    may_alias.add(fname)
                chunks.append({"offset": list(offset),
                               "shape": list(arr.shape), "file": fname})
        table.append({"key": key, "shape": list(shape), "dtype": dtype,
                      "chunks": chunks})
    return {"leaves": table, "chunks": chunks_out, "may_alias": may_alias,
            "process_index": jax.process_index()}


def save_sharded(directory: str, state: Any) -> list[str]:
    """Write this process's unique shards of `state` into `directory`.

    Every process of the world must call this with the same state; chunks
    are deduplicated so each array region is written exactly once
    world-wide (the writer is the shard with replica_id == 0). Returns
    the basenames of the files THIS process wrote (its chunks + its index
    file) — what a non-shared-FS mirror must upload from this host.
    """
    return write_snapshot(directory, snapshot_shards(state))


def snapshot_nbytes(snap: dict) -> int:
    """Total payload bytes of a snapshot's chunks — what a donor advert
    quotes and a full peer restore moves over the wire.

    Accepts both chunk layouts: the ``snapshot_shards`` /
    ``snapshot_host_tree`` list of ``(fname, array)`` pairs and the
    ``sealed_snapshot`` fname->array dict. Counts bytes AS STORED, so
    quantized optimizer moments (train/fused_opt.py's int8 ``(q, scale,
    rq, rscale)`` planes — ordinary pytree leaves to this format) show
    their ~2x cut on disk and on the migration wire, not only in HBM:
    the codes are serialized and shipped, never a dequantized fp32
    copy."""
    chunks = snap["chunks"]
    arrays = chunks.values() if isinstance(chunks, dict) else (
        a for _, a in chunks)
    return int(sum(a.nbytes for a in arrays))


def snapshot_host_tree(state: Any) -> dict:
    """Leaf-table + full-array-chunk view of a HOST pytree.

    The replicated checkpoint payload (rank 0's `jax.device_get` tree)
    expressed in the same self-describing structure `snapshot_shards`
    emits: every leaf is one chunk covering the whole array, owned by
    process 0. This is what lets the state-migration plane serve
    replicated AND sharded snapshots through one region planner —
    a peer restoring from a replicated donor plans regions against this
    table exactly as it would against on-disk chunk indexes. Chunk
    crc32s are recorded here (not only at write time) so a replicated
    donor's manifest carries checksums for the peer-fetch verify."""
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    chunks_out: list[tuple[str, np.ndarray]] = []
    table = []
    for i, (path, leaf) in enumerate(leaves):
        arr = np.asarray(leaf)
        offset = tuple(0 for _ in arr.shape)
        fname = _chunk_name(i, offset)
        chunks_out.append((fname, arr))
        table.append({"key": _leaf_key(path), "shape": list(arr.shape),
                      "dtype": str(arr.dtype),
                      "chunks": [{"offset": list(offset),
                                  "shape": list(arr.shape),
                                  "file": fname,
                                  "crc32": chunk_crc32(arr)}]})
    return {"leaves": table, "chunks": chunks_out, "process_index": 0}


def restore_threads() -> int:
    """Region-read pool width for restore (the restore-side half of the
    elastic downtime budget). Env-tunable; defaults past 1 even on small
    hosts because the reads are mmap-page-in bound, not CPU bound."""
    configured = config.env_int("EDL_TPU_CKPT_RESTORE_THREADS", 0)
    if configured > 0:
        return configured
    return min(8, 2 * (os.cpu_count() or 1))


def _region_key(index: tuple, shape: tuple[int, ...]) -> tuple:
    return _slices_to_offset_shape(index, shape)


def restore_sharded(directory: str, target: Any,
                    threads: int | None = None) -> Any:
    """Re-place a sharded checkpoint onto `target`'s shardings.

    `target` is a pytree whose array leaves carry the DESTINATION sharding
    (materialized arrays on the new mesh, or jax.ShapeDtypeStruct with a
    `sharding` set) — typically the freshly initialized state of the new
    world. Leaves are assembled chunk-wise per target shard, so a state
    saved on one mesh shape restores onto any other.

    ``threads``: region-read pool width (default `restore_threads()`,
    env ``EDL_TPU_CKPT_RESTORE_THREADS``); every unique target region is
    prefetched concurrently before device placement, and 1 keeps the
    serial path. Chunk integrity is verified against the index's sealed
    crc32s (``EDL_TPU_CKPT_VERIFY``); corruption raises
    ``EdlCheckpointCorrupt`` — CheckpointManager.restore falls back to
    the previous sealed version on it.
    """
    merged = _merged_index(directory)
    files = _ChunkFiles(directory, crcs=checksum_map(merged))
    try:
        return restore_from_index(merged, files.load, target, threads)
    finally:
        files.close()


def restore_from_index(merged: dict[str, dict], load, target: Any,
                       threads: int | None = None) -> Any:
    """The resharding planner behind `restore_sharded`, with the chunk
    source abstracted: plan every unique (leaf, region) the TARGET's
    shardings need, read regions through ``load(fname) -> ndarray``
    (thread-pooled), assemble via `jax.make_array_from_callback`. The
    state-migration plane drives this with a peer-fetch loader so the
    SAME planner that reshards on-disk checkpoints reshards donor
    memory across the wire.
    """
    if threads is None:
        threads = restore_threads()
    leaves, treedef = jax.tree_util.tree_flatten_with_path(target)

    # Plan every unique region to read: one entry per (leaf, region) —
    # a dp-replicated target asks for the same region once per replica,
    # the cache below reads it once.
    plans = []   # (key, entry, sharding|None, leaf, [region indexes])
    for path, leaf in leaves:
        key = _leaf_key(path)
        entry = merged.get(key)
        if entry is None:
            raise KeyError(f"checkpoint has no leaf {key}")
        shape = tuple(entry["shape"])
        sharding = getattr(leaf, "sharding", None)
        if not isinstance(sharding, jax.sharding.NamedSharding):
            # Leaf without a mesh placement (eagerly created scalars like
            # opt-state counters, or host leaves): restore as host numpy —
            # uncommitted, so a following jit places it freely instead of
            # pinning it to one device of somebody else's mesh.
            sharding = None
        if isinstance(leaf, jax.Array) and sharding is not None:
            if tuple(leaf.shape) != shape:
                raise ValueError(
                    f"{key}: target shape {tuple(leaf.shape)} != saved "
                    f"{shape}")
            idx_map = sharding.addressable_devices_indices_map(shape)
            uniq = {_region_key(idx, shape): idx for idx in idx_map.values()}
            plans.append((key, entry, sharding, leaf, list(uniq.values())))
        else:
            plans.append((key, entry, None, leaf,
                          [tuple(slice(0, s) for s in shape)]))

    regions: dict[tuple, np.ndarray] = {}

    def read(entry, idx):
        k = (id(entry), _region_key(idx, tuple(entry["shape"])))
        regions[k] = _read_region(load, entry, idx)

    jobs = [(entry, idx) for _, entry, _, _, idxs in plans for idx in idxs]
    if threads > 1 and len(jobs) > 1:
        with ThreadPoolExecutor(max_workers=threads,
                                thread_name_prefix="edl-ckpt-read") as pool:
            # list() re-raises the first read error (coverage holes must
            # fail the restore loudly, threaded or not)
            list(pool.map(lambda j: read(*j), jobs))
    else:
        for j in jobs:
            read(*j)

    out = []
    for key, entry, sharding, leaf, idxs in plans:
        shape = tuple(entry["shape"])
        if sharding is not None:
            def region(idx, e=entry):
                k = (id(e), _region_key(idx, tuple(e["shape"])))
                if k not in regions:  # older-jax fallback: no prefetch plan
                    regions[k] = _read_region(load, e, idx)
                return regions[k]

            arr = jax.make_array_from_callback(shape, sharding, region)
            # preserve weak_type of scalars created by jit (e.g. step)
            out.append(arr.astype(leaf.dtype) if arr.dtype != leaf.dtype
                       else arr)
        else:
            full = regions[(id(entry), _region_key(idxs[0], shape))]
            out.append(full if shape else full[()])
    return jax.tree_util.tree_unflatten(treedef, out)
