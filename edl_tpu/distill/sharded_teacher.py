"""Multi-chip teacher serving: one server process drives ALL local chips.

The round-4 teacher served one chip per process; a pod-slice teacher
(v5e-8) then needed 8 processes and 8 registry entries. Here the teacher
forward is jitted over a LOCAL `jax.sharding.Mesh`: parameters land
tp/fsdp-sharded per the model's logical-axis annotations
(parallel/sharding.py rules — how an ERNIE-class teacher larger than one
chip's HBM is served at all), the batch splits over the data axes, and
XLA's SPMD partitioner materializes the collectives over ICI. One
process, one registry entry, N chips.

The reference's analogue is Paddle Serving's multi-card deployment
(README.md:74-92 serves the ERNIE teacher on multi-GPU hosts); the
redesign rides the same mesh machinery as training instead of a serving
framework.

Composes with the compressed wire (teacher_server.compress_outputs):
``serve_topk`` runs `lax.top_k` INSIDE the sharded jit — on a
vocab-parallel (tp) head XLA computes the distributed top-k before
anything crosses to host — and packs (idx, val) into ONE fp32 array so
latency-bound links pay a single device->host fetch.

Usage (library; the teacher_server CLI exposes --local-mesh for the
dp-replicated flavor):

    mesh = make_mesh(MeshSpec({"dp": 2, "tp": 4}))
    variables = init_sharded(lambda: model.init(...), mesh)
    predict, meta = sharded_predict_fn(
        lambda v, x: model.apply(v, x, train=False), variables, mesh,
        serve_topk=16, classes=1000)
    TeacherServer(predict, compressed_meta=meta).start()
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from edl_tpu.parallel import mesh as mesh_lib
from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.distill.sharded_teacher")


def sharded_predict_fn(apply_fn, variables, mesh: Mesh, *,
                       input_key: str = "image",
                       output_key: str = "logits",
                       batch_axes: tuple[str, ...] = ("dp", "fsdp"),
                       input_dtype=None,
                       serve_topk: int = 0,
                       classes: int | None = None):
    """Build a `TeacherServer` predict_fn over a local mesh.

    apply_fn(variables, x) -> logits (any rank; classes on the LAST
    axis). Returns ``(predict, compressed_meta)`` — meta is None without
    ``serve_topk``, else the announcement TeacherServer attaches so
    dense clients scatter-expand transparently.

    Request rows need not divide the data axes: the batch pads to the
    next multiple (rows beyond the caller's are dropped after the
    forward), so the Batcher's power-of-two buckets and ragged tails
    both serve.
    """
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    data_sharding = mesh_lib.data_sharding(mesh, axes or None)
    dp = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if serve_topk and classes is None:
        raise ValueError("serve_topk needs `classes` (the dense width) "
                         "for the client-side expansion announcement")
    if serve_topk and serve_topk > classes:
        # lax.top_k rejects k > axis size — clamp instead of an opaque
        # XLA error on the first predict (same guard as the CLI path)
        log.warning("serve_topk %d > %d classes; clamping", serve_topk,
                    classes)
        serve_topk = int(classes)

    @jax.jit
    def fwd(variables, x):
        logits = apply_fn(variables, x)
        if not serve_topk:
            return logits
        val, idx = lax.top_k(logits.astype(jnp.float32), serve_topk)
        # ONE packed fp32 fetch instead of two tiny device->host pulls
        idx_bits = lax.bitcast_convert_type(idx.astype(jnp.int32),
                                            jnp.float32)
        return jnp.concatenate([idx_bits, val], axis=-1)

    def predict(feeds: dict) -> dict:
        x = np.asarray(feeds[input_key])
        if input_dtype is not None:
            x = x.astype(input_dtype)
        rows = x.shape[0]
        pad = (-rows) % dp
        if pad:
            x = np.concatenate(
                [x, np.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
        placed = jax.device_put(x, data_sharding)
        out = np.asarray(fwd(variables, placed))[:rows]
        if not serve_topk:
            return {output_key: out.astype(np.float32)}
        idx = np.ascontiguousarray(out[..., :serve_topk]).view(np.int32)
        val = out[..., serve_topk:].astype(np.float16)
        return {output_key + ".idx": idx, output_key + ".val": val}

    meta = None
    if serve_topk:
        meta = {output_key: {"topk": serve_topk, "classes": int(classes),
                             "values": "<f2"}}
    log.info("sharded teacher predict over mesh %s (data axes %s, x%d)",
             dict(mesh.shape), axes, dp)
    return predict, meta


def parse_local_mesh(spec: str) -> Mesh:
    """``"dp=4,tp=2"`` -> a local-device Mesh (teacher CLI flag)."""
    axes = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        axes[name.strip()] = int(size)
    return mesh_lib.make_mesh(mesh_lib.MeshSpec(axes))
