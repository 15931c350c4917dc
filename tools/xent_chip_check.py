"""The streamed CE alone, on the chip, at a cell's real shapes.

Compares `edl_tpu.ops.fused_xent.streamed_lm_xent` with another
checkout's op of that name (the parent commit's, unpacked with
`git archive` into a directory `.gitignore` lists) and with a plain
float32 `jax.grad` of `log_softmax` at matmul precision highest: loss,
d_hidden and d_kernel, max and rms differences, and the time of one
value_and_grad call of each. One JSON line a shape, also appended to
chiprun_out/xent_chip_check-<start time>.jsonl (a call's own file: what
comes back from the chip replaces a file of the same name). Needs a TPU.

    chiprun -- python tools/xent_chip_check.py _checkout/parent [block_rows ...]
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from edl_tpu.ops import fused_xent  # noqa: E402

# (cell, sequences, positions, d, vocabulary): hidden bf16, kernel float32
SHAPES = (("lm_d8.steady", 6, 2048, 2048, 50257),
          ("olmoe_d1.steady", 4, 4096, 2048, 50304),
          ("lm_full.fsdp4_steady, a chip's share", 2, 2048, 2048, 50257))
SLAB = 2048  # rows of the plain reference at a time: (SLAB, V) float32


def other_op(checkout: str):
    spec = importlib.util.spec_from_file_location(
        "other_fused_xent",
        os.path.join(checkout, "edl_tpu", "ops", "fused_xent.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.streamed_lm_xent


def timed(fn, *args, repeats: int = 10) -> tuple[float, object]:
    out = jax.block_until_ready(fn(*args))  # compiles
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3, out


def plain(h, k, t, n):
    """float32, precision highest, SLAB rows at a time; rows with a
    negative target do not count."""

    def slab_loss(hs, k, ts):
        logp = jax.nn.log_softmax(jnp.dot(
            hs.astype(jnp.float32), k, precision=jax.lax.Precision.HIGHEST))
        ll = jnp.take_along_axis(logp, jnp.maximum(ts, 0)[:, None], 1)[:, 0]
        return -jnp.sum(jnp.where(ts >= 0, ll, 0.0)) / n

    step = jax.jit(jax.value_and_grad(slab_loss, argnums=(0, 1)))
    loss, dh, dk = 0.0, [], jnp.zeros(k.shape, jnp.float32)
    for lo in range(0, h.shape[0], SLAB):
        part, (dhs, dks) = step(h[lo:lo + SLAB], k, t[lo:lo + SLAB])
        loss, dk = loss + float(part), dk + dks
        dh.append(dhs)
    return loss, jnp.concatenate(dh), dk


def diff(a, b) -> dict:
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return {"max": float(jnp.abs(d).max()),
            "rms": float(jnp.sqrt(jnp.mean(d * d))),
            "rms_of_ref": float(jnp.sqrt(jnp.mean(
                b.astype(jnp.float32) ** 2)))}


def main(argv: list[str]) -> int:
    dev = jax.devices()[0]
    old = other_op(argv[0])
    blockings = [int(b) for b in argv[1:]] or [None]
    os.makedirs("chiprun_out", exist_ok=True)
    out_path = f"chiprun_out/xent_chip_check-{int(time.time())}.jsonl"
    for cell, b, s, d, v in SHAPES:
        key = jax.random.PRNGKey(b * s)
        h = jax.random.normal(key, (b, s, d), jnp.float32).astype(jnp.bfloat16)
        k = jax.random.normal(jax.random.fold_in(key, 1), (d, v)) / d ** 0.5
        t = jax.random.randint(jax.random.fold_in(key, 2), (b, s), 0, v)
        # the new op takes the batch whole, last positions not counting;
        # the old one the rows that count
        t_new = t.at[:, -1].set(-1)
        h_old, t_old = h[:, :-1].reshape(-1, d), t[:, :-1].reshape(-1)
        n = b * (s - 1)
        line = {"cell": cell, "rows": n, "d": d, "vocab": v,
                "device": dev.device_kind}

        f_old = jax.jit(jax.value_and_grad(old, argnums=(0, 1)))
        line["old_ms"], (l_old, (dh_old, dk_old)) = timed(
            f_old, h_old, k, t_old)
        line["old_loss_only_ms"], _ = timed(jax.jit(old), h_old, k, t_old)
        l_ref, dh_ref, dk_ref = plain(h_old, k, t_old, n)
        line["old_vs_plain"] = {
            "loss": abs(float(l_old) - l_ref),
            "d_hidden": diff(dh_old, dh_ref), "d_kernel": diff(dk_old, dk_ref)}
        for rows in blockings:
            tag = f"new[{fused_xent.blocking(b * s, v, rows)[1]}]"
            f_new = jax.jit(jax.value_and_grad(
                lambda h, k, t, rows=rows: fused_xent.streamed_lm_xent(
                    h, k, t, rows), argnums=(0, 1)))
            ms, (l_new, (dh_new, dk_new)) = timed(f_new, h, k, t_new)
            dh_new = dh_new[:, :-1].reshape(-1, d)
            line[tag] = {
                "ms": ms,
                "vs_old": {"loss": abs(float(l_new) - float(l_old)),
                           "d_hidden": diff(dh_new, dh_old),
                           "d_kernel": diff(dk_new, dk_old)},
                "vs_plain": {"loss": abs(float(l_new) - l_ref),
                             "d_hidden": diff(dh_new, dh_ref),
                             "d_kernel": diff(dk_new, dk_ref)}}
            line[tag]["loss_only_ms"], _ = timed(jax.jit(
                lambda h, k, t, rows=rows: fused_xent.streamed_lm_xent(
                    h, k, t, rows)), h, k, t_new)
        stats = dev.memory_stats() or {}
        line["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        print(json.dumps(line), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"needs a TPU, found {jax.devices()[0].platform}")
    sys.exit(main(sys.argv[1:]))
