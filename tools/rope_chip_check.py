"""The rotary kernel alone, on the chip, at the cells' real shapes.

Compares `edl_tpu.ops.rope.rotate` (compiled, not interpreted) with the
formula it stands for, `edl_tpu.models.transformer.rope`: the result
and the gradient of sum(rope(x) * g), the largest difference in units
of bfloat16's last place and the share of elements that differ, and the
time of one call of each, forward alone and forward + backward. The
kernel is timed on the head-major view it is given inside a step, (B*H,
S, 1, D), where its transposes are views, and the formula on (B, S, H,
D). One JSON line a shape and block size, also appended to
chiprun_out/rope_chip_check-<start time>.jsonl. Needs a TPU.

    chiprun -- python tools/rope_chip_check.py [rows a block ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from edl_tpu.models.transformer import rope  # noqa: E402
from edl_tpu.ops import rope as kernel  # noqa: E402

# (cell and tensor, B, S, H, D, theta, the block-diffusion index or not)
SHAPES = (("sdar_p1e16v8.steady q", 1, 16384, 32, 128, 1e6, True),
          ("sdar_p1e16v8.steady k", 1, 16384, 4, 128, 1e6, True),
          ("trinity_p1e16v8.steady q", 2, 8192, 32, 128, 1e4, False),
          ("trinity_p1e16v8.steady k", 2, 8192, 4, 128, 1e4, False),
          ("olmoe_d1.steady q and k", 4, 4096, 16, 128, 1e4, False))
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def timed(fn, *args, repeats: int = 20) -> tuple[float, object]:
    out = jax.block_until_ready(fn(*args))  # compiles
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3, out


def ulps(got, want) -> dict:
    """Differences in units of bfloat16's last place at ``want``'s size."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    spacing = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(
        jnp.abs(want), 2.0 ** -100))) - 7)   # the chip flushes subnormals
    d = jnp.abs(got - want) / spacing
    return {"max_ulps": float(d.max()), "share_differing": float(
        jnp.mean(d > 0))}


def main(argv: list[str]) -> int:
    dev = jax.devices()[0]
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak_bytes_per_s = json.load(f)[dev.device_kind]["hbm_bytes_per_s"]
    blockings = [int(r) for r in argv] or [kernel.ROWS]
    os.makedirs("chiprun_out", exist_ok=True)
    out_path = f"chiprun_out/rope_chip_check-{int(time.time())}.jsonl"
    for what, b, s, h, d, theta, two_copies in SHAPES:
        key = jax.random.PRNGKey(s + h)
        x = jax.random.normal(key, (b, s, h, d), jnp.float32).astype(
            jnp.bfloat16)
        g = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d),
                              jnp.float32).astype(jnp.bfloat16)
        at = jnp.arange(s) % (s // 2) if two_copies else None
        # (B, S, H, D) as the kernel's own view: one head a row of B*H
        flat = (b * h, s, 1, d)
        xt, gt = (t.transpose(0, 2, 1, 3).reshape(flat) for t in (x, g))

        def both(turn):
            def loss(x, g):
                return jnp.sum(turn(x).astype(jnp.float32)
                               * g.astype(jnp.float32))
            return jax.jit(turn), jax.jit(jax.grad(loss))

        f_plain, g_plain = both(lambda x: rope(x, theta, at))
        line = {"what": what, "shape": [b, s, h, d],
                "device": dev.device_kind}
        line["plain_fwd_ms"], y_plain = timed(f_plain, x)
        line["plain_bwd_ms"], dx_plain = timed(g_plain, x, g)
        least = 2 * x.size * 2 / peak_bytes_per_s * 1e3
        line["one_read_one_write_ms"] = least
        for rows in blockings:
            f_new, g_new = both(
                lambda x, rows=rows: kernel.rotate(x, theta, at, rows))
            fwd_ms, y = timed(f_new, xt)
            bwd_ms, dx = timed(g_new, xt, gt)
            y, dx = (t.reshape(b, h, s, d).transpose(0, 2, 1, 3)
                     for t in (y, dx))
            line[f"kernel[{rows}]"] = {
                "fwd_ms": fwd_ms, "bwd_ms": bwd_ms,
                "fwd_share_of_peak_bytes": least / fwd_ms,
                "fwd_vs_plain": ulps(y, y_plain),
                "bwd_vs_plain": ulps(dx, dx_plain)}
        print(json.dumps(line), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"needs a TPU, found {jax.devices()[0].platform}")
    sys.exit(main(sys.argv[1:]))
