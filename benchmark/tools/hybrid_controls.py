"""`check_granite_hybrid` with one fault put into the program, or the
reference computed in a lower precision: what each of the checker's
readings is when something is wrong, which is what its limits have to
refuse. Takes the checker's arguments, so a configuration whose
`reference.checker` names this module sends a fault through the
driver's own `reference_check` (benchmark/tests/
test_granite_hybrid_cell.py does, at a tiny size); by hand, on made
shards:

    EDL_BENCH_CONTROL=<fault> python -m benchmark.tools.hybrid_controls <config.json> <data_dir> <step>
    python -m benchmark.tools.hybrid_controls <config.json> --all [seed [fault ...]]

The second form makes one shard from ``seed``, runs every fault (or
those named) in a process of its own (a chip belongs to one process) and prints a line
each. No time is taken; a number from here is never a device metric.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def fault_env(fault: str) -> dict:
    """The environment of one fault's process: the fault's name, and the
    reference children's compile cache, as the driver gives it. Sharing
    it with the honest child is sound: a fault that patches a function
    changes the program that holds it and so that program's key, and a
    fault that rounds the reference's matrices changes arguments, which
    are in no key (`reference/child_cache.py`)."""
    from benchmark.harness.cell import REF_CACHE
    return {**os.environ, "EDL_BENCH_CONTROL": fault,
            "JAX_COMPILATION_CACHE_DIR": os.path.join(ROOT, REF_CACHE)}


def _program(**changed):
    """The program's configuration with fields replaced."""
    from benchmark.reference import check_granite_hybrid as check
    build = check.program_config
    check.program_config = lambda config: dataclasses.replace(
        build(config), **changed)


def _reference_rounded(name: str):
    """The reference computed on matrices rounded to a narrower type."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import granite_hybrid_plain as plain
    dtype = getattr(jnp, name)

    def on_rounded(fn):
        return lambda params, *rest: fn(jax.tree.map(
            lambda w: w.astype(dtype).astype(jnp.float32)
            if w.ndim >= 2 else w, params), *rest)
    plain.batch_losses = on_rounded(plain.batch_losses)
    plain.batch_grads = on_rounded(plain.batch_grads)


def _scan_forgets():
    """The chunked scan without what one chunk hands the next."""
    from edl_tpu.ops import ssd
    forward = ssd._forward

    def forgetful(x, dt, a, b, c):
        _, prev = forward(x, dt, a, b, c)
        _, _, m, u, *_ = ssd._local(x, dt, a, b, c)
        return ssd._dot("bchij,bcjhp->bcihp", m, u), prev
    ssd._forward = forgetful


def _scan_backward(fault):
    """The scan's written-out backward with one gradient spoilt; its
    forward, and so every forward comparison, is as it was."""
    from edl_tpu.ops import ssd
    backward = ssd._ssd_bwd
    ssd._ssd.defvjp(ssd._ssd_fwd, lambda chunk, res, dy: fault(
        *backward(chunk, res, dy)))


def _optimizer(**changed):
    from edl_tpu.examples import lm_train
    make = lm_train.make_optimizer
    lm_train.make_optimizer = lambda lr, total, warmup, fused=None: make(
        changed.get("lr", lr), total, warmup, fused)


FAULTS = {
    "none": lambda: None,
    "reference_bfloat16": lambda: _reference_rounded("bfloat16"),
    "reference_float8_e4m3": lambda: _reference_rounded("float8_e4m3fn"),
    "attention_multiplier_1_over_8": lambda: _program(attn_scale=None),
    "no_residual_multiplier": lambda: _program(residual_scale=1.0),
    "embedding_multiplier_1": lambda: _program(embed_scale=1.0),
    "chunk_states_dropped": _scan_forgets,
    # dx, d_dt, d_a, db, dc
    "scan_backward_no_step_size_gradient": lambda: _scan_backward(
        lambda dx, d_dt, d_a, db, dc: (dx, 0 * d_dt, d_a, db, dc)),
    "scan_backward_no_decay_gradient": lambda: _scan_backward(
        lambda dx, d_dt, d_a, db, dc: (dx, d_dt, 0 * d_a, db, dc)),
    "scan_backward_dB_dC_swapped": lambda: _scan_backward(
        lambda dx, d_dt, d_a, db, dc: (dx, d_dt, d_a, dc, db)),
    "state_left_unchanged": lambda: _optimizer(lr=0.0),
}


def every_fault(config_path: str, seed: int, faults: list[str]) -> int:
    from benchmark.harness.shards import make_shards
    with open(config_path) as f:
        config = json.load(f)
    with tempfile.TemporaryDirectory() as tmp:
        make_shards(tmp, 1, 2 * config["run"]["global_batch"],
                    config["run"]["seq_len"], config["vocab_size"], seed)
        for fault in faults or FAULTS:
            out = subprocess.run(
                [sys.executable, "-m", "benchmark.tools.hybrid_controls",
                 config_path, tmp, "1"], capture_output=True, text=True,
                env=fault_env(fault))
            line = out.stdout.strip().splitlines()[-1:] or [
                json.dumps({"failed": out.stderr[-1500:]})]
            print(json.dumps({"fault": fault, **json.loads(line[0])}),
                  flush=True)
    return 0


def main(argv: list[str]) -> int:
    if argv[1] == "--all":
        return every_fault(argv[0], int(argv[2]) if len(argv) > 2
                           else 2290033100, argv[3:])
    FAULTS[os.environ.get("EDL_BENCH_CONTROL", "none")]()
    from benchmark.reference import check_granite_hybrid as check
    return check.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
