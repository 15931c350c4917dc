"""`check_sdar` with one fault put into the program or into the
reference, or the reference computed in a lower precision: what each of
the checker's readings is when something is wrong, which is what its
limits have to refuse. Takes the checker's arguments, so a configuration
whose `reference.checker` names this module sends a fault through the
driver's own `reference_check` (benchmark/tests/test_sdar_cell.py does,
at a tiny size); by hand, on made shards:

    EDL_BENCH_CONTROL=<fault> python -m benchmark.tools.sdar_controls <config.json> <data_dir> <step>
    python -m benchmark.tools.sdar_controls <config.json> --all [seed [fault ...]]

The second form makes one shard from ``seed``, runs every fault (or
those named) in a process of its own (a chip belongs to one process) and
prints a line each. No time is taken; a number from here is never a
device metric.

A fault of the mask is put into the reference, which builds the mask
outright (three lines to change) and which the program is then far
from; a fault of the kernels' flag, the positions, the weights or the
gates into the program.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools.hybrid_controls import (_optimizer,  # noqa: E402
                                             fault_env)


def _fields(**changed):
    """The program's configuration, changed; its parameters drawn as the
    trainer draws them."""
    from benchmark.reference import check_sdar as check
    build = check.program_config
    check.program_config = lambda config: dataclasses.replace(
        build(config), **changed)


def _reference_rounded(name: str):
    """The reference computed on matrices rounded to a narrower type."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import sdar_plain as plain
    dtype = getattr(jnp, name)

    def on_rounded(fn):
        return lambda params, *rest, **kw: fn(jax.tree.map(
            lambda w: w.astype(dtype).astype(jnp.float32)
            if w.ndim >= 2 else w, params), *rest, **kw)
    plain.batch_losses = on_rounded(plain.batch_losses)
    plain.batch_grads = on_rounded(plain.batch_grads)


def _reference_mask(change):
    """The reference's mask from ``change(noised query?, clean key?,
    the query's block, the key's block, the place of each)`` instead."""
    import jax.numpy as jnp

    from benchmark.reference import sdar_plain as plain

    def visible(length, block):
        place = jnp.arange(2 * length)
        clean = place >= length
        b = (place % length) // block
        return change(~clean[:, None], clean[None, :], b[:, None],
                      b[None, :], place[:, None], place[None, :])
    plain.visible = visible


def _published(noised_q, clean_k, qb, kb, *_):
    return ((qb == kb) & (noised_q != clean_k)
            | (qb > kb) & clean_k & noised_q
            | (qb >= kb) & clean_k & ~noised_q)


def _kernels_never_strict():
    """The noised queries' call given at-or-before for strictly-before:
    the flag of the kernels, in the program."""
    from edl_tpu.models import blockdiff
    lse = blockdiff.flash_attention_lse
    blockdiff.flash_attention_lse = lambda *a, blocks, **kw: lse(
        *a, blocks=(blocks[0], False), **kw)


def _no_weight():
    """Every masked token counted once: the 1/t left out."""
    from edl_tpu.models import blockdiff
    draw = blockdiff.noised_batch

    def noised_batch(batch, mask_id):
        import jax.numpy as jnp
        return draw({**batch, "t": jnp.ones_like(batch["t"])}, mask_id)
    blockdiff.noised_batch = noised_batch


def _rope_by_place():
    """Positions 0..2L-1 along [noised ; clean]."""
    from edl_tpu.models import transformer
    rope = transformer.rope
    transformer.rope = lambda x, theta, positions=None: rope(x, theta)


FAULTS = {
    "none": lambda: None,
    "reference_bfloat16": lambda: _reference_rounded("bfloat16"),
    "reference_float8_e4m3": lambda: _reference_rounded("float8_e4m3fn"),
    "causal_over_2L": lambda: _reference_mask(
        lambda nq, ck, qb, kb, i, j: j <= i),
    "noised_sees_its_own_clean_block": lambda: _reference_mask(
        lambda nq, ck, qb, kb, *_: _published(nq, ck, qb, kb)
        | (qb == kb) & ck & nq),
    "clean_sees_a_noised_key": lambda: _reference_mask(
        lambda nq, ck, qb, kb, *_: _published(nq, ck, qb, kb)
        | (qb == kb) & ~ck & ~nq),
    "at_or_before_for_strictly_before": _kernels_never_strict,
    "weight_left_out": _no_weight,
    "rope_by_place_in_2L": _rope_by_place,
    "gates_not_renormalised": lambda: _fields(moe_renorm=False),
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
                [sys.executable, "-m", "benchmark.tools.sdar_controls",
                 config_path, tmp, "1"], capture_output=True, text=True,
                env=fault_env(fault))
            for text in out.stderr.splitlines():
                if text.startswith("[check"):  # the checker's phases
                    print(f"{fault}: {text}", file=sys.stderr, flush=True)
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
    from benchmark.reference import check_sdar as check
    return check.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
