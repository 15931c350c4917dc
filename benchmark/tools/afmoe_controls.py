"""`check_trinity_mini` with one fault put into the program, or the
reference computed in a lower precision: what each of the checker's
readings is when something is wrong, which is what its limits have to
refuse. Takes the checker's arguments, so a configuration whose
`reference.checker` names this module sends a fault through the
driver's own `reference_check` (benchmark/tests/test_trinity_cell.py
does, at a tiny size); by hand, on made shards:

    EDL_BENCH_CONTROL=<fault> python -m benchmark.tools.afmoe_controls <config.json> <data_dir> <step>
    python -m benchmark.tools.afmoe_controls <config.json> --all [seed [fault ...]]

The second form makes one shard from ``seed``, runs every fault (or
those named) in a process of its own (a chip belongs to one process) and
prints a line each. No time is taken; a number from here is never a
device metric.
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


def _program(change):
    """The program's configuration, changed; its parameters drawn as the
    trainer draws them, by the configuration as it is."""
    from benchmark.reference import check_trinity_mini as check
    from edl_tpu.models.transformer import Transformer
    build, draw = check.program_config, check.seeded_variables
    check.program_config = lambda config: change(build(config))
    check.seeded_variables = lambda program, config: draw(
        Transformer(build(config)), config)


def _fields(**changed):
    _program(lambda cfg: dataclasses.replace(cfg, **changed))


def _reference_rounded(name: str):
    """The reference computed on matrices rounded to a narrower type."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import trinity_mini_plain as plain
    dtype = getattr(jnp, name)

    def on_rounded(fn):
        return lambda params, *rest, **kw: fn(jax.tree.map(
            lambda w: w.astype(dtype).astype(jnp.float32)
            if w.ndim >= 2 else w, params), *rest, **kw)
    plain.batch_losses = on_rounded(plain.batch_losses)
    plain.batch_grads = on_rounded(plain.batch_grads)


def _bias_in_the_gates():
    """The gates taken from score + bias, which the rule forbids: put
    into the reference, which the program is then far from."""
    import jax

    from benchmark.reference import trinity_mini_plain as plain
    gates = plain.gates

    def route(x, p, hp):
        scores = jax.nn.sigmoid(x @ p["router"]) + p["bias"]
        _, idx = jax.lax.top_k(scores, hp["top_k"])
        return gates(scores, idx, hp), idx, scores
    plain.route = route


FAULTS = {
    "none": lambda: None,
    "reference_bfloat16": lambda: _reference_rounded("bfloat16"),
    "reference_float8_e4m3": lambda: _reference_rounded("float8_e4m3fn"),
    "no_gate": lambda: _fields(attn_gate=False),
    "rope_on_the_global_layer": lambda: _program(
        lambda cfg: dataclasses.replace(cfg, layer_types=tuple(
            "attention" if k == "full" else k for k in cfg.layer_types))),
    "window_halved": lambda: _program(
        lambda cfg: dataclasses.replace(cfg, window=cfg.window // 2)),
    "bias_added_to_the_gates": _bias_in_the_gates,
    "no_shared_expert": lambda: _fields(moe_shared=0),
    "bias_left_unchanged": lambda: _fields(moe_bias_rate=1e-12),
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
                [sys.executable, "-m", "benchmark.tools.afmoe_controls",
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
    from benchmark.reference import check_trinity_mini as check
    return check.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
