"""`check_joyai` with one fault put into the program or into the
reference, or the reference computed in a lower precision: what each of
the checker's readings is when something is wrong, which is what its
limits have to refuse. Takes the checker's arguments, so a configuration
whose `reference.checker` names this module sends a fault through the
driver's own `reference_check` (benchmark/tests/test_joyai_cell.py
does, at a tiny size); by hand, on made shards:

    EDL_BENCH_CONTROL=<fault> python -m benchmark.tools.joyai_controls <config.json> <data_dir> <step>
    python -m benchmark.tools.joyai_controls <config.json> --all [seed [fault ...]]

The second form makes one shard from ``seed``, runs every fault (or
those named) in a process of its own (a chip belongs to one process) and
prints a line each. No time is taken; a number from here is never a
device metric.

A fault of the mathematics is put into the reference (`joyai_plain`'s
small functions are the seams), which the program is then far from; a
fault of a size or of the training step into the program.
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
    trainer draws them, by the configuration as it is."""
    from benchmark.reference import check_joyai as check
    from edl_tpu.models.transformer import Transformer
    build, draw = check.program_config, check.seeded_variables
    check.program_config = lambda config: dataclasses.replace(
        build(config), **changed)
    check.seeded_variables = lambda program, config: draw(
        Transformer(build(config)), config)


def _plain(**seams):
    from benchmark.reference import joyai_plain as plain
    for name, fault in seams.items():
        setattr(plain, name, fault(plain, getattr(plain, name)))


def _reference_rounded(name: str):
    """The reference computed on matrices rounded to a narrower type.
    The gradient is the last thing the checker does with the parameters
    it hands over, and at the cell's size the chip does not hold them,
    their rounded copy and the gradient's program at once (8.00 GB asked
    for at its load, 7.65 free; PERF.md section 6, PR 55): there each
    matrix is let go as its rounded copy is made."""
    import jax
    import jax.numpy as jnp
    dtype = getattr(jnp, name)

    def on_rounded(let_go: bool):
        def rounded(w):
            if w.ndim < 2:
                return w
            narrow = w.astype(dtype).astype(jnp.float32)
            if let_go:
                w.delete()
            return narrow
        return lambda plain, fn: lambda params, *rest, **kw: fn(
            jax.tree.map(rounded, params), *rest, **kw)
    _plain(batch_losses=on_rounded(False), batch_grads=on_rounded(True))


def _turned(how):
    """`joyai_plain.turned` with ``how(plain, q_nope, q_pe, k_nope,
    k_pe, theta, eps)`` in its place."""
    _plain(turned=lambda plain, real: (
        lambda q_nope, q_pe, k_nope, k_pe, hp: how(
            plain, q_nope, q_pe, k_nope, k_pe, hp["theta"], hp["eps"])))


def _rotate_half(x, theta):
    """(S, D): rotate-half as the vector lies, no de-interleaving."""
    import jax.numpy as jnp
    s, d = x.shape
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] / theta ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :d // 2], x[:, d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _latents_without_norms(plain, real):
    def latents(x, p, hp):
        s, heads = x.shape[0], hp["n_head"]
        n, rank = hp["nope"], hp["kv_rank"]
        q = ((x @ p["q_a"]) @ p["q_b"]).reshape(s, heads, -1)
        c_kv = x @ p["kv_a"]
        kv = (c_kv[:, :rank] @ p["kv_b"]).reshape(s, heads, -1)
        return (q[..., :n], q[..., n:], kv[..., :n], c_kv[:, rank:],
                kv[..., n:])
    return latents


def _bias_in_the_gates(plain, real):
    import jax

    def route(x, p, hp):
        scores = jax.nn.sigmoid(x @ p["router"]) + p["bias"]
        _, idx = jax.lax.top_k(scores, hp["top_k"])
        return plain.gates(scores, idx, hp), idx, scores
    return route


def _cut_the_gradient_into_h():
    import jax

    from edl_tpu.models import transformer as tfm
    real = tfm._mtp_hidden
    tfm._mtp_hidden = lambda cfg, embed, h, tokens, train: real(
        cfg, embed, jax.lax.stop_gradient(h), tokens, train)


FAULTS = {
    "none": lambda: None,
    "reference_bfloat16": lambda: _reference_rounded("bfloat16"),
    "reference_float8_e4m3": lambda: _reference_rounded("float8_e4m3fn"),
    "rope_on_the_whole_head": lambda: _turned(
        lambda plain, qn, qp, kn, kp, theta, eps: tuple(
            plain.rope(t, theta) for t in (qn, qp, kn, kp))),
    "pairs_on_q_halves_on_k": lambda: _turned(
        lambda plain, qn, qp, kn, kp, theta, eps: (
            qn, plain.rope(qp, theta), kn, _rotate_half(kp, theta))),
    "k_pe_normed": lambda: _turned(
        lambda plain, qn, qp, kn, kp, theta, eps: (
            qn, plain.rope(qp, theta), kn,
            plain.rope(plain.rms(kp, 1.0, eps), theta))),
    "scale_from_the_value_size": lambda: _plain(
        softmax_scale=lambda plain, real: lambda hp: hp["nope"] ** -0.5),
    "latent_norms_left_out": lambda: _plain(latents=_latents_without_norms),
    "mtp_fed_t_i": lambda: _plain(
        next_tokens=lambda plain, real: lambda tokens: tokens),
    "mtp_target_off_by_one": lambda: _plain(
        ahead_pairs=lambda plain, real: lambda ahead, tokens: (
            ahead[:-2], tokens[1:-1])),
    "lambda_0": lambda: _fields(mtp_weight=0.0),
    "mtp_gradient_into_h_cut": _cut_the_gradient_into_h,
    "no_shared_expert": lambda: _fields(moe_shared=0),
    "bias_added_to_the_gates": lambda: _plain(route=_bias_in_the_gates),
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
                [sys.executable, "-m", "benchmark.tools.joyai_controls",
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
                           else 2290051100, argv[3:])
    FAULTS[os.environ.get("EDL_BENCH_CONTROL", "none")]()
    from benchmark.reference import check_joyai as check
    return check.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
