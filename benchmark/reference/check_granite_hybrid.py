"""The reference's side of `correct` for a trained hybrid state-space
language model whose configuration file carries the source's keys
(`model_type: granitemoehybrid`). A child of the benchmark, started
after the trainer has ended, which draws the trainer's parameters with
the program's own initialiser on the configuration's `trainer_seed`,
takes the batch of one global step from the shards through the
program's loader (`trainer_draw.py`), and prints one JSON line. Its
readings, each against `granite_hybrid_plain` (float32, the state-space
layers walked one position at a time):

`loss`, what the trainer's `loss=` holds: the mean next-token
cross-entropy of the whole batch. The driver compares it with the loss
the trainer logged (`reference.loss_tolerance`).

`token_loss_rms_diff`: the program's own forward pass as the trainer
builds it (its activation type, the chunked scan, its attention kernel
with the key/value heads repeated) against the plain one, token by
token, on the first `TOKEN_ROWS` sequences: at the cell's batch of 2
that is every token. The driver compares it with
`reference.token_loss_rms_tolerance`.

`grad_rel_err`, `step_size_grad_rel_err`, `update_rel_err` and
`timed_loss_diff`: the program the window times. The
train step as `lm_train` builds it (`make_train_step` on `lm_loss_fused`
or `lm_loss_fn` as the flags say, `lm_train.make_optimizer`, the whole
batch, remat as the flags say, donated state) runs twice on that batch
from the seeded parameters. The schedule's first learning rate is 0, so
the first step fills the moments and leaves the parameters where they
were, and the second repeats the gradient: AdamW's bias-corrected first
moment is then the gradient the compiled step made (streamed CE on the
tied table, flash backward, the scan's written-out backward, remat's
replay), whatever the optimizer fused. `grad_rel_err` is the largest
over the parameter leaves of |that gradient - jax.grad of the plain
loss| / |the plain one|; a gradient of zeros reads 1. The mixers'
`A_log` and `dt_bias` are not among those leaves: their gradients come
from a difference of two nearly equal sums, which bfloat16 operands
leave with a fifth to two fifths of noise a leaf, so they are pooled
over the layers into `step_size_grad_rel_err` with a limit of its own.
`update_rel_err` is |(parameters after - before) - AdamW's update
written out here from the step's own moments| / |that update| over all
leaves together; a state left unchanged reads 1. `timed_loss_diff` is the step's own loss
against the plain one, under `loss_tolerance`.

The accepted driver (`drivers/train_steady_ref.py`) compares the first
two readings and no other. So this child holds the other four to the
limits of the configuration's `reference` itself, names what failed
under `refused`, and then withholds `loss` (NaN), which the driver's
comparison turns into `correct: false`; `reference_loss` always holds
the number.

    python -m benchmark.reference.check_granite_hybrid <config.json> <data_dir> <step>
"""

from __future__ import annotations

import json
import sys

TOKEN_ROWS = 2  # sequences compared token by token: 16,382 tokens at S=8192
# optax.adamw as lm_train.make_optimizer calls it
B1, B2, EPS, WEIGHT_DECAY = 0.9, 0.999, 1e-8, 0.01


def program_config(config: dict):
    """The `TransformerConfig` `lm_train` builds from the file's flags:
    the sizes `harness/job.lm_args` passes and `--layer-types`; the
    mixers' sizes are `granite_hybrid_config`'s own."""
    import jax.numpy as jnp

    from edl_tpu.models.transformer import granite_hybrid_config
    run = config["run"]
    flags = run["flags"]
    kinds = {}
    if "--layer-types" in flags:
        kinds["layer_types"] = tuple(
            {"m": "mamba", "a": "attention"}[c]
            for c in flags[flags.index("--layer-types") + 1])
    remat = "--remat" in flags and flags[flags.index("--remat") + 1] == "on"
    return granite_hybrid_config(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=run["seq_len"], remat=remat,
        dtype=jnp.bfloat16 if "--bf16" in flags else jnp.float32, **kinds)


def reference_hp(config: dict) -> dict:
    return {"n_head": config["n_head"],
            "n_kv_head": config["num_key_value_heads"],
            "eps": config["rms_norm_eps"],
            "attention_multiplier": config["attention_multiplier"],
            "embedding_multiplier": float(config["embedding_multiplier"]),
            "residual_multiplier": config["residual_multiplier"],
            "logits_scaling": float(config["logits_scaling"])}


def timed_program(config: dict, program, tree, batch, per_epoch: int):
    """Two steps of the trainer's train step on ``batch`` from ``tree``
    (donated: gone afterwards). Returns the step's loss, the gradient it
    made (host, the program's names), and `update_rel_err`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from edl_tpu.examples.lm_train import make_optimizer
    from edl_tpu.models.transformer import lm_loss_fn, lm_loss_fused
    from edl_tpu.train.state import TrainState
    from edl_tpu.train.step import make_train_step
    run = config["run"]
    before = jax.device_get(tree)
    total = per_epoch * run["epochs"]
    warmup = min(run["warmup_steps"], max(1, total // 10))
    state = TrainState.create(
        apply_fn=program.apply, params=tree,
        tx=make_optimizer(run["lr"], total, run["warmup_steps"]))
    step = make_train_step(lm_loss_fused if "--fused-loss" in run["flags"]
                           else lm_loss_fn, donate=True)
    tokens = {"tokens": jnp.asarray(batch, jnp.int32)}
    state, first = step(state, tokens)
    state, second = step(state, tokens)
    adam = next(s for s in state.opt_state if hasattr(s, "mu"))
    lr = run["lr"] / warmup  # the schedule at its second step

    @jax.jit
    def update_error(before, after, mu, nu):
        def leaf(t0, t1, m, v):
            m, v = m / (1 - B1 ** 2), v / (1 - B2 ** 2)
            want = -lr * (m / (jnp.sqrt(v) + EPS) + WEIGHT_DECAY * t0)
            return jnp.stack([jnp.sum(jnp.square(t1 - t0 - want)),
                              jnp.sum(jnp.square(want))])
        err, ref = sum(jax.tree.leaves(jax.tree.map(
            leaf, before, after, mu, nu)))
        return jnp.sqrt(err / ref)
    update = float(update_error(jax.device_put(before), state.params,
                                adam.mu, adam.nu))
    grads = jax.tree.map(lambda m: np.asarray(m) / np.float32(1 - B1 ** 2),
                         adam.mu)
    losses = (float(first["loss"]), float(second["loss"]))
    for leaf in jax.tree.leaves(state):  # the reference needs the room
        if hasattr(leaf, "delete"):
            leaf.delete()
    return losses, grads, update, before


# the scan's step sizes and decay rates: their gradients come from
# d(cs), rows minus columns of the masked product, two nearly equal sums
STEP_SIZE_LEAVES = ("['A_log']", "['dt_bias']")


def leaf_errors(mine: dict, theirs: dict) -> list[tuple]:
    """(name, |mine - theirs|, |theirs|, <mine, theirs> / |theirs|^2) of
    every leaf: the last is 1 where an error is noise and off 1 where
    it is a factor."""
    import jax
    import numpy as np
    flat = jax.tree_util.tree_flatten_with_path(theirs)[0]
    out = []
    for (path, ref), got in zip(flat, jax.tree.leaves(mine)):
        r2 = float(np.vdot(ref, ref))
        out.append((jax.tree_util.keystr(path),
                    float(np.linalg.norm(got - ref)), r2 ** 0.5,
                    float(np.vdot(got, ref)) / r2 if r2 else float("nan")))
    return out


def pooled(errors: list[tuple]) -> float:
    return (sum(e * e for _, e, _, _ in errors)
            / sum(r * r for _, _, r, _ in errors)) ** 0.5


def main(argv: list[str]) -> int:
    config_path, data_dir, step = argv
    step = int(step)
    with open(config_path) as f:
        config = json.load(f)
    limits = config["reference"]
    import jax
    import jax.numpy as jnp
    import numpy as np
    # The harness gives JAX_COMPILATION_CACHE_DIR, a directory of the
    # reference children's own and never the trainer's: what this child
    # compiles it keeps there, and the next run of the checkout reads it
    # (child_cache.py).
    from benchmark.reference.child_cache import (keep_programs, phase_log,
                                                 sentence)
    programs = keep_programs()

    from benchmark.reference import granite_hybrid_plain as plain
    from benchmark.reference.trainer_draw import seeded_params, step_batch
    from edl_tpu.models.transformer import Transformer
    phase = phase_log()  # seconds after the imports

    batch, per_epoch = step_batch(config, data_dir, step)
    program = Transformer(program_config(config))
    tree = seeded_params(program, config)
    hp = reference_hp(config)
    phase("parameters drawn")
    theirs = plain.batch_losses(plain.from_program(tree), batch, hp)
    loss = float(np.mean(np.concatenate(theirs)))
    phase("the plain forward")

    @jax.jit
    def program_forward(tree, toks):
        out = program.apply({"params": tree}, toks, train=True)
        logp = jax.nn.log_softmax(out[:, :-1].astype(jnp.float32))
        return -jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1)[..., 0]
    # one sequence at a time: the logits of two are 1.6 GB twice over
    mine = np.stack([np.asarray(program_forward(
        tree, jnp.asarray(row[None], jnp.int32)))[0]
        for row in batch[:TOKEN_ROWS]])
    rms = float(np.sqrt(np.mean(np.square(
        mine - np.stack(theirs[:TOKEN_ROWS])))))
    phase("the program's forward")

    timed_losses, grads, update, before = timed_program(
        config, program, tree, batch, per_epoch)
    phase("the trainer's step, twice")
    wanted = plain.batch_grads(
        plain.from_program(jax.device_put(before)), batch, hp)
    errors = leaf_errors(plain.from_program(grads), wanted)
    phase("the plain gradient")
    for name, e, r, along in errors:
        print(f"gradient {name}: |diff| {e:.4g} / |plain| {r:.4g} = "
              f"{e / r if r else float('nan'):.4g}, along the plain one "
              f"{along:.5f}", file=sys.stderr)
    step_size = [row for row in errors if row[0].endswith(STEP_SIZE_LEAVES)]
    others = [row for row in errors if row not in step_size]
    grad, where = max((e / r if r else float(e > 0), name)
                      for name, e, r, _ in others)
    step_size_grad = pooled(step_size) if step_size else 0.0
    timed_diff = max(abs(v - loss) for v in timed_losses)
    refused = [f"{name} {value:.6g} > {limits[key]}" for name, value, key in (
        ("grad_rel_err", grad, "grad_rel_tolerance"),
        ("step_size_grad_rel_err", step_size_grad,
         "step_size_grad_rel_tolerance"),
        ("update_rel_err", update, "update_rel_tolerance"),
        ("timed_loss_diff", timed_diff, "loss_tolerance"))
        if not value <= limits[key]]
    dev = jax.devices()[0]
    phase("done: " + sentence(programs()))
    print(json.dumps({
        "loss": float("nan") if refused else loss, "reference_loss": loss,
        "step": step, "rows": int(len(batch)), "token_loss_rms_diff": rms,
        "program_loss": float(mine.mean()), "timed_loss": timed_losses[0],
        "timed_loss_diff": timed_diff, "grad_rel_err": grad,
        "grad_rel_err_leaf": where, "step_size_grad_rel_err": step_size_grad,
        "grad_rel_err_all_leaves": pooled(errors),
        "grad_along_plain": sum(a * r * r for _, _, r, a in errors)
        / sum(r * r for _, _, r, _ in errors),
        "update_rel_err": update, "refused": refused,
        "programs": programs(), "platform": dev.platform,
        "kind": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
