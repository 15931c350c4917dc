"""The reference's side of `correct` for a trained Trinity-Mini share
(`model_type: afmoe`: sliding-window and global attention mixed, sigmoid
routing with a balancing bias, a shared expert, a chip's share of the
experts). A child of the benchmark, started after the trainer has ended,
which draws the trainer's parameters with the program's own initialiser
on the configuration's `trainer_seed`, takes the batch of one global
step from the shards through the program's loader (`trainer_draw.py`),
and prints one JSON line. Its readings, each against
`trinity_mini_plain` (float32, attention a masked softmax a head, the
held experts by a plain loop):

`loss`, what the trainer's `loss=` holds: the mean next-token
cross-entropy of the whole batch, the balancing bias at zero as the
trainer starts it. The driver compares it with the loss the trainer
logged (`reference.loss_tolerance`).

Every other reading is taken at a drawn bias (normal, std `BIAS_STD`,
centred, from the `trainer_seed`): state a run reaches, where a bias
that is forgotten, or that weighs the gates, shows; at the zeros of a
fresh start it would not.

`token_loss_rms_diff`: the program's own forward pass as the trainer
builds it (its activation type, its attention kernels with the window,
the sorted dispatch over the held experts) against the plain one, token
by token, on the first `TOKEN_ROWS` sequences: at the cell's batch that
is every token. The driver compares it with
`reference.token_loss_rms_tolerance`. `routing_diff_share` is the share
of the program's assignments that the reference, routing in float32,
did not make (near-ties that bfloat16 activations turn).

`grad_rel_err`, `routed_grad_rel_err`, `update_rel_err`,
`bias_update_err`, `timed_loss_diff`: the program the window times. The train step as `lm_train` builds it
(`make_train_step` on `lm_loss_fused` or `lm_loss_fn` as the flags say,
`lm_train.make_optimizer`, the whole batch, remat as the flags say,
donated state, the bias in `batch_stats`) runs twice on that batch. The
schedule's first learning rate is 0, so the first step fills the moments
and moves the bias and nothing else: AdamW's bias-corrected first moment
after it is the gradient the compiled step made (streamed CE, the
windowed flash backward, the grouped matmuls' backward, remat's replay).
Routing is discrete, so the plain gradient is taken with the program's
own experts given (`jax.grad` of the plain loss on them, as its forward
pass above chose them); `grad_rel_err` is the largest over the
parameter leaves of |it - the plain one| / |the plain one|, and a
gradient of zeros reads 1. The routers' and the held experts' leaves are
not among those: their gradients are sums over the rows an expert was
given, the compiled step rounds its activations in another order than
the forward pass above, and an assignment that one of the two turns the
other way at a near-tie moves a whole row from one expert's sum to
another's (a hundredth of the assignments: a fifth of noise a leaf in
the last layer). They are pooled over the layers into
`routed_grad_rel_err` with a limit of its own. `bias_update_err` is
|the bias after the first step - the published rule applied to the
counts of those experts| / |the rule's own change|; a bias left
unchanged reads 1. The second step has a learning rate: `update_rel_err`
is |(parameters after - before) - AdamW's update written out here from
the step's own moments| / |that update| over all leaves together; a
state left unchanged reads 1. `timed_loss_diff` is the first step's own
loss against the plain one, under `loss_tolerance`.

The accepted driver (`drivers/train_steady_ref.py`) compares the first
two readings and no other. So this child holds the others to the limits
of the configuration's `reference` itself, names what failed under
`refused`, and then withholds `loss` (NaN), which the driver's
comparison turns into `correct: false`; `reference_loss` always holds
the number.

    python -m benchmark.reference.check_trinity_mini <config.json> <data_dir> <step>
"""

from __future__ import annotations

import json
import sys

# optax.adamw as lm_train.make_optimizer calls it, and the leaves' errors
from benchmark.reference.check_granite_hybrid import (B1, B2, EPS,
                                                      WEIGHT_DECAY,
                                                      leaf_errors, pooled)
from benchmark.reference.trainer_draw import seeded_variables

TOKEN_ROWS = 3  # sequences compared token by token: all of the batch
BIAS_STD = 0.05


def _flag(flags: list, name: str, default=None):
    return flags[flags.index(name) + 1] if name in flags else default


def program_config(config: dict):
    """The `TransformerConfig` `lm_train` builds from the file's flags:
    the sizes `harness/job.lm_args` passes and the afmoe flags; the head
    size, the key/value heads, an expert's width and the routing are
    `afmoe_config`'s own."""
    import jax.numpy as jnp

    from edl_tpu.models.transformer import afmoe_config
    run = config["run"]
    flags = run["flags"]
    sizes = {field: int(_flag(flags, flag)) for field, flag in (
        ("n_experts", "--n-experts"), ("moe_top_k", "--moe-top-k"),
        ("experts_held", "--experts-held"), ("window", "--window"),
        ("n_dense_layers", "--dense-layers"))
        if flag in flags}
    if "--layer-types" in flags:
        sizes["layer_types"] = tuple(
            {"s": "sliding", "f": "full"}[c]
            for c in _flag(flags, "--layer-types"))
    return afmoe_config(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=run["seq_len"],
        remat=_flag(flags, "--remat") == "on",
        dtype=jnp.bfloat16 if "--bf16" in flags else jnp.float32, **sizes)


def reference_hp(config: dict, cfg) -> dict:
    """The reference's sizes from the file's own (source) keys; what the
    file does not hold (a tiny rehearsal file) from the program's."""
    return {"n_head": config["n_head"],
            "n_kv_head": config.get("num_key_value_heads", cfg.kv_heads),
            "eps": config.get("rms_norm_eps", cfg.norm_eps),
            "theta": float(config.get("rope_theta", cfg.rope_theta)),
            "window": config.get("sliding_window", cfg.window),
            "top_k": config.get("num_experts_per_tok", cfg.moe_top_k),
            "route_scale": config.get("route_scale", cfg.moe_route_scale),
            "first_expert": cfg.experts_offset,
            "layer_types": [k.split("_")[0] for k in config["layer_types"]]}


def drawn_bias(stats: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    leaves, tree = jax.tree.flatten(stats)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    drawn = [BIAS_STD * jax.random.normal(k, b.shape, jnp.float32)
             for k, b in zip(keys, leaves)]
    return jax.tree.unflatten(tree, [b - jnp.mean(b) for b in drawn])


def timed_program(config: dict, program, tree, stats, batch,
                  per_epoch: int):
    """Two steps of the trainer's train step on ``batch`` from ``tree``
    and the biases ``stats`` (donated: gone afterwards). Returns the
    first step's loss, the gradient it made (host, the program's names),
    the biases after it (host), `update_rel_err` of the second, and the
    parameters as they were (host)."""
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
        tx=make_optimizer(run["lr"], total, run["warmup_steps"]),
        batch_stats=stats)
    step = make_train_step(lm_loss_fused if "--fused-loss" in run["flags"]
                           else lm_loss_fn, donate=True)
    tokens = {"tokens": jnp.asarray(batch, jnp.int32)}
    state, first = step(state, tokens)
    adam = next(s for s in state.opt_state if hasattr(s, "mu"))
    grads = jax.tree.map(lambda m: np.asarray(m) / np.float32(1 - B1),
                         adam.mu)
    biases = jax.device_get(state.batch_stats)
    state, _ = step(state, tokens)
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
    loss = float(first["loss"])
    for leaf in jax.tree.leaves(state):  # the reference needs the room
        if hasattr(leaf, "delete"):
            leaf.delete()
    return loss, grads, biases, update, before


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

    from benchmark.reference import trinity_mini_plain as plain
    from benchmark.reference.trainer_draw import step_batch
    from edl_tpu.models.transformer import Transformer
    phase = phase_log()  # seconds after the imports

    batch, per_epoch = step_batch(config, data_dir, step)
    cfg = program_config(config)
    program = Transformer(cfg)
    seeded = seeded_variables(program, config)
    tree = seeded["params"]
    hp = reference_hp(config, cfg)
    phase("parameters drawn")
    # the loss the trainer logs: its bias starts at zero
    fresh, _ = plain.batch_losses(plain.from_program(tree), batch, hp)
    loss = float(np.mean(np.concatenate(fresh)))
    del fresh
    stats = drawn_bias(seeded["batch_stats"], config["run"]["trainer_seed"])
    theirs, their_experts = plain.batch_losses(
        plain.from_program(tree, stats), batch, hp)
    drawn_loss = float(np.mean(np.concatenate(theirs)))
    phase("the plain forward, at both biases")

    @jax.jit
    def program_forward(tree, stats, toks):
        out, sown = program.apply(
            {"params": tree, "batch_stats": stats}, toks, train=True,
            mutable=["intermediates"])
        logp = jax.nn.log_softmax(out[:, :-1].astype(jnp.float32))
        chosen = [sown["intermediates"][f"block{i}"]["moe_mlp"]["moe_idx"][0]
                  for i in range(cfg.n_layers) if cfg.moe_layer(i)]
        return -jnp.take_along_axis(logp, toks[:, 1:, None],
                                    axis=-1)[..., 0], chosen
    # one sequence at a time: the logits of one are 0.8 GB twice over
    mine, my_experts = [], []
    for row in batch:
        got, chosen = program_forward(tree, stats,
                                      jnp.asarray(row[None], jnp.int32))
        mine.append(np.asarray(got)[0])
        my_experts.append([np.asarray(c) for c in chosen])
    rows = min(TOKEN_ROWS, len(batch))
    rms = float(np.sqrt(np.mean(np.square(
        np.stack(mine[:rows]) - np.stack(theirs[:rows])))))
    strangers = sum(int((~(a[:, :, None] == b[:, None, :]).any(-1)).sum())
                    for mine_, theirs_ in zip(my_experts, their_experts)
                    for a, b in zip(mine_, theirs_))
    assignments = sum(a.size for row in my_experts for a in row)
    routing_diff = strangers / assignments
    phase("the program's forward")

    before_stats = jax.device_get(stats)
    timed_loss, grads, biases, update, before = timed_program(
        config, program, tree, stats, batch, per_epoch)
    stats = jax.device_put(before_stats)  # the step took its own
    phase("the trainer's step, twice")
    wanted = plain.batch_grads(
        plain.from_program(jax.device_put(before), stats), batch, hp,
        chosen=my_experts)
    got = plain.from_program(grads)
    for b in got["blocks"]:
        b.pop("bias", None)
    errors = leaf_errors(got, wanted)
    phase("the plain gradient")
    for name, e, r, along in errors:
        print(f"gradient {name}: |diff| {e:.4g} / |plain| {r:.4g} = "
              f"{e / r if r else float('nan'):.4g}, along the plain one "
              f"{along:.5f}", file=sys.stderr)
    routed = [row for row in errors
              if "['router']" in row[0] or "['experts']" in row[0]]
    others = [row for row in errors if row not in routed]
    grad, where = max((e / r if r else float(e > 0), name)
                      for name, e, r, _ in others)
    routed_grad = pooled(routed)
    # the rule on the counts of the experts the program chose
    layers = [i for i in range(cfg.n_layers) if cfg.moe_layer(i)]
    off = moved = 0.0
    for at, i in enumerate(layers):
        counts = sum(np.bincount(row[at].ravel(), minlength=cfg.n_experts)
                     for row in my_experts)
        b0 = before_stats[f"block{i}"]["moe_mlp"]["expert_bias"]
        want = np.asarray(plain.bias_after(
            jnp.asarray(b0), jnp.asarray(counts, jnp.float32),
            config.get("load_balance_coeff", 0.001)))
        b1 = biases[f"block{i}"]["moe_mlp"]["expert_bias"]
        off += float(np.sum(np.square(b1 - want)))
        moved += float(np.sum(np.square(want - b0)))
    bias_update = (off / moved) ** 0.5
    timed_diff = abs(timed_loss - drawn_loss)
    refused = [f"{name} {value:.6g} > {limits[key]}" for name, value, key in (
        ("grad_rel_err", grad, "grad_rel_tolerance"),
        ("routed_grad_rel_err", routed_grad, "routed_grad_rel_tolerance"),
        ("update_rel_err", update, "update_rel_tolerance"),
        ("bias_update_err", bias_update, "bias_update_tolerance"),
        ("routing_diff_share", routing_diff, "routing_diff_tolerance"),
        ("timed_loss_diff", timed_diff, "loss_tolerance"))
        if not value <= limits[key]]
    dev = jax.devices()[0]
    phase("done: " + sentence(programs()))
    print(json.dumps({
        "loss": float("nan") if refused else loss, "reference_loss": loss,
        "step": step, "rows": int(len(batch)), "token_loss_rms_diff": rms,
        "program_loss": float(np.mean(np.concatenate(mine))),
        "drawn_bias_loss": drawn_loss, "timed_loss": timed_loss,
        "timed_loss_diff": timed_diff, "routing_diff_share": routing_diff,
        "grad_rel_err": grad, "grad_rel_err_leaf": where,
        "routed_grad_rel_err": routed_grad,
        "grad_rel_err_all_leaves": pooled(errors),
        "grad_rel_err_worst_leaves": [
            [name, round(e / r, 4)] for name, e, r, _ in sorted(
                errors, key=lambda row: -row[1] / row[2])[:12]],
        "grad_along_plain": sum(a * r * r for _, _, r, a in errors)
        / sum(r * r for _, _, r, _ in errors),
        "update_rel_err": update, "bias_update_err": bias_update,
        "refused": refused, "programs": programs(),
        "platform": dev.platform,
        "kind": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
