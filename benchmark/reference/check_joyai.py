"""The reference's side of `correct` for a trained JoyAI-LLM-Flash share
(`model_type: joyai_llm_flash`: latent attention with keys of 192 and
values of 128, sigmoid routing with a balancing bias and a shared
expert, a chip's share of the experts, one multi-token-prediction module
trained through a second loss on the same head). A child of the
benchmark, started after the trainer has ended, which draws the
trainer's parameters with the program's own initialiser on the
configuration's `trainer_seed`, takes the batch of one global step from
the shards through the program's loader (`trainer_draw.py`), and prints
one JSON line. Its readings, each against `joyai_plain` (float32,
attention a masked softmax a head with the shared rotary key indexed,
the held experts by a plain loop, the module and both losses):

`loss`, what the trainer's `loss=` holds: the mean next-token
cross-entropy of the whole batch plus `mtp_loss_weight` times the
module's mean cross-entropy against the token after the next, the
balancing biases at zero as the trainer starts them (`main_loss` and
`mtp_loss` hold the two terms). The driver compares it with the loss
the trainer logged (`reference.loss_tolerance`).

Every other reading is taken at drawn biases (normal, std `BIAS_STD`,
centred, from the `trainer_seed`): state a run reaches, where a bias
that is forgotten, or that weighs the gates, shows.

`token_loss_rms_diff`, `mtp_token_loss_rms_diff`: the program's own
forward pass as the trainer builds it (its activation type, the flash
kernels at keys of 192 and values of 128, the sorted dispatch over the
held experts, the module behind the final norm) against the plain one,
token by token, the main head's S - 1 losses a row and the module's
S - 2. The driver compares the first with
`reference.token_loss_rms_tolerance`; this child holds the second to
`mtp_token_loss_rms_tolerance`. `routing_diff_share` is the share of the
program's assignments (every expert layer, the module's too) that the
reference, routing in float32, did not make.

`grad_rel_err`, `routed_grad_rel_err`, `update_rel_err`,
`bias_update_err`, `timed_loss_diff`, `timed_mtp_loss_diff`: the
program the window times. The train step as `lm_train` builds it
(`make_train_step` on `lm_loss_fused` or `lm_loss_fn` as the flags say,
`lm_train.make_optimizer`, the whole batch, remat as the flags say,
donated state, the biases in `batch_stats`) runs twice on that batch.
The schedule's first learning rate is 0, so the first step fills the
moments and moves the biases and nothing else: AdamW's bias-corrected
first moment after it is the gradient the compiled step made (both
streamed sweeps, the flash backward at two head sizes, the grouped
matmuls' backward, remat's replay, the module's loss reaching the main
blocks through the final norm). Routing is discrete, so the plain
gradient of L_main + lambda L_mtp is taken with the program's own
experts given. `grad_rel_err` is the largest over the parameter leaves
outside the routed experts of |it - the plain one| / |the plain one|;
the routers' and the held experts' leaves are pooled over the layers
into `routed_grad_rel_err` (their gradients are sums over the rows an
expert was given, and a near-tie that the compiled step turns the other
way moves a whole row between sums). `bias_update_err` is |the biases'
move in the first step - the published rule's on the counts of those
experts|, both less their mean over a layer's experts (the program
centres its step; a constant added to every expert's bias changes no
top-k), over |the rule's own centred move|; a bias left unchanged reads
1. The second step has a learning rate: `update_rel_err` is |(parameters
after - before) - AdamW's update written out here from the step's own
moments| / |that update| over all leaves together. `timed_loss_diff`
and `timed_mtp_loss_diff` are the first step's own `loss` and `mtp_loss`
against the plain ones, under `loss_tolerance`.

The accepted driver (`drivers/train_steady_ref.py`) compares `loss` and
`token_loss_rms_diff` and no other. So this child holds the others to
the limits of the configuration's `reference` itself, names what failed
under `refused`, and then withholds `loss` (NaN), which the driver's
comparison turns into `correct: false`; `reference_loss` always holds
the number.

    python -m benchmark.reference.check_joyai <config.json> <data_dir> <step>
"""

from __future__ import annotations

import json
import sys

# optax.adamw as lm_train.make_optimizer calls it, and the leaves' errors
from benchmark.reference.check_granite_hybrid import (B1, B2, EPS,
                                                      WEIGHT_DECAY,
                                                      leaf_errors, pooled)
from benchmark.reference.check_trinity_mini import (_flag, drawn_bias,
                                                    seeded_variables)


def program_config(config: dict):
    """The `TransformerConfig` `lm_train` builds from the file's flags:
    the sizes `harness/job.lm_args` passes and the joyai flags; the
    ranks, the head sizes, an expert's width and the routing are
    `joyai_config`'s own."""
    import jax.numpy as jnp

    from edl_tpu.models.transformer import joyai_config
    run = config["run"]
    flags = run["flags"]
    sizes = {field: int(_flag(flags, flag)) for field, flag in (
        ("n_experts", "--n-experts"), ("moe_top_k", "--moe-top-k"),
        ("experts_held", "--experts-held"),
        ("n_dense_layers", "--dense-layers")) if flag in flags}
    return joyai_config(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=run["seq_len"],
        remat=_flag(flags, "--remat") == "on",
        dtype=jnp.bfloat16 if "--bf16" in flags else jnp.float32, **sizes)


def reference_hp(config: dict, cfg) -> dict:
    """The reference's sizes from the file's own (source) keys; what the
    file does not hold (a tiny rehearsal file) from the program's."""
    return {"n_head": config["n_head"],
            "eps": config.get("rms_norm_eps", cfg.norm_eps),
            "theta": float(config.get("rope_theta", cfg.rope_theta)),
            "nope": config.get("qk_nope_head_dim", cfg.qk_nope_head_dim),
            "rope": config.get("qk_rope_head_dim", cfg.qk_rope_head_dim),
            "kv_rank": config.get("kv_lora_rank", cfg.kv_lora_rank),
            "top_k": config.get("num_experts_per_tok", cfg.moe_top_k),
            "route_scale": config.get("routed_scaling_factor",
                                      cfg.moe_route_scale),
            "first_expert": cfg.experts_offset,
            "mtp_weight": config.get("mtp_loss_weight", cfg.mtp_weight)}


def expert_layers(cfg) -> list[tuple]:
    """Where the expert layers' state lies in the program's trees, in
    the order the reference routes them: the blocks', then the
    module's."""
    return [(f"block{i}",) for i in range(cfg.n_layers)
            if cfg.moe_layer(i)] + [("mtp", "block")] * cfg.mtp_layers


def _at(tree, path):
    for name in path:
        tree = tree[name]
    return tree["moe_mlp"]


def timed_program(config: dict, program, tree, stats, batch,
                  per_epoch: int):
    """Two steps of the trainer's train step on ``batch`` from ``tree``
    and the biases ``stats`` (donated: gone afterwards). Returns the
    first step's metrics, the gradient it made (host, the program's
    names), the biases after it (host), `update_rel_err` of the second,
    and the parameters as they were (host)."""
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
    first = {k: float(v) for k, v in first.items() if np.ndim(v) == 0}
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
    for leaf in jax.tree.leaves(state):  # the reference needs the room
        if hasattr(leaf, "delete"):
            leaf.delete()
    return first, grads, biases, update, before


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

    from benchmark.reference import joyai_plain as plain
    from benchmark.reference.trainer_draw import step_batch
    from edl_tpu.models.transformer import Transformer
    phase = phase_log()  # seconds after the imports

    batch, per_epoch = step_batch(config, data_dir, step)
    cfg = program_config(config)
    program = Transformer(cfg)
    seeded = seeded_variables(program, config)
    tree = seeded["params"]
    hp = reference_hp(config, cfg)
    layers = expert_layers(cfg)
    phase("parameters drawn")

    def means(main, ahead):
        return (float(np.mean(np.concatenate(main))),
                float(np.mean(np.concatenate(ahead))))
    # the loss the trainer logs: its biases start at zero
    main_loss, mtp_loss = means(*plain.batch_losses(
        plain.from_program(tree), batch, hp)[:2])
    loss = main_loss + hp["mtp_weight"] * mtp_loss
    stats = drawn_bias(seeded["batch_stats"], config["run"]["trainer_seed"])
    their_main, their_ahead, their_experts = plain.batch_losses(
        plain.from_program(tree, stats), batch, hp)
    drawn_main, drawn_mtp = means(their_main, their_ahead)
    phase("the plain forward, at both biases")

    @jax.jit
    def program_forward(tree, stats, toks):
        (out, ahead), sown = program.apply(
            {"params": tree, "batch_stats": stats}, toks, train=True,
            mtp=True, mutable=["intermediates"])

        def ce(logits, targets):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.take_along_axis(logp, targets[..., None],
                                        axis=-1)[..., 0]
        chosen = [_at(sown["intermediates"], path)["moe_idx"][0]
                  for path in layers]
        return (ce(out[:, :-1], toks[:, 1:]),
                ce(ahead[:, :-2], toks[:, 2:]), chosen)
    # one sequence at a time: the logits of one are 0.5 GB twice over
    my_main, my_ahead, my_experts = [], [], []
    for row in batch:
        one, two, chosen = program_forward(
            tree, stats, jnp.asarray(row[None], jnp.int32))
        my_main.append(np.asarray(one)[0])
        my_ahead.append(np.asarray(two)[0])
        my_experts.append([np.asarray(c) for c in chosen])

    def rms_of(mine, theirs):
        return float(np.sqrt(np.mean(np.square(
            np.stack(mine) - np.stack(theirs)))))
    rms, mtp_rms = rms_of(my_main, their_main), rms_of(my_ahead, their_ahead)
    strangers = sum(int((~(a[:, :, None] == b[:, None, :]).any(-1)).sum())
                    for mine_, theirs_ in zip(my_experts, their_experts)
                    for a, b in zip(mine_, theirs_))
    assignments = sum(a.size for row in my_experts for a in row)
    routing_diff = strangers / assignments
    phase("the program's forward")

    before_stats = jax.device_get(stats)
    first, grads, biases, update, before = timed_program(
        config, program, tree, stats, batch, per_epoch)
    stats = jax.device_put(before_stats)  # the step took its own
    phase("the trainer's step, twice")
    params = jax.device_put(before)
    del before  # 2.7 GB the host needs for the plain gradient's tree
    wanted = plain.batch_grads(plain.from_program(params, stats), batch,
                               hp, chosen=my_experts)
    got = plain.from_program(grads)
    for b in (*got["blocks"], got["mtp"]["block"]):
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
    def rel(e, r):  # a leaf the reference gives no gradient reads 0 or 1
        return e / r if r else float(e > 0)
    grad, where = max((rel(e, r), name) for name, e, r, _ in others)
    routed_grad = pooled(routed)
    # the rule on the counts of the experts the program chose, both
    # moves less their mean
    off = moved = 0.0
    for at, path in enumerate(layers):
        counts = sum(np.bincount(row[at].ravel(), minlength=cfg.n_experts)
                     for row in my_experts)
        b0 = _at(before_stats, path)["expert_bias"]
        want = np.asarray(plain.bias_after(
            jnp.asarray(b0), jnp.asarray(counts, jnp.float32),
            config.get("bias_update_rate", cfg.moe_bias_rate))) - b0
        have = _at(biases, path)["expert_bias"] - b0
        want, have = want - want.mean(), have - have.mean()
        off += float(np.sum(np.square(have - want)))
        moved += float(np.sum(np.square(want)))
    bias_update = (off / moved) ** 0.5
    timed_diff = abs(first["loss"] - (
        drawn_main + hp["mtp_weight"] * drawn_mtp))
    timed_mtp_diff = abs(first["mtp_loss"] - drawn_mtp)
    refused = [f"{name} {value:.6g} > {limits[key]}" for name, value, key in (
        ("mtp_token_loss_rms_diff", mtp_rms, "mtp_token_loss_rms_tolerance"),
        ("grad_rel_err", grad, "grad_rel_tolerance"),
        ("routed_grad_rel_err", routed_grad, "routed_grad_rel_tolerance"),
        ("update_rel_err", update, "update_rel_tolerance"),
        ("bias_update_err", bias_update, "bias_update_tolerance"),
        ("routing_diff_share", routing_diff, "routing_diff_tolerance"),
        ("timed_loss_diff", timed_diff, "loss_tolerance"),
        ("timed_mtp_loss_diff", timed_mtp_diff, "loss_tolerance"))
        if not value <= limits[key]]
    dev = jax.devices()[0]
    phase("done: " + sentence(programs()))
    print(json.dumps({
        "loss": float("nan") if refused else loss, "reference_loss": loss,
        "main_loss": main_loss, "mtp_loss": mtp_loss,
        "step": step, "rows": int(len(batch)), "token_loss_rms_diff": rms,
        "mtp_token_loss_rms_diff": mtp_rms,
        "program_loss": float(np.mean(np.concatenate(my_main))),
        "program_mtp_loss": float(np.mean(np.concatenate(my_ahead))),
        "drawn_bias_loss": drawn_main, "drawn_bias_mtp_loss": drawn_mtp,
        "timed_loss": first["loss"], "timed_mtp_loss": first["mtp_loss"],
        "timed_loss_diff": timed_diff,
        "timed_mtp_loss_diff": timed_mtp_diff,
        "routing_diff_share": routing_diff,
        "grad_rel_err": grad, "grad_rel_err_leaf": where,
        "routed_grad_rel_err": routed_grad,
        "grad_rel_err_all_leaves": pooled(errors),
        "grad_rel_err_worst_leaves": [
            [name, round(rel(e, r), 4)] for name, e, r, _ in sorted(
                errors, key=lambda row: -rel(row[1], row[2]))[:12]],
        "grad_along_plain": sum(a * r * r for _, _, r, a in errors)
        / sum(r * r for _, _, r, _ in errors),
        "update_rel_err": update, "bias_update_err": bias_update,
        "refused": refused, "programs": programs(),
        "platform": dev.platform,
        "kind": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
