"""The reference's side of `correct` for a share of SDAR-30B-A3B-Chat
(`model_type: sdar_moe`) trained by diffusion over blocks: a noised and
a clean copy of every row in one pass, attention by block index, the
loss on the masked tokens by 1/t. A child of the benchmark, started
after the trainer has ended, which draws the trainer's parameters with
the program's own initialiser on the configuration's `trainer_seed`,
takes the batch of one global step *and its noise* from the shards
through the program's loader (`edl_tpu.data.block_noise` on the rows'
indices), and prints one JSON line. Its readings, each against
`sdar_plain` (float32, the mask built outright on the 2L x 2L grid,
attention a masked softmax a head, the held experts by a plain loop):

`loss`, what the trainer's `loss=` holds: 1 / (rows x L) x the sum over
the masked tokens of CE / t. The driver compares it with the loss the
trainer logged (`reference.loss_tolerance`).

`token_loss_rms_diff`: the program's own forward pass as the trainer
builds it (its activation type, the flash kernels under the two block
masks, the own-block product and the join, the sorted dispatch over the
held experts) against the plain one, token by token over the masked
positions of every row. The driver compares it with
`reference.token_loss_rms_tolerance`. `early_token_loss_rms_diff` is the
same over the masked tokens of the first `EARLY` positions alone, where
a block's own four keys are a large part of what a query sees: a noised
query that also sees its own block's clean copy moves a late token's
loss by less than bfloat16 does, and an early one's by several times
that. `routing_diff_share` is the share
of the program's assignments (both copies, every layer) that the
reference, routing in float32, did not make.

`grad_rel_err`, `routed_grad_rel_err`, `update_rel_err`,
`timed_loss_diff`: the program the window times. The train step as
`lm_train` builds it (`make_train_step` on `lm_loss_fused` or
`lm_loss_fn` as the flags say, `lm_train.make_optimizer`, the whole
batch with its noise, remat as the flags say, donated state) runs twice
on that batch. The schedule's first learning rate is 0, so the first
step fills the moments and nothing else: AdamW's bias-corrected first
moment after it is the gradient the compiled step made (the weighted
streamed CE, the flash backward under both masks, the join's and the
own-block product's transposes, the grouped matmuls' backward, remat's
replay). Routing is discrete, so the plain gradient is taken with the
program's own experts given; `grad_rel_err` is the largest over the
leaves outside the expert layers of |it - the plain one| / |the plain
one|; the routers', the held experts' and the norms' before them
(`norm_2`: every gradient into it comes through the router and the
experts) are pooled over the layers into `routed_grad_rel_err` (a
near-tie that the compiled step turns the other way than the forward
pass above moves a whole row from one expert's sum to another's; a
fresh model's positions are nearly alike from the second layer on, the
common mean of the values they attend to, so a near-tie between the
8th and the 9th expert is every position's at once: `routing_by_layer`). `update_rel_err` is |(parameters after
- before) - AdamW's update written out here from the step's own
moments| / |that update| over all leaves; a state left unchanged reads
1. `timed_loss_diff` is the first step's own loss against the plain
one, under `loss_tolerance`.

The accepted driver (`drivers/train_steady_ref.py`) compares the first
two readings and no other. So this child holds the others to the limits
of the configuration's `reference` itself, names what failed under
`refused`, and then withholds `loss` (NaN), which the driver's
comparison turns into `correct: false`; `reference_loss` always holds
the number.

    python -m benchmark.reference.check_sdar <config.json> <data_dir> <step>
"""

from __future__ import annotations

import json
import os
import sys

# optax.adamw as lm_train.make_optimizer calls it, and the leaves' errors
from benchmark.reference.check_granite_hybrid import (B1, B2, EPS,
                                                      WEIGHT_DECAY,
                                                      leaf_errors, pooled)


EARLY = 256  # positions of `early_token_loss_rms_diff`


def _flag(flags: list, name: str, default=None):
    return flags[flags.index(name) + 1] if name in flags else default


def program_config(config: dict):
    """The `TransformerConfig` `lm_train` builds from the file's flags:
    the sizes `harness/job.lm_args` passes and the sdar flags; the head
    size, the key/value heads and the routing are `sdar_config`'s own."""
    import jax.numpy as jnp

    from edl_tpu.models.transformer import sdar_config
    run = config["run"]
    flags = run["flags"]
    sizes = {field: int(_flag(flags, flag)) for field, flag in (
        ("n_experts", "--n-experts"), ("moe_top_k", "--moe-top-k"),
        ("experts_held", "--experts-held"),
        ("block_length", "--block-length")) if flag in flags}
    return sdar_config(
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
            "top_k": config.get("num_experts_per_tok", cfg.moe_top_k),
            "first_expert": cfg.experts_offset,
            "block_length": config.get("block_length", cfg.block_length),
            "mask_id": config["vocab_size"] - 1}


def step_batch(config: dict, cfg, data_dir: str, step: int):
    """({tokens, masked, t} of global step ``step`` (1-based) as the
    trainer's loader makes it, the loader's steps an epoch)."""
    from edl_tpu.data import block_noise
    from edl_tpu.data.pipeline import DataLoader, FileSource
    run = config["run"]
    files = sorted(os.path.join(data_dir, f) for f in os.listdir(data_dir)
                   if f.startswith("train-") and f.endswith(".npz"))
    loader = DataLoader(block_noise.RowIndexed(FileSource(files)),
                        run["global_batch"], rank=0, world=1,
                        seed=run["trainer_seed"])
    per_epoch = loader.steps_per_epoch()
    epoch, index = divmod(step - 1, per_epoch)
    batch = next(iter(block_noise.with_noise(
        loader.epoch(epoch, index), seed=run["trainer_seed"], epoch=epoch,
        block_length=cfg.block_length)))
    loader.close()
    return batch, per_epoch


def timed_program(config: dict, program, tree, batch: dict, per_epoch: int):
    """Two steps of the trainer's train step on ``batch`` from ``tree``
    (donated: gone afterwards). Returns the first step's loss, the
    gradient it made (host, the program's names), `update_rel_err` of
    the second, and the parameters as they were (host)."""
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
    on_device = jax.tree.map(jnp.asarray, batch)
    state, first = step(state, on_device)
    adam = next(s for s in state.opt_state if hasattr(s, "mu"))
    grads = jax.tree.map(lambda m: np.asarray(m) / np.float32(1 - B1),
                         adam.mu)
    state, _ = step(state, on_device)
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
    return loss, grads, update, before


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
    from benchmark.reference.child_cache import keep_programs, sentence
    programs = keep_programs()

    import time

    from benchmark.reference import sdar_plain as plain
    from benchmark.reference.trainer_draw import seeded_params
    from edl_tpu.models.transformer import Transformer

    t0 = time.monotonic()

    def phase(what):
        held = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
        print(f"[check +{time.monotonic() - t0:6.1f}s] {what}; the device "
              f"holds {held} B", file=sys.stderr, flush=True)
    cfg = program_config(config)
    batch, per_epoch = step_batch(config, cfg, data_dir, step)
    program = Transformer(cfg)
    tree = seeded_params(program, config)
    hp = reference_hp(config, cfg)
    phase("parameters drawn")
    theirs, their_experts = plain.batch_losses(
        plain.from_program(tree), batch, hp)
    loss = plain.batch_loss(theirs, batch)
    phase("the plain forward")

    @jax.jit
    def program_forward(tree, toks, noised):
        out, sown = program.apply({"params": tree}, toks, train=True,
                                  noised=noised, mutable=["intermediates"])
        logp = jax.nn.log_softmax(out.astype(jnp.float32))
        chosen = [sown["intermediates"][f"block{i}"]["moe_mlp"]["moe_idx"][0]
                  for i in range(cfg.n_layers)]
        return -jnp.take_along_axis(logp, toks[..., None],
                                    axis=-1)[..., 0], chosen
    # one row at a time: a row's logits are 0.6 GB twice over
    mine, my_experts = [], []
    for row, masked in zip(batch["tokens"], batch["masked"]):
        row = jnp.asarray(row[None], jnp.int32)
        got, chosen = program_forward(tree, row, plain.noised_copy(
            row, jnp.asarray(masked[None]), hp["mask_id"]))
        mine.append(np.asarray(got)[0])
        my_experts.append([np.asarray(c) for c in chosen])
    at = np.asarray(batch["masked"])
    off = np.square(np.stack(mine) - np.stack(theirs))
    rms = float(np.sqrt(np.mean(off[at])))
    early_rms = float(np.sqrt(np.mean(off[:, :EARLY][at[:, :EARLY]])))
    strangers = sum(int((~(a[:, :, None] == b[:, None, :]).any(-1)).sum())
                    for mine_, theirs_ in zip(my_experts, their_experts)
                    for a, b in zip(mine_, theirs_))
    routing_diff = strangers / sum(a.size for row in my_experts for a in row)
    # a layer: (share of its assignments routed differently, the fullest
    # expert's positions over the mean expert's)
    by_layer = [[round(float(np.mean([
        (~(row[i][:, :, None] == ref[i][:, None, :]).any(-1)).mean()
        for row, ref in zip(my_experts, their_experts)])), 5),
        round(float(max(np.bincount(row[i].ravel(), minlength=cfg.n_experts)
                        .max() for row in my_experts)
                    * cfg.n_experts / my_experts[0][i].size), 2)]
        for i in range(cfg.n_layers)]
    phase("the program's forward")

    timed_loss, grads, update, before = timed_program(
        config, program, tree, batch, per_epoch)
    import gc
    gc.collect()  # the step's buffers, before the plain gradient needs them
    phase("the trainer's step, twice")
    wanted = plain.batch_grads(plain.from_program(jax.device_put(before)),
                               batch, hp, chosen=my_experts)
    errors = leaf_errors(plain.from_program(grads), wanted)
    phase("the plain gradient")
    for name, e, r, along in errors:
        print(f"gradient {name}: |diff| {e:.4g} / |plain| {r:.4g} = "
              f"{e / r if r else float('nan'):.4g}, along the plain one "
              f"{along:.5f}", file=sys.stderr)
    routed = [row for row in errors if any(
        leaf in row[0] for leaf in ("['router']", "['experts']",
                                    "['norm_2']"))]
    others = [row for row in errors if row not in routed]
    grad, where = max((e / r if r else float(e > 0), name)
                      for name, e, r, _ in others)
    routed_grad = pooled(routed)
    timed_diff = abs(timed_loss - loss)
    refused = [f"{name} {value:.6g} > {limits[key]}" for name, value, key in (
        ("grad_rel_err", grad, "grad_rel_tolerance"),
        ("routed_grad_rel_err", routed_grad, "routed_grad_rel_tolerance"),
        ("update_rel_err", update, "update_rel_tolerance"),
        ("routing_diff_share", routing_diff, "routing_diff_tolerance"),
        ("early_token_loss_rms_diff", early_rms,
         "early_token_loss_rms_tolerance"),
        ("timed_loss_diff", timed_diff, "loss_tolerance"))
        if not value <= limits[key]]
    dev = jax.devices()[0]
    phase("done: " + sentence(programs()))
    print(json.dumps({
        "loss": float("nan") if refused else loss, "reference_loss": loss,
        "step": step, "rows": int(len(batch["tokens"])),
        "masked_share": float(at.mean()), "token_loss_rms_diff": rms,
        "early_token_loss_rms_diff": early_rms,
        "program_loss": plain.batch_loss(mine, batch),
        "timed_loss": timed_loss, "timed_loss_diff": timed_diff,
        "routing_diff_share": routing_diff, "routing_by_layer": by_layer,
        "grad_rel_err": grad,
        "grad_rel_err_leaf": where, "routed_grad_rel_err": routed_grad,
        "grad_rel_err_all_leaves": pooled(errors),
        "grad_rel_err_worst_leaves": [
            [name, round(e / r, 4)] for name, e, r, _ in sorted(
                errors, key=lambda row: -row[1] / row[2])[:12]],
        "grad_along_plain": sum(a * r * r for _, _, r, a in errors)
        / sum(r * r for _, _, r, _ in errors),
        "update_rel_err": update, "refused": refused,
        "programs": programs(), "platform": dev.platform,
        "kind": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
