"""The reference's side of `correct` for a trained mixture-of-experts
language model whose configuration file carries the source's keys
(`model_type: olmoe`). As `check_lm.py`: a child of the benchmark,
started after the trainer has ended, which draws the trainer's
parameters with the program's own initialiser on the configuration's
`trainer_seed`, takes the batch of one global step from the shards
through the program's loader, and prints one JSON line.

`loss` is what the trainer's `loss=` holds, from `olmoe_plain` on the
whole batch: cross-entropy + router_aux_loss_coef x load-balance +
router_z_loss_coef x z-loss, the three also by themselves.
`token_loss_rms_diff` compares the program's own forward pass as the
trainer builds it (its activation type, its attention kernel, its
dropless dispatch) with the plain one, token by token, on the first
`TOKEN_ROWS` sequences. `routing` counts, on those sequences, the
assignments of tokens to experts that the two made differently (top-k
near-ties that bfloat16 and float32 order differently) among all, and
gives the fullest expert's load of both.

    python -m benchmark.reference.check_olmoe <config.json> <data_dir> <step>
"""

from __future__ import annotations

import json
import os
import sys

TOKEN_ROWS = 2  # sequences compared token by token: 8,190 tokens at S=4096


def program_config(config: dict):
    """The `TransformerConfig` `lm_train` builds from the file's flags."""
    import jax.numpy as jnp

    from edl_tpu.models.transformer import olmoe_config
    run = config["run"]
    return olmoe_config(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=run["seq_len"],
        n_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        dtype=jnp.bfloat16 if "--bf16" in run["flags"] else jnp.float32)


def reference_hp(config: dict) -> dict:
    return {"n_head": config["n_head"], "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"]),
            "top_k": config["num_experts_per_tok"],
            "aux_coef": config["router_aux_loss_coef"],
            "z_coef": config["router_z_loss_coef"]}


def main(argv: list[str]) -> int:
    config_path, data_dir, step = argv
    step = int(step)
    with open(config_path) as f:
        config = json.load(f)
    run = config["run"]
    seed = run["trainer_seed"]
    # the harness gives JAX_COMPILATION_CACHE_DIR; keep quick compiles too
    import jax
    import jax.numpy as jnp
    import numpy as np
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from benchmark.reference import olmoe_plain
    from edl_tpu.data.pipeline import DataLoader, FileSource
    from edl_tpu.models.transformer import Transformer

    files = sorted(os.path.join(data_dir, f) for f in os.listdir(data_dir)
                   if f.startswith("train-") and f.endswith(".npz"))
    loader = DataLoader(FileSource(files), run["global_batch"], rank=0,
                        world=1, seed=seed)
    per_epoch = loader.steps_per_epoch()
    epoch, index = divmod(step - 1, per_epoch)
    batch = next(iter(loader.epoch(epoch, index)))["tokens"]
    loader.close()

    cfg = program_config(config)
    program = Transformer(cfg)
    toks0 = jnp.zeros((1, run["seq_len"]), jnp.int32)
    from flax.core import meta
    tree = jax.jit(lambda: meta.unbox(program.init(
        jax.random.PRNGKey(seed), toks0, train=False)))()["params"]
    hp = reference_hp(config)
    stats = olmoe_plain.batch_stats(olmoe_plain.from_program(tree), batch, hp)
    plain = olmoe_plain.pool(stats, hp)

    @jax.jit
    def program_forward(tree, toks):
        out, mutated = program.apply({"params": tree}, toks, train=True,
                                     mutable=["intermediates"])
        logp = jax.nn.log_softmax(out[:, :-1].astype(jnp.float32))
        losses = -jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1)[..., 0]
        return losses, [b["moe_mlp"]["moe_frac"][0] for _, b in sorted(
            mutated["intermediates"].items())]
    mine, fracs = program_forward(
        tree, jnp.asarray(batch[:TOKEN_ROWS], jnp.int32))
    theirs = np.stack([s["token_losses"] for s in stats[:TOKEN_ROWS]])
    rms = float(np.sqrt(np.mean(np.square(np.asarray(mine) - theirs))))
    # assignments of every layer, experts by row: program against plain
    assigned = TOKEN_ROWS * run["seq_len"] * hp["top_k"]
    mine_counts = np.stack([np.asarray(f) for f in fracs]) * assigned
    plain_counts = sum(s["slot_counts"].sum(1) for s in stats[:TOKEN_ROWS])
    moved = float(np.abs(mine_counts - plain_counts).sum() / 2)
    mean_load = assigned / plain_counts.shape[-1]
    dev = jax.devices()[0]
    print(json.dumps({
        "loss": float(plain["loss"]), "ce": float(plain["ce"]),
        "balance": float(plain["balance"]), "z": float(plain["z"]),
        "step": step, "rows": int(len(batch)), "token_loss_rms_diff": rms,
        "routing": {"assignments": int(assigned * len(fracs)),
                    "moved_between_experts": moved,
                    "max_load_program": float(mine_counts.max() / mean_load),
                    "max_load_plain": float(plain_counts.max() / mean_load)},
        "platform": dev.platform, "kind": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
