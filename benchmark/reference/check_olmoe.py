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
    # the harness gives JAX_COMPILATION_CACHE_DIR, a directory of the
    # reference children's own: keep every program there (child_cache.py)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference.child_cache import (keep_programs, phase_log,
                                                 sentence)
    programs = keep_programs()

    from benchmark.reference import olmoe_plain
    from benchmark.reference.trainer_draw import seeded_params, step_batch
    from edl_tpu.models.transformer import Transformer
    phase = phase_log()  # seconds after the imports

    batch, _ = step_batch(config, data_dir, step)
    program = Transformer(program_config(config))
    tree = seeded_params(program, config)
    hp = reference_hp(config)
    phase("parameters drawn")
    stats = olmoe_plain.batch_stats(olmoe_plain.from_program(tree), batch, hp)
    plain = olmoe_plain.pool(stats, hp)
    phase("the plain forward")

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
    phase("the program's forward; done: " + sentence(programs()))
    print(json.dumps({
        "loss": float(plain["loss"]), "ce": float(plain["ce"]),
        "balance": float(plain["balance"]), "z": float(plain["z"]),
        "step": step, "rows": int(len(batch)), "token_loss_rms_diff": rms,
        "routing": {"assignments": int(assigned * len(fracs)),
                    "moved_between_experts": moved,
                    "max_load_program": float(mine_counts.max() / mean_load),
                    "max_load_plain": float(plain_counts.max() / mean_load)},
        "programs": programs(), "platform": dev.platform,
        "kind": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
