"""The reference's side of `correct` for a trained language model.

A child of the benchmark, started after the trainer has ended, because a
chip belongs to one process at a time. It builds the parameters the
trainer started from (the program's own initialiser on the
configuration's `trainer_seed`: a draw, not arithmetic under test), takes the batch of one global step
from the shards through the program's loader (the same order the trainer
saw), and prints the loss of `gpt2_plain` on it as one JSON line.

The trainer logs one number a step, and at a seeded start that number
sits near ln(vocab) + 0.5 whatever the blocks compute. So the child also
runs the program's own forward pass as the trainer builds it (its
activation type, its attention kernel) on the first `TOKEN_ROWS`
sequences of that batch and compares the loss of every token with the
plain one: `token_loss_rms_diff`. A wrong kernel moves single tokens by
about 1, float8 arithmetic by about 0.1, bfloat16 by about 0.01. It
uses one device, whatever the host holds.

    python -m benchmark.reference.check_lm <config.json> <data_dir> <step>
"""

from __future__ import annotations

import json
import os
import sys

TOKEN_ROWS = 2  # sequences compared token by token: 4,094 tokens at S=2048


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
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from benchmark.reference import gpt2_plain
    from edl_tpu.data.pipeline import DataLoader, FileSource
    from edl_tpu.models.transformer import Transformer, TransformerConfig

    files = sorted(os.path.join(data_dir, f) for f in os.listdir(data_dir)
                   if f.startswith("train-") and f.endswith(".npz"))
    loader = DataLoader(FileSource(files), run["global_batch"], rank=0,
                        world=1, seed=seed)
    per_epoch = loader.steps_per_epoch()
    epoch, index = divmod(step - 1, per_epoch)
    batch = next(iter(loader.epoch(epoch, index)))["tokens"]
    loader.close()

    # as the trainer builds it: its activation type, and the attention
    # kernel its backend picks
    program = Transformer(TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=run["seq_len"],
        dtype=jnp.bfloat16 if "--bf16" in run["flags"] else jnp.float32))
    toks0 = jnp.zeros((1, run["seq_len"]), jnp.int32)
    from flax.core import meta
    tree = jax.jit(lambda: meta.unbox(program.init(
        jax.random.PRNGKey(seed), toks0, train=False)))()["params"]
    params = gpt2_plain.from_program(tree, config["n_layer"])
    plain = gpt2_plain.batch_token_losses(
        params, batch, config["n_head"], config["layer_norm_epsilon"])

    @jax.jit
    def program_token_losses(tree, toks):
        out = program.apply({"params": tree}, toks, train=True)
        logp = jax.nn.log_softmax(out[:, :-1].astype(jnp.float32))
        return -jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1)[..., 0]
    mine = program_token_losses(
        tree, jnp.asarray(batch[:TOKEN_ROWS], jnp.int32))
    rms = float(jnp.sqrt(jnp.mean(jnp.square(mine - plain[:TOKEN_ROWS]))))
    dev = jax.devices()[0]
    print(json.dumps({"loss": float(plain.mean()), "step": step,
                      "rows": int(len(batch)), "token_loss_rms_diff": rms,
                      "platform": dev.platform, "kind": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
