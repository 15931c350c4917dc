"""What every checker under `benchmark/reference/` draws before it
compares anything: the batch the trainer saw at one global step, through
the program's own loader, and the trainer's parameters, through the
program's own initialiser on the configuration's `trainer_seed`.
(`check_lm.py` and `check_olmoe.py` carry copies of these lines; a
`benchmark` PR can point them here.)"""

from __future__ import annotations

import os


def step_batch(config: dict, data_dir: str, step: int):
    """((global_batch, S) token ids of global step ``step`` (1-based),
    the loader's steps an epoch)."""
    from edl_tpu.data.pipeline import DataLoader, FileSource
    run = config["run"]
    files = sorted(os.path.join(data_dir, f) for f in os.listdir(data_dir)
                   if f.startswith("train-") and f.endswith(".npz"))
    loader = DataLoader(FileSource(files), run["global_batch"], rank=0,
                        world=1, seed=run["trainer_seed"])
    per_epoch = loader.steps_per_epoch()
    epoch, index = divmod(step - 1, per_epoch)
    batch = next(iter(loader.epoch(epoch, index)))["tokens"]
    loader.close()
    return batch, per_epoch


def seeded_params(program, config: dict):
    """The parameter tree `lm_train` starts from, unboxed."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta
    run = config["run"]
    toks0 = jnp.zeros((1, run["seq_len"]), jnp.int32)
    return jax.jit(lambda: meta.unbox(program.init(
        jax.random.PRNGKey(run["trainer_seed"]), toks0,
        train=False)))()["params"]
