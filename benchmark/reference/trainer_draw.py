"""What every checker under `benchmark/reference/` draws before it
compares anything: the batch the trainer saw at one global step, through
the program's own loader, and the trainer's parameters, through the
program's own initialiser on the configuration's `trainer_seed`.
(`check_lm.py` carries copies of these lines; a `benchmark` PR can
point it here.)"""

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


# The initialiser is traced on this many tokens, not on the cell's
# sequence: a parameter's shape and the path its key is folded from do
# not depend on the length, and tracing the whole forward at 8,192
# tokens to shape them took 11-25 s of every child (compiled anew, 59 s
# of JoyAI's). Every leaf equal to the draw at the cell's length, bit
# for bit: on the chip at the five cells' sizes (PERF.md section 6, PR
# 55) and in benchmark/tests/test_reference_child.py at the tiny ones.
DRAW_TOKENS = 8


def seeded_variables(program, config: dict) -> dict:
    """What `lm_train` starts from, unboxed: the parameters and, where
    the model has them, the balancing biases (zeros)."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta
    run = config["run"]
    toks0 = jnp.zeros((1, min(DRAW_TOKENS, run["seq_len"])), jnp.int32)
    return jax.jit(lambda: meta.unbox(program.init(
        jax.random.PRNGKey(run["trainer_seed"]), toks0, train=False)))()


def seeded_params(program, config: dict):
    """The parameter tree `lm_train` starts from, unboxed."""
    return seeded_variables(program, config)["params"]
