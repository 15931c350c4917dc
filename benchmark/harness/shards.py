"""Seeded synthetic token shards, written once in set-up.

The benchmark's own copy of `edl_tpu.examples.lm_train.
make_synthetic_shards` (Markov chain: each token has 8 plausible
successors), so that the trainer is started without `--make-synthetic`
and a respawn does not write them again inside `resume_s`. Two changes:
the transition table is seeded too, and no validation shard is written
(an epoch never ends inside a run, so nothing would read it).
"""

from __future__ import annotations

import os

import numpy as np


def make_shards(data_dir: str, n_files: int, rows: int, seq_len: int,
                vocab: int, seed: int) -> None:
    os.makedirs(data_dir, exist_ok=True)
    successors = np.random.default_rng([seed, 55]).integers(
        0, vocab, size=(vocab, 8))
    for i in range(n_files):
        rng = np.random.default_rng([seed, 271, i])
        toks = np.empty((rows, seq_len), np.int32)
        toks[:, 0] = rng.integers(0, vocab, size=rows)
        picks = rng.integers(0, 8, size=(seq_len, rows), dtype=np.int8)
        for t in range(1, seq_len):
            toks[:, t] = successors[toks[:, t - 1], picks[t]]
        np.savez(os.path.join(data_dir, f"train-{i:04d}.npz"), tokens=toks)
