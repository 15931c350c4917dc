"""The noise of training by diffusion over blocks (models/blockdiff.py),
on the host: which tokens of a row are masked, and at which level.

A row's noise is a function of (the trainer's seed, the epoch, the row's
index in the source) and of nothing else: not of the step, the rank, the
world's size or the process. So a row meets the same noise after a kill
and a resume, and after a resize that hands it to another trainer, and
a checker that walks the loader sees the trainer's own batch.

Per block one level t, uniform on (`T_MIN`, 1]; each token of the block
masked with probability t.
"""

from __future__ import annotations

from typing import Iterable, Iterator

import numpy as np

from edl_tpu.obs import trace

T_MIN = 1e-3
_TAG = 0xB10C  # keeps these draws apart from any other use of the seed


class RowIndexed:
    """A source whose batches also carry the rows' indices, as `row`."""

    def __init__(self, source):
        self.source = source

    def __len__(self) -> int:
        return len(self.source)

    def batch(self, idx: np.ndarray) -> dict[str, np.ndarray]:
        return {**self.source.batch(idx), "row": np.asarray(idx, np.int64)}


def row_noise(seed: int, epoch: int, row: int, length: int,
              block_length: int) -> tuple[np.ndarray, np.ndarray]:
    """(masked (L,) bool, t (L,) float32, a block's level repeated over
    its tokens) of one row."""
    # epoch + 1: an evaluation's rows are drawn at epoch -1
    rng = np.random.default_rng([_TAG, seed, epoch + 1, row])
    # 1 - U[0, 1) is U(0, 1]
    t = T_MIN + (1.0 - T_MIN) * (1.0 - rng.random(length // block_length))
    t = np.repeat(t.astype(np.float32), block_length)
    return rng.random(length) < t, t


def with_noise(batches: Iterable[dict], *, seed: int, epoch: int,
               block_length: int) -> Iterator[dict]:
    """The batches of a `RowIndexed` source with `row` turned into
    `masked` and `t`."""
    for batch in batches:
        with trace.span("loader.noise"):
            batch = dict(batch)
            rows = batch.pop("row")
            length = batch["tokens"].shape[1]
            noise = [row_noise(seed, epoch, int(r), length, block_length)
                     for r in rows]
            batch["masked"] = np.stack([m for m, _ in noise])
            batch["t"] = np.stack([t for _, t in noise])
        yield batch
