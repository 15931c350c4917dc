"""The one import site of shard_map: ``jax.shard_map`` with the
varying-axes check off by default — the manual collectives of
train/comm.py pass ``axis_index_groups``, which that check does not
implement."""

from __future__ import annotations

import functools

import jax

shard_map = functools.partial(jax.shard_map, check_vma=False)
