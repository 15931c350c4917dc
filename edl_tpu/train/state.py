"""Train state and resume status.

``TrainState`` is the functional training-step state (params/opt/batch_stats)
threaded through jitted step functions. ``TrainStatus`` is the host-side
resume cursor — the capability of the reference's ``TrainStatus`` carrying
``epoch_no`` for checkpoint resume (doc/fault_tolerance.md, used at
example/collective/resnet50/train_with_fleet.py:491 "for pass_id in
range(train_status.next(), num_epochs)").
"""

from __future__ import annotations

from dataclasses import asdict, dataclass
from typing import Any, Callable

import jax
import optax
from flax import struct


@struct.dataclass
class TrainState:
    """Minimal functional train state (flax struct pytree).

    apply_fn/tx are static (not serialized); params/opt_state/batch_stats
    and step are the pytree leaves that checkpoints capture.
    """

    step: jax.Array | int
    params: Any
    opt_state: Any
    batch_stats: Any = None
    apply_fn: Callable = struct.field(pytree_node=False, default=None)
    tx: optax.GradientTransformation = struct.field(
        pytree_node=False, default=None)

    @classmethod
    def create(cls, *, apply_fn, params, tx, batch_stats=None, **kwargs):
        return cls(
            step=0,
            params=params,
            opt_state=tx.init(params),
            batch_stats=batch_stats,
            apply_fn=apply_fn,
            tx=tx,
            **kwargs,
        )

    def apply_gradients(self, *, grads, **kwargs):
        if hasattr(self.tx, "fused_apply"):
            # Fused bucket path (train/fused_opt.py): no "updates tree"
            # intermediate — params and moments are rewritten in one
            # kernel pass. Duck-typed so the plain jit step and the comm
            # step both pick it up through this seam.
            new_params, new_opt_state = self.tx.fused_apply(
                grads, self.opt_state, self.params)
        else:
            updates, new_opt_state = self.tx.update(grads,
                                                    self.opt_state,
                                                    self.params)
            new_params = optax.apply_updates(self.params, updates)
        return self.replace(step=self.step + 1, params=new_params,
                            opt_state=new_opt_state, **kwargs)


@dataclass
class TrainStatus:
    """Host-side resume cursor persisted alongside each checkpoint."""

    epoch: int = -1          # last fully completed epoch (-1 = none)
    step: int = 0            # global optimizer steps completed
    step_in_epoch: int = 0   # steps into the partially-done epoch (0 = none)
    samples_seen: int = 0    # for data-order resume bookkeeping
    world_size: int = 1      # devices at save time (resharding hint)

    def next_epoch(self) -> int:
        return self.epoch + 1

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TrainStatus":
        return cls(**{k: d[k] for k in
                      ("epoch", "step", "step_in_epoch", "samples_seen",
                       "world_size")
                      if k in d})
