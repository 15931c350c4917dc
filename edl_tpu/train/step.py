"""Jitted train/eval step builders.

One compiled SPMD program replaces the reference's per-GPU process + NCCL
allreduce (fleet.distributed_optimizer(...).minimize, train_with_fleet.py:326):
with the batch sharded over the mesh's data axes and params replicated (or
sharded by rules), XLA's partitioner inserts the gradient reductions over
ICI — there is no explicit collective call in user code.
"""

from __future__ import annotations

from typing import Any, Callable

import jax

LossFn = Callable[..., tuple[jax.Array, dict]]


def make_train_step(loss_fn: LossFn, donate: bool = True) -> Callable:
    """Build a jitted step from loss_fn(state, params, batch)->(loss, aux).

    If the model has batch_stats (BN), loss_fn should return aux containing
    'batch_stats' with the new stats; they are folded into the state.

    The manual DCN-aware gradient path is a builder of its own:
    `train/comm.make_comm_train_step`.
    """
    # The function's name is the program's in a device trace
    # (`jit_train_step` on `XLA Modules`), whatever the mesh; the scope
    # names the optimizer's share of it. Names are metadata: the
    # compiled step is the same with and without them.
    def train_step(state, batch):
        def compute(params):
            return loss_fn(state, params, batch)

        (loss, aux), grads = jax.value_and_grad(compute, has_aux=True)(
            state.params)
        new_stats = aux.pop("batch_stats", None)
        with jax.named_scope("opt_update"):
            if new_stats is not None:
                state = state.apply_gradients(grads=grads,
                                              batch_stats=new_stats)
            else:
                state = state.apply_gradients(grads=grads)
        return state, {"loss": loss, **aux}

    return jax.jit(train_step, donate_argnums=(0,) if donate else ())


def make_eval_step(metric_fn: Callable[[Any, Any], dict]) -> Callable:
    return jax.jit(metric_fn)


def donation_coverage(step_fn: Callable, *args) -> dict:
    """Compile-time donated-buffer audit of a jitted train step.

    Lowers (does not run) the step on ``args`` and counts the
    input->output buffer aliases XLA recorded for the donated state —
    the in-place-update guarantee that keeps peak HBM at one copy of
    params+moments instead of two. A step whose params/opt_state
    leaves all alias reports ``full=True``; a refactor that breaks
    donation (e.g. an op capturing the old params beyond the update)
    shows up as a structural drop, which tests assert on rather than
    eyeballing profiler output.

    Returns {aliased, state_leaves, full}. ``state_leaves`` counts the
    array leaves of args[0] (the donated TrainState) — quantized
    moment planes count like any other leaf; their int8 buffers alias
    the same way.
    """
    import re

    header = step_fn.lower(*args).compile().as_text().split("\n", 1)[0]
    aliased = len(re.findall(r"-alias", header))
    donatable = sum(1 for leaf in jax.tree_util.tree_leaves(args[0])
                    if hasattr(leaf, "dtype"))
    return {"aliased": aliased, "state_leaves": donatable,
            "full": aliased >= donatable}
