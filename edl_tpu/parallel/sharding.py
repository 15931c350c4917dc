"""Parameter/activation sharding rules (logical-axis style).

The TPU-native replacement for everything the reference delegates to NCCL
process groups (SURVEY.md §2.3): parameters carry *logical* axis names, a
rule table maps logical names to mesh axes, and `jax.jit` + XLA's SPMD
partitioner materialize the collectives (all-gather for fsdp params,
reduce-scatter/all-reduce for grads, all-to-all for tp boundaries) over ICI.

Rules are `(logical_name, mesh_axis | None)` pairs, first match wins —
the flax `logical_to_mesh` convention.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default rule table for the transformer family. Logical names:
#   batch   — batch dim of activations
#   seq     — sequence dim (ring-attention shards live here)
#   vocab   — embedding table rows
#   embed   — model dim
#   heads   — attention heads
#   kv      — per-head dim
#   mlp     — feed-forward hidden dim
DEFAULT_RULES: tuple[tuple[str, Any], ...] = (
    ("batch", ("dp", "fsdp")),
    ("seq", "sp"),
    ("vocab", "tp"),
    ("embed", "fsdp"),
    ("heads", "tp"),
    ("kv", None),
    ("mlp", "tp"),
    # Embedding-table axes. The token-id gather cannot be partitioned
    # along its vocab (operand) dim — XLA falls back to "involuntary full
    # rematerialization", all-gathering the whole table every step — so
    # the table shards along the embedding dim only (tp); the gather then
    # partitions trivially and the cheap reshard is on the (b, s, d)
    # activations, not the (V, d) table.
    ("vocab_table", None),
    ("embed_table", "tp"),
    # MoE expert tables: the leading expert dim shards over ep, so the
    # checkpoint index carries each table as ep-sharded leaves and the
    # cross-mesh resharding planner (train/sharded_checkpoint.py +
    # collective/migration.py) re-shards experts on an ep resize like
    # any other sharded state. The router's expert dim stays replicated
    # (expert_router) — every chip routes against all experts.
    ("expert", "ep"),
    ("expert_router", None),
)


def logical_to_spec(logical: Sequence[str | None],
                    rules: Sequence[tuple[str, Any]] = DEFAULT_RULES,
                    mesh: Mesh | None = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec.

    Mesh axes not present in `mesh` (or of size 1) are dropped so one rule
    table serves every mesh shape — the elasticity hook: resize the mesh and
    re-derive shardings, no rule edits.
    """
    taken: set[str] = set()
    out: list[Any] = []
    for name in logical:
        axis = None
        if name is not None:
            for rule_name, rule_axis in rules:
                if rule_name == name:
                    axis = rule_axis
                    break
        if axis is None:
            out.append(None)
            continue
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        if mesh is not None:
            axes = tuple(a for a in axes
                         if a in mesh.axis_names and mesh.shape[a] > 1)
        axes = tuple(a for a in axes if a not in taken)
        taken.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_shardings(mesh: Mesh, abstract_params: Any,
                    rules: Sequence[tuple[str, Any]] = DEFAULT_RULES) -> Any:
    """NamedShardings for a pytree of flax Partitioned/plain leaves.

    Leaves carrying flax `Partitioned` metadata (`.names`) get their logical
    names mapped through `rules`; plain leaves are replicated.
    """

    def one(leaf):
        names = getattr(leaf, "names", None)
        if names is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, logical_to_spec(names, rules, mesh))

    return jax.tree.map(one, abstract_params,
                        is_leaf=lambda x: hasattr(x, "names"))


def init_sharded(init_fn, mesh: Mesh,
                 rules: Sequence[tuple[str, Any]] = DEFAULT_RULES) -> Any:
    """Run a flax `init` thunk with params materialized ALREADY sharded.

    `jax.eval_shape` gives the abstract boxed variable tree; logical names
    become NamedShardings; the real init runs under jit with those
    out_shardings so each device only materializes its own parameter
    shards — no full replica ever exists in HBM (how multi-billion-param
    states fit, and how elastic restore re-places shards on a new mesh).
    Returns the unboxed variables dict.
    """
    from flax.core import meta

    abstract = jax.eval_shape(init_fn)
    shardings = param_shardings(mesh, abstract, rules)
    return jax.jit(lambda: meta.unbox(init_fn()),
                   out_shardings=shardings)()


def placement_of(tree: Any, mesh: Mesh) -> Any:
    """The shardings a restored copy of ``tree`` is placed with: each
    leaf's own where it has a mesh placement, replicated over ``mesh``
    otherwise (eagerly created scalars such as step counters)."""
    replicated = NamedSharding(mesh, P())

    def one(leaf):
        sharding = getattr(leaf, "sharding", None)
        return sharding if isinstance(sharding, NamedSharding) \
            else replicated

    return jax.tree.map(one, tree)


def bytes_per_device(tree: Any) -> dict[int, int]:
    """Bytes of ``tree`` each local device holds: tells a sharded state
    (a share each) from a replicated one (all of it on every device)."""
    held: dict[int, int] = {}
    for leaf in jax.tree.leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            held[shard.device.id] = held.get(shard.device.id, 0) \
                + shard.data.nbytes
    return dict(sorted(held.items()))


def dp_row_sharding(mesh: Mesh) -> NamedSharding:
    """One distinct row per dp position: ``(W, ...)`` arrays laid out
    ``P('dp')``. The placement of the comm plane's per-chip
    error-feedback residuals (train/comm.py) — each chip owns exactly
    its own row, so a shard_map over dp sees its local ``(1, ...)``
    block and no residual ever crosses a link."""
    return NamedSharding(mesh, P("dp"))


def constrain(x: jax.Array, logical: Sequence[str | None],
              mesh: Mesh | None = None,
              rules: Sequence[tuple[str, Any]] = DEFAULT_RULES) -> jax.Array:
    """with_sharding_constraint by logical names (no-op without a mesh)."""
    if mesh is None:
        return x
    spec = logical_to_spec(logical, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
