"""Rehearsal 3 of the on-chip-measurement guide: compile a configuration's
train step at its real size for a described v5e (one chip, or the 2x2
host under the file's mesh) and print the compiler's memory analysis per
chip. Nothing runs; no chip is needed; this is not a measurement.

    JAX_PLATFORMS=cpu python benchmark/tools/compile_step.py <config.json> [global batch ...]
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402


def main(argv: list[str]) -> int:
    with open(argv[0]) as f:
        config = json.load(f)
    run = config["run"]
    batches = [int(b) for b in argv[1:]] or [run["global_batch"]]
    jax.config.update("jax_enable_compilation_cache", False)
    # the program asks the backend which attention to build
    jax.default_backend = lambda: "tpu"
    from flax.core import meta

    from edl_tpu.models.transformer import (Transformer, TransformerConfig,
                                            lm_loss_fused)
    from edl_tpu.parallel import sharding as shd
    from edl_tpu.train.state import TrainState
    from edl_tpu.train.step import make_train_step

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    n = 4 if run["mesh"] == "fsdp" else 1
    mesh = Mesh(np.array(topo.devices[:n]), (run["mesh"],))
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=run["seq_len"], dtype=jnp.bfloat16,
        mesh=mesh)
    model = Transformer(cfg)
    toks0 = jnp.zeros((n, run["seq_len"]), jnp.int32)

    def init():
        return model.init(jax.random.PRNGKey(0), toks0, train=False)

    shardings = shd.param_shardings(mesh, jax.eval_shape(init))["params"]
    by_path = {tuple(str(k) for k in path): s for path, s in
               jax.tree_util.tree_flatten_with_path(shardings)[0]}

    def create():
        return TrainState.create(
            apply_fn=model.apply, params=meta.unbox(init())["params"],
            tx=optax.adamw(run["lr"], weight_decay=0.01))

    def place(path, leaf):
        keys = tuple(str(k) for k in path)
        for start in range(len(keys)):  # moments mirror the parameters
            if keys[start:] in by_path and leaf.ndim:
                return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                            sharding=by_path[keys[start:]])
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, P()))

    state = jax.tree_util.tree_map_with_path(place, jax.eval_shape(create))
    step = make_train_step(lm_loss_fused, donate=True)
    for b in batches:
        batch = {"tokens": jax.ShapeDtypeStruct(
            (b, run["seq_len"]), jnp.int32,
            sharding=NamedSharding(mesh, P(run["mesh"])))}
        compiled = step.lower(state, batch).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "global_batch": b, "chips": n, "mesh": run["mesh"],
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "held_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes,
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "all_gathers": text.count(" all-gather("),
            "reduce_scatters": text.count(" reduce-scatter("),
            "all_reduces": text.count(" all-reduce(")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
