"""`compile_step_hybrid.py` for a configuration whose trainer is started
with `--arch joyai`: the train step `lm_train` builds for it on one chip
(both streamed CE sweeps, per-block remat as the file's flags say, the
flash kernels at keys of 192 and values of 128, the balancing biases in
`batch_stats`), compiled at its real size for a described v5e,
with the compiler's memory analysis. Nothing runs; no chip is needed;
this is not a measurement.

    JAX_PLATFORMS=cpu python benchmark/tools/compile_step_joyai.py <config.json> [global batch ...]
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
import optax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def main(argv: list[str]) -> int:
    with open(argv[0]) as f:
        config = json.load(f)
    run = config["run"]
    batches = [int(b) for b in argv[1:]] or [run["global_batch"]]
    jax.config.update("jax_enable_compilation_cache", False)
    # the program asks the backend which attention to build
    jax.default_backend = lambda: "tpu"
    from flax.core import meta

    from benchmark.reference.check_joyai import program_config
    from edl_tpu.models.transformer import Transformer, lm_loss_fused
    from edl_tpu.train.state import TrainState
    from edl_tpu.train.step import make_train_step

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = program_config(config)
    model = Transformer(cfg)

    def create():
        variables = meta.unbox(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, run["seq_len"]), jnp.int32),
            train=False))
        return TrainState.create(
            apply_fn=model.apply, params=variables["params"],
            tx=optax.adamw(run["lr"], weight_decay=0.01),
            batch_stats=variables["batch_stats"])

    state = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=one_chip),
        jax.eval_shape(create))
    step = make_train_step(lm_loss_fused, donate=True)
    for b in batches:
        batch = {"tokens": jax.ShapeDtypeStruct(
            (b, run["seq_len"]), jnp.int32, sharding=one_chip)}
        compiled = step.lower(state, batch).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "global_batch": b, "chips": 1,
            "parameters": sum(p.size for p in jax.tree.leaves(state.params)),
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "held_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes,
            "tpu_custom_calls": text.count("tpu_custom_call")}),
            flush=True)
        if os.environ.get("EDL_BENCH_KEEP_HLO"):
            with open(os.environ["EDL_BENCH_KEEP_HLO"], "w") as f:
                f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
