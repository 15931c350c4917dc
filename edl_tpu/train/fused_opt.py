"""Fused optimizer: bucketed single-pass updates + quantized resident
moments.

The plain step leaves the optimizer to optax: a long chain of
per-leaf elementwise HLOs, with fp32 moments dominating resident state,
checkpoint bytes and p2p migration bytes. This module is the raw-speed
variant (ROADMAP item 4): parameters/gradients are packed into the SAME
flat dtype-grouped buckets as the DCN gradient path
(train/comm.plan_buckets, align = the 128 TPU lane width) and each
bucket's whole update — momentum-SGD or Adam(W), optionally with the
moments dequantized-updated-requantized in place — runs as ONE Pallas
VMEM pass (ops/opt_kernels.py; plain-XLA expression everywhere off-TPU,
bitwise-identical by construction).

Resident moment formats (``quant``):

- ``off``: fp32 bucket buffers. The fused fp32 momentum-SGD update is
  BITWISE-identical to optax.chain(add_decayed_weights, sgd(momentum))
  + apply_updates (pinned by ``update_parity_gate`` and CI); Adam
  matches optax.adamw to float tolerance (bias-correction pow order).
- ``int8``/``fp8``: each moment plane lives between steps as
  (q, scale, rq, rscale) — the quantized moment plus its quantized
  error-feedback RESIDUAL (ops/opt_kernels.QPlane). 2 bytes/element vs
  fp32's 4: optimizer state, checkpoint bytes and migration
  donor-manifest bytes halve, and elastic peer restores ship half the
  moment bytes. Behind the r21 gate discipline: the quantized path
  must keep >= 1-envelope of the dense run's loss improvement on the
  CNN + transformer convergence smokes (``convergence_smoke``,
  ``python -m edl_tpu.train.fused_opt smoke`` in CI).

Integration: ``FusedOptimizer`` is duck-typed where optax's
GradientTransformation sits (``TrainState.create(tx=fused_sgd(...))``);
``TrainState.apply_gradients`` routes through ``fused_apply`` whenever
the tx provides it, so the plain jit step and the comm-path step both
pick it up without changes.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.ops import opt_kernels as ok
from edl_tpu.train import comm as comm_lib
from edl_tpu.utils.config import env_str

OPTIMIZERS = ok.OPTIMIZERS
QUANT_MODES = ok.QUANT_MODES
FUSED_MODES = ("off", "fp32", "int8", "fp8")   # the --fused-opt knob

_LANE = 128

ScheduleOrFloat = Union[float, Callable[[jnp.ndarray], jnp.ndarray]]


class FusedOptState(NamedTuple):
    """Resident optimizer state: per-bucket moment buffers.

    count: int32 step counter (Adam bias correction; schedule input).
    m: per-bucket first moments — fp32 buffers (quant='off') or
       ops.opt_kernels.QPlane quadruples.
    v: per-bucket second moments (Adam only; () for momentum-SGD).
    """

    count: jnp.ndarray
    m: tuple
    v: tuple


class FusedOptimizer:
    """Bucketed fused optimizer with optax-compatible ``init``.

    Not an optax.GradientTransformation: the fused path has no
    "updates tree" intermediate (the param write happens inside the
    kernel pass), so instead of ``update`` it exposes
    ``fused_apply(grads, opt_state, params) -> (new_params,
    new_opt_state)`` — the hook TrainState.apply_gradients dispatches
    on. ``update`` raises with that pointer rather than silently
    de-fusing.
    """

    def __init__(self, optimizer: str, learning_rate: ScheduleOrFloat,
                 *, momentum: float = 0.9, b1: float = 0.9,
                 b2: float = 0.999, eps: float = 1e-8,
                 weight_decay: float = 0.0, quant: str = "off",
                 bucket_mb: float = 4.0):
        if optimizer not in OPTIMIZERS:
            raise ValueError(f"optimizer must be one of {OPTIMIZERS}, "
                             f"got {optimizer!r}")
        if quant not in QUANT_MODES:
            raise ValueError(f"quant must be one of {QUANT_MODES}, "
                             f"got {quant!r}")
        if bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be > 0, got {bucket_mb}")
        self.optimizer = optimizer
        self.learning_rate = learning_rate
        self.momentum = float(momentum)
        self.b1, self.b2, self.eps = float(b1), float(b2), float(eps)
        self.weight_decay = float(weight_decay)
        self.quant = quant
        self.bucket_mb = float(bucket_mb)

    # plan is a pure function of leaf shapes/dtypes (deterministic —
    # the same seeded-exact contract as the comm path), so recomputing
    # per call is safe; calls happen at trace time only.
    def plan(self, params) -> comm_lib.BucketPlan:
        plan = comm_lib.plan_buckets(params, self.bucket_mb,
                                     align=_LANE)
        for b in plan.buckets:
            if not jnp.issubdtype(b.dtype, jnp.floating):
                raise ValueError(
                    f"fused optimizer supports float params only; got "
                    f"a {b.dtype} bucket")
        return plan

    def init(self, params) -> FusedOptState:
        plan = self.plan(params)

        def zero(b):
            if self.quant == "off":
                return jnp.zeros((b.padded,), jnp.float32)
            return ok.zero_plane(b.padded, self.quant)

        m = tuple(zero(b) for b in plan.buckets)
        v = (tuple(zero(b) for b in plan.buckets)
             if self.optimizer == "adam" else ())
        return FusedOptState(count=jnp.zeros((), jnp.int32), m=m, v=v)

    def update(self, grads, state, params=None):
        raise NotImplementedError(
            "FusedOptimizer has no de-fused update(); the param write "
            "happens inside the kernel pass. Use fused_apply(grads, "
            "opt_state, params) — TrainState.apply_gradients does so "
            "automatically.")

    def fused_apply(self, grads, opt_state: FusedOptState, params):
        """One fused optimizer step over every bucket.

        Returns (new_params, new_opt_state). Traceable — runs inside
        the jitted train step.
        """
        plan = self.plan(params)
        p_bufs = comm_lib.pack_buckets(params, plan)
        g_bufs = comm_lib.pack_buckets(grads, plan)
        lr = (self.learning_rate(opt_state.count)
              if callable(self.learning_rate) else self.learning_rate)
        lr = jnp.asarray(lr, jnp.float32)
        if self.optimizer == "adam":
            t = (opt_state.count + 1).astype(jnp.float32)
            c1 = 1.0 - jnp.asarray(self.b1, jnp.float32) ** t
            c2 = 1.0 - jnp.asarray(self.b2, jnp.float32) ** t
        new_p, new_m, new_v = [], [], []
        for i, b in enumerate(plan.buckets):
            p = p_bufs[i].astype(jnp.float32)
            g = g_bufs[i].astype(jnp.float32)
            if self.optimizer == "sgdm":
                pn, mn = ok.sgdm_bucket(
                    p, g, opt_state.m[i], lr, mu=self.momentum,
                    wd=self.weight_decay, quant=self.quant)
            else:
                pn, mn, vn = ok.adam_bucket(
                    p, g, opt_state.m[i], opt_state.v[i], lr, c1, c2,
                    b1=self.b1, b2=self.b2, eps=self.eps,
                    wd=self.weight_decay, quant=self.quant)
                new_v.append(vn)
            new_p.append(pn.astype(b.dtype))
            new_m.append(mn)
        new_params = comm_lib.unpack_buckets(new_p, plan)
        return new_params, FusedOptState(count=opt_state.count + 1,
                                         m=tuple(new_m),
                                         v=tuple(new_v))


def fused_sgd(learning_rate: ScheduleOrFloat, momentum: float = 0.9,
              weight_decay: float = 0.0, *, quant: str = "off",
              bucket_mb: float = 4.0) -> FusedOptimizer:
    """Fused momentum-SGD; fp32 mode is bitwise vs
    optax.chain(add_decayed_weights(wd), sgd(lr, momentum))."""
    return FusedOptimizer("sgdm", learning_rate, momentum=momentum,
                          weight_decay=weight_decay, quant=quant,
                          bucket_mb=bucket_mb)


def fused_adam(learning_rate: ScheduleOrFloat, b1: float = 0.9,
               b2: float = 0.999, eps: float = 1e-8,
               weight_decay: float = 0.0, *, quant: str = "off",
               bucket_mb: float = 4.0) -> FusedOptimizer:
    """Fused Adam(W); matches optax.adamw (eps_root=0) to float
    tolerance in fp32 mode."""
    return FusedOptimizer("adam", learning_rate, b1=b1, b2=b2, eps=eps,
                          weight_decay=weight_decay, quant=quant,
                          bucket_mb=bucket_mb)


def fused_mode(flag: str | None = None) -> str:
    """The --fused-opt knob: the flag where given (not None), else
    EDL_TPU_FUSED_OPT, else off."""
    mode = flag if flag is not None else env_str("EDL_TPU_FUSED_OPT", "off")
    if mode not in FUSED_MODES:
        raise ValueError(f"--fused-opt / EDL_TPU_FUSED_OPT must be one of "
                         f"{FUSED_MODES}, got {mode!r}")
    return mode


def make_fused_tx(optimizer: str, learning_rate: ScheduleOrFloat,
                  flag: str | None = None, **kw):
    """`fused_mode(flag)` -> tx ('off' returns None — caller keeps its
    optax chain)."""
    mode = fused_mode(flag)
    if mode == "off":
        return None
    quant = "off" if mode == "fp32" else mode
    factory = fused_sgd if optimizer == "sgdm" else fused_adam
    return factory(learning_rate, quant=quant, **kw)


def opt_state_bytes(opt_state) -> int:
    """Resident optimizer-state bytes (sum over leaves) — the metric
    the quantized modes must cut >= 1.8x."""
    return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
               if hasattr(l, "shape") else np.asarray(l).nbytes
               for l in jax.tree.leaves(opt_state))


# -- parity gate -------------------------------------------------------------


def _gate_world(seed: int = 0):
    """A small ragged param/grad tree exercising multi-bucket packing,
    lane padding and the oversized-leaf path."""
    rng = np.random.default_rng(seed)

    def leaf(*shape):
        return jnp.asarray(rng.normal(0, 0.1, size=shape)
                           .astype(np.float32))

    params = {"dense": {"kernel": leaf(257, 33), "bias": leaf(33)},
              "emb": leaf(64, 64), "norm": {"scale": leaf(129)}}
    grads = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(0, 0.02, size=p.shape)
                              .astype(np.float32)), params)
    return params, grads


def _run_fused(tx: FusedOptimizer, params, grads, steps: int):
    state = tx.init(params)
    for _ in range(steps):
        params, state = tx.fused_apply(grads, state, params)
    return params, state


def update_parity_gate(seed: int = 0, steps: int = 3,
                       lr: float = 0.1, wd: float = 1e-4) -> dict:
    """The fused path's equivalence gate (CI runs it in `smoke`).

    - fused-fp32 momentum-SGD is BITWISE-identical to the optax chain;
    - fused-fp32 Adam matches optax.adamw within float tolerance;
    - for every optimizer x quant mode, the interpret-mode Pallas
      kernel is BITWISE-identical to the plain-XLA fallback (the same
      jnp math on both sides — this is the structural guarantee the
      TPU path inherits).
    """
    import optax

    params, grads = _gate_world(seed)
    report: dict = {"steps": steps}

    def optax_run(tx):
        # jitted like the fused path, so XLA's fusion (fma contraction)
        # is identical on both sides of the bitwise comparison
        @jax.jit
        def one(p, s):
            u, s = tx.update(grads, s, p)
            return optax.apply_updates(p, u), s

        p, s = params, tx.init(params)
        for _ in range(steps):
            p, s = one(p, s)
        return p

    def kernel_vs_xla(tx):
        p_xla, s_xla = _run_fused(tx, params, grads, steps)
        prev = ok._FORCE_INTERPRET
        ok.force_pallas_interpret()
        try:
            p_krn, s_krn = _run_fused(tx, params, grads, steps)
        finally:
            ok._FORCE_INTERPRET = prev
        return (comm_lib.tree_bitwise_equal(p_xla, p_krn)
                and comm_lib.tree_bitwise_equal(s_xla, s_krn))

    # momentum-SGD: fp32 fused vs the optax chain, bitwise
    sgd_ref = optax_run(optax.chain(optax.add_decayed_weights(wd),
                                    optax.sgd(lr, momentum=0.9)))
    sgd_fused, _ = _run_fused(fused_sgd(lr, 0.9, wd, bucket_mb=0.05),
                              params, grads, steps)
    report["sgdm_fp32_vs_optax_bitwise"] = comm_lib.tree_bitwise_equal(
        sgd_ref, sgd_fused)

    # Adam: fp32 fused vs optax.adamw, float tolerance
    adam_ref = optax_run(optax.adamw(lr, weight_decay=wd))
    adam_fused, _ = _run_fused(fused_adam(lr, weight_decay=wd,
                                          bucket_mb=0.05),
                               params, grads, steps)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(adam_ref),
                              jax.tree.leaves(adam_fused)))
    report["adam_fp32_vs_optax_max_err"] = err
    report["adam_fp32_vs_optax_close"] = err <= 1e-5

    # kernel == XLA, every optimizer x quant mode
    for q in ok.QUANT_MODES:
        report[f"sgdm_{q}_kernel_bitwise"] = kernel_vs_xla(
            fused_sgd(lr, 0.9, wd, quant=q, bucket_mb=0.05))
        report[f"adam_{q}_kernel_bitwise"] = kernel_vs_xla(
            fused_adam(lr, weight_decay=wd, quant=q, bucket_mb=0.05))
    report["ok"] = all(v for k, v in report.items()
                       if k.endswith(("_bitwise", "_close")))
    return report


# -- convergence-parity smoke (the CI gate for quantized moments) ------------


def convergence_smoke(quant: str = "int8", steps: int = 40,
                      envelope: float = 0.25) -> dict:
    """Quantized-moment convergence vs the dense optax reference.

    Same discipline as comm.convergence_smoke: momentum-SGD trains the
    BN CNN, Adam trains the markov transformer, each against its dense
    reference from the SAME init; both runs must LEARN and the
    quantized run must keep >= 1-envelope of dense's loss improvement
    (relative envelope — one pin across models whose loss scales
    differ by 40x).
    """
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from edl_tpu.parallel import mesh as mesh_lib
    from edl_tpu.train.state import TrainState
    from edl_tpu.train.step import make_train_step

    world = jax.device_count()
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": -1}))
    report: dict = {"quant": quant, "steps": steps,
                    "envelope": envelope, "world": world}

    def run(name, loss_fn, state_dense, state_q, batch):
        placed = mesh_lib.shard_batch(mesh, batch)
        rep = lambda t: jax.device_put(  # noqa: E731
            t, NamedSharding(mesh, P()))
        step = make_train_step(loss_fn, donate=False)
        s_a = jax.tree.map(rep, state_dense)
        s_b = jax.tree.map(rep, state_q)
        first = last_a = last_b = None
        for _ in range(steps):
            s_a, m_a = step(s_a, placed)
            s_b, m_b = step(s_b, placed)
            if first is None:
                first = float(m_a["loss"])
            last_a, last_b = float(m_a["loss"]), float(m_b["loss"])
        delta = abs(last_a - last_b)
        improvement = max(first - last_a, 1e-9)
        report[name] = {
            "loss_initial": round(first, 4),
            "loss_dense": round(last_a, 4),
            "loss_quant": round(last_b, 4),
            "delta_rel": round(delta / improvement, 5),
            "opt_bytes_dense": opt_state_bytes(state_dense.opt_state),
            "opt_bytes_quant": opt_state_bytes(state_q.opt_state),
            "learned": last_a < first and last_b < first,
            "within_envelope": delta <= envelope * improvement}

    # momentum-SGD on the BN CNN (batch_stats ride apply_gradients)
    loss_fn, state, batch = comm_lib._smoke_cnn(world)
    state_q = TrainState.create(
        apply_fn=state.apply_fn, params=state.params,
        tx=fused_sgd(0.05, 0.9, quant=quant, bucket_mb=0.05),
        batch_stats=state.batch_stats)
    run("cnn_sgdm", loss_fn, state, state_q, batch)

    # Adam on the markov transformer
    loss_fn, state, batch = comm_lib._smoke_transformer(world, mesh)
    lr = 1e-2
    state_a = TrainState.create(apply_fn=state.apply_fn,
                                params=state.params,
                                tx=optax.adamw(lr))
    state_q = TrainState.create(
        apply_fn=state.apply_fn, params=state.params,
        tx=fused_adam(lr, quant=quant, bucket_mb=0.05))
    run("transformer_adam", loss_fn, state_a, state_q, batch)

    report["ok"] = all(
        report[k]["learned"] and report[k]["within_envelope"]
        and report[k]["opt_bytes_dense"]
        >= 1.8 * report[k]["opt_bytes_quant"]
        for k in ("cnn_sgdm", "transformer_adam"))
    return report


def _main(argv=None) -> int:
    import argparse
    import json

    parser = argparse.ArgumentParser(prog="edl_tpu.train.fused_opt")
    sub = parser.add_subparsers(dest="cmd", required=True)
    smoke = sub.add_parser(
        "smoke", help="fused-optimizer gate: interpret-mode kernel "
                      "equivalence + quantized-moment convergence "
                      "parity vs the dense optax reference")
    smoke.add_argument("--quant", choices=("int8", "fp8"),
                       default="int8")
    smoke.add_argument("--steps", type=int, default=40)
    smoke.add_argument("--envelope", type=float, default=0.25,
                       help="RELATIVE loss envelope: the quantized run "
                            "must keep >= 1-envelope of dense's loss "
                            "improvement")
    args = parser.parse_args(argv)
    gate = update_parity_gate()
    conv = convergence_smoke(quant=args.quant, steps=args.steps,
                             envelope=args.envelope)
    report = {"kernel_gate": gate, "convergence": conv,
              "ok": gate["ok"] and conv["ok"]}
    print(json.dumps({"fused_opt_smoke": report}))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    import sys

    sys.exit(_main())
