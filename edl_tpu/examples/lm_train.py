"""Causal-LM pretraining over file-backed token shards.

The NLP-training face of the flagship trainer (no reference counterpart
— its models are CNNs + served ERNIE; this is the net-new transformer
path that pairs with ring attention and the Pallas flash kernel):
dp/fsdp-sharded transformer LM over a device mesh, token shards streamed
through the deterministic file pipeline, cosine LR with warmup, optional
sharded checkpoints (per-process chunks + resharding restore), tokens/s
+ eval-loss benchmark log.

  python -m edl_tpu.examples.lm_train --make-synthetic 4 \\
      --data-dir /tmp/lm --d-model 128 --n-layers 2 --seq-len 128 \\
      --epochs 2 --batch-size 16
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from edl_tpu.data.pipeline import DataLoader, FileSource
from edl_tpu.models.transformer import (Transformer, TransformerConfig,
                                        afmoe_config, granite_hybrid_config,
                                        lm_loss_fn, lm_loss_fused,
                                        olmoe_config)
from edl_tpu.obs import trace
from edl_tpu.parallel import distributed, mesh as mesh_lib, sharding as shd
from edl_tpu.train import lr as lr_lib
from edl_tpu.train.benchlog import BenchmarkLog
from edl_tpu.train.loop import LoopConfig, TrainLoop
from edl_tpu.train.state import TrainState
from edl_tpu.train.step import make_train_step
from edl_tpu.utils.config import from_env, given
from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.examples.lm_train")

# --arch values that train one chip's share of the experts
# (--experts-held) with no exchange between chips, and the archs each
# size option belongs to: what the refusals below name
SHARE_ARCHS = ("afmoe", "sdar", "joyai")
SIZE_FLAGS = {"--experts-held": SHARE_ARCHS,
              "--dense-layers": ("afmoe", "joyai"),
              "--window": ("afmoe",)}


def make_synthetic_shards(data_dir: str, n_files: int, rows: int,
                          seq_len: int, vocab: int, seed: int = 0) -> None:
    """Markov-chain token shards (learnable: next-token depends on
    current token through a fixed random transition table)."""
    os.makedirs(data_dir, exist_ok=True)
    gen = np.random.default_rng(55)
    # each token has 8 plausible successors
    successors = gen.integers(0, vocab, size=(vocab, 8))
    for i in range(n_files + 1):  # last = validation
        rng = np.random.default_rng(seed * 271 + i)
        toks = np.empty((rows, seq_len), np.int32)
        toks[:, 0] = rng.integers(0, vocab, size=rows)
        for t in range(1, seq_len):
            pick = rng.integers(0, 8, size=rows)
            toks[:, t] = successors[toks[:, t - 1], pick]
        name = "val.npz" if i == n_files else f"train-{i:04d}.npz"
        np.savez(os.path.join(data_dir, name), tokens=toks)


def make_optimizer(lr: float, total_steps: int, warmup_steps: int,
                   fused_opt: str | None = None):
    """The trainer's optimizer: AdamW (weight decay 0.01 on every
    parameter) on a cosine schedule over ``total_steps`` with a linear
    warm-up; the fused path where a flag or the environment asks."""
    schedule = lr_lib.cosine_with_warmup(
        lr, total_steps, min(warmup_steps, max(1, total_steps // 10)))
    from edl_tpu.train.fused_opt import make_fused_tx
    tx = make_fused_tx("adam", schedule, fused_opt, weight_decay=0.01)
    return optax.adamw(schedule, weight_decay=0.01) if tx is None else tx


def main(argv=None) -> int:
    # start-up by phase, from the kernel's start of this process: the
    # imports above are its age when this line runs
    startup = trace.Phases("train.startup", "startup",
                           age_s=trace.process_age_s() or 0.0)
    startup.done("imports")
    parser = argparse.ArgumentParser(prog="edl_tpu.examples.lm_train")
    parser.add_argument("--data-dir", required=True)
    parser.add_argument("--make-synthetic", type=int, default=0)
    parser.add_argument("--rows-per-file", type=int, default=512)
    parser.add_argument("--loader-workers", type=int, default=None,
                        help="input-plane worker PROCESSES with "
                             "shared-memory batch hand-off (default: "
                             "$EDL_TPU_LOADER_WORKERS, else 0 = inline)")
    parser.add_argument("--vocab", type=int, default=512)
    parser.add_argument("--seq-len", type=int, default=256)
    parser.add_argument("--d-model", type=int, default=256)
    parser.add_argument("--n-heads", type=int, default=8)
    parser.add_argument("--n-layers", type=int, default=4)
    parser.add_argument("--d-ff", type=int, default=1024)
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument("--schedule-epochs", type=int, default=0,
                        help="LR horizon (default --epochs); pin to the "
                             "job's total for elastic segments")
    parser.add_argument("--batch-size", type=int, default=32,
                        help="GLOBAL batch size")
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--warmup-steps", type=int, default=100)
    parser.add_argument("--bf16", action="store_true")
    parser.add_argument("--fused-loss", action="store_true",
                        help="streamed-vocab CE: never materializes the "
                             "(B,S,V) logits (ops/fused_xent.py) — use "
                             "when the vocab is large")
    parser.add_argument("--dcn-compress", choices=("off", "topk", "int8"),
                        default=None,
                        help="cross-slice gradient wire format (default "
                             "$EDL_TPU_DCN_COMPRESS, else off): topk "
                             "ships values+indices, int8 one scale per "
                             "chip — both with error-feedback residuals "
                             "behind the loss-parity gate "
                             "(doc/design_comm.md)")
    parser.add_argument("--comm-bucket-mb", type=float, default=None,
                        help="bucket the gradient tree into N-MiB "
                             "reduction groups so late-backward buckets "
                             "overlap earlier buckets' communication "
                             "(default $EDL_TPU_COMM_BUCKET_MB, else 0 "
                             "= XLA's single fused reduction)")
    parser.add_argument("--arch", choices=("gpt2", "olmoe",
                                           "granite-hybrid", "afmoe",
                                           "sdar", "joyai"),
                        default="gpt2",
                        help="the block: gpt2 = LayerNorm, learned "
                             "positions, gelu; olmoe = models.transformer."
                             "olmoe_config (RMSNorm, RoPE, qk-norm, SwiGLU "
                             "experts, top-k gates as they are; implies "
                             "--moe, 64 experts, 8 a token unless given); "
                             "granite-hybrid = models.transformer."
                             "granite_hybrid_config (RMSNorm, no positions, "
                             "Mamba-2 mixers with grouped-query attention "
                             "among them, dense SwiGLU, a tied head, the "
                             "four multipliers; --d-ff is the MLP's width); "
                             "afmoe = models.transformer.afmoe_config "
                             "(Trinity-Mini: sliding-window and global "
                             "attention mixed, gated, 4 key/value heads of "
                             "128, four norms a block, sigmoid routing over "
                             "score + bias, a shared expert, leading dense "
                             "layers of width --d-ff; implies --moe, 128 "
                             "experts, 8 a token unless given; one chip, "
                             "which holds --experts-held of them); sdar = "
                             "models.transformer.sdar_config (SDAR-30B-A3B: "
                             "the Qwen3-MoE block, 4 key/value heads of "
                             "128, softmax top-8 of 128 experts of width "
                             "--d-ff, trained by diffusion over blocks of "
                             "--block-length: a noised and a clean copy of "
                             "every row in one pass, the loss on the masked "
                             "tokens; one chip, which holds --experts-held); "
                             "joyai = models.transformer.joyai_config "
                             "(JoyAI-LLM-Flash: latent attention, heads of "
                             "128 + 64 for q and k and 128 for v, sigmoid "
                             "top-8 of 256 experts over score + bias, a "
                             "shared expert, --dense-layers leading dense "
                             "layers of width --d-ff, one multi-token-"
                             "prediction module whose loss is added x 0.3; "
                             "one chip, which holds --experts-held)")
    parser.add_argument("--layer-types", default="",
                        help="one letter a layer. granite-hybrid: m = "
                             "mamba, a = attention (default: the "
                             "published pattern, attention at layers 5, "
                             "15, 25, ..., cut to --n-layers; the "
                             "mixers' sizes and the 8 key/value heads are "
                             "granite_hybrid_config's own). afmoe: s = "
                             "sliding window, f = full (default: sssf "
                             "repeated, cut to --n-layers; the head size, "
                             "the key/value heads, an expert's width and "
                             "the routing are afmoe_config's own)")
    parser.add_argument("--block-length", type=int, default=0,
                        help="sdar: tokens a block of the diffusion "
                             "objective (default 4, the family's)")
    parser.add_argument("--experts-held", type=int, default=0,
                        help="afmoe, sdar, joyai: the experts this chip "
                             "holds, the "
                             "first of --n-experts (default all): the "
                             "router and top-k stay over all of them, and "
                             "the layer computes the held ones' part")
    parser.add_argument("--dense-layers", type=int, default=None,
                        help="afmoe, joyai: leading layers with a dense "
                             "MLP of width --d-ff (default the published "
                             "2, joyai 1)")
    parser.add_argument("--window", type=int, default=0,
                        help="afmoe: keys a sliding layer's query sees "
                             "(default the published 2048)")
    parser.add_argument("--moe", action="store_true",
                        help="mixture-of-experts FFNs. One device: "
                             "dropless sort-and-gather dispatch into "
                             "grouped matmuls, inside the jit step. "
                             "Several: top-k capacity-factor router, "
                             "expert tables sharded over an ep mesh, "
                             "hierarchical all-to-all dispatch "
                             "(train/comm.py; doc/design_comm.md)")
    parser.add_argument("--n-experts", type=int, default=0,
                        help="expert count (default 2x device count, 64 "
                             "under --arch olmoe; must divide evenly over "
                             "the devices)")
    parser.add_argument("--moe-top-k", type=int, default=0,
                        help="experts per token (default 2, 8 under "
                             "--arch olmoe)")
    parser.add_argument("--moe-dispatch", choices=("flat", "hier"),
                        default=None,
                        help="MoE all-to-all decomposition (default "
                             "$EDL_TPU_MOE_DISPATCH, else hier): flat = "
                             "one global collective; hier = ICI leg + "
                             "cross-slice DCN leg, bitwise with flat")
    parser.add_argument("--moe-compress", choices=("off", "int8"),
                        default=None,
                        help="MoE DCN-leg wire format (default "
                             "$EDL_TPU_MOE_COMPRESS, else off): int8 "
                             "ships dispatched activations at one scale "
                             "per destination slice (parity-gated)")
    parser.add_argument("--fused-opt",
                        choices=("off", "fp32", "int8", "fp8"),
                        default=None,
                        help="fused optimizer path (train/fused_opt.py; "
                             "default $EDL_TPU_FUSED_OPT, else off): "
                             "fp32 = one kernel pass per bucket, "
                             "bitwise vs the optax chain; int8/fp8 also "
                             "hold the adam moments quantized with "
                             "error-feedback residuals (opt state and "
                             "checkpoint bytes halve, convergence-"
                             "parity gated)")
    parser.add_argument("--remat", choices=("off", "on", "auto"),
                        default="off",
                        help="per-block activation checkpointing: on = "
                             "always, auto = models.transformer."
                             "choose_remat decides from the activation-"
                             "footprint estimate vs device memory")
    parser.add_argument("--mesh", choices=("dp", "fsdp", "sp"),
                        default="dp",
                        help="dp: data parallel; fsdp: params sharded; "
                             "sp: sequence parallel — ring attention over "
                             "the sequence axis (long-context mode)")
    parser.add_argument("--ckpt-dir", default="")
    parser.add_argument("--ckpt-sharded", action="store_true")
    parser.add_argument("--ckpt-steps", type=int, default=None,
                        help="also checkpoint every N optimizer steps "
                             "(cheap under async saves; default "
                             "$EDL_TPU_CKPT_STEPS, else epoch-end only)")
    parser.add_argument("--ckpt-sync", action="store_true",
                        help="synchronous saves (escape hatch; default "
                             "async snapshot-then-write)")
    parser.add_argument("--benchmark-log", default="")
    parser.add_argument("--profile", default="",
                        help="jax profiler trace dir (steps 10-15, rank 0)")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    args.moe = args.moe or args.arch in ("olmoe", *SHARE_ARCHS)
    if args.moe and args.arch == "granite-hybrid":
        raise SystemExit("--arch granite-hybrid has a dense MLP "
                         "(num_local_experts 0); --moe conflicts")
    for flag, archs in SIZE_FLAGS.items():
        if getattr(args, flag[2:].replace("-", "_")) \
                and args.arch not in archs:
            raise SystemExit(
                f"{flag}: only --arch {', '.join(archs)} has such a size; "
                f"--arch {args.arch} conflicts")
    if args.block_length and args.arch != "sdar":
        raise SystemExit(f"--block-length: only --arch sdar trains by "
                         f"diffusion over blocks; --arch {args.arch} "
                         "conflicts")
    if args.arch in SHARE_ARCHS and (args.moe_dispatch
                                     or args.moe_compress):
        raise SystemExit(
            f"--arch {args.arch} computes one chip's share of the experts "
            "and has no exchange: --moe-dispatch / --moe-compress conflict")
    if args.profile:
        trace.collect(args.profile)  # spans from here on, start-up's too

    if 0 < args.schedule_epochs < args.epochs:
        raise SystemExit(
            f"--schedule-epochs {args.schedule_epochs} < --epochs "
            f"{args.epochs}: epochs past the horizon would train at "
            "LR ~0 (the horizon is the job TOTAL; the stop point is "
            "--epochs)")
    startup.done("args")
    distributed.force_platform_from_env()
    env = distributed.init_from_env()
    jax.devices()  # the runtime starts here, not inside a later phase
    startup.done("runtime")
    world = max(1, env.world_size)
    rank = max(0, env.rank)
    if args.make_synthetic and rank == 0:
        make_synthetic_shards(args.data_dir, args.make_synthetic,
                              args.rows_per_file, args.seq_len, args.vocab,
                              args.seed)
    if args.make_synthetic and jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("edl_lm_data_gen")

    files = sorted(os.path.join(args.data_dir, f)
                   for f in os.listdir(args.data_dir)
                   if f.startswith("train-") and f.endswith(".npz"))
    if not files:
        raise SystemExit(f"no train-*.npz under {args.data_dir}")
    if args.batch_size % world:
        raise SystemExit("global batch not divisible by world")
    local_bs = args.batch_size // world

    # every option below: the flag where given, else its environment
    # name (bound on the dataclass of the module that consumes it)
    loop_cfg = from_env(LoopConfig, num_epochs=args.epochs,
                        ckpt_dir=args.ckpt_dir or env.checkpoint_path
                        or None, ckpt_sharded=args.ckpt_sharded,
                        profile_dir=args.profile or None,
                        **given(ckpt_every_steps=args.ckpt_steps,
                                ckpt_async=False if args.ckpt_sync
                                else None))

    if args.mesh == "sp":
        if world > 1:
            # rank-sharded loading + replicate_host_tree assume a data
            # axis; an sp-only mesh would feed divergent "replicated"
            # batches across processes — corrupt, not slow.
            raise SystemExit("--mesh sp is single-process long-context "
                             "mode; combine sp with dp/fsdp axes for "
                             "multi-pod (see parallel/mesh.MeshSpec)")
        n_dev = jax.device_count()
        if args.seq_len % n_dev:
            raise SystemExit(f"--mesh sp shards the sequence over "
                             f"{n_dev} devices; --seq-len {args.seq_len} "
                             f"is not divisible by {n_dev}")
    if args.moe:
        if args.mesh != "dp":
            raise SystemExit(f"--moe owns the ep mesh (expert tables "
                             f"sharded over every chip); --mesh "
                             f"{args.mesh} conflicts")
        if args.batch_size % jax.device_count():
            raise SystemExit(f"--moe routes per chip: --batch-size "
                             f"{args.batch_size} must divide over "
                             f"{jax.device_count()} devices")
    # env-aware: multi-slice jobs get the hybrid ICI x DCN layout (needs
    # a dp axis — or ep under --moe — to carry DCN; other --mesh kinds
    # fail fast there); single-slice worlds get the flat mesh as before
    mesh = distributed.make_mesh_from_env(
        mesh_lib.MeshSpec({"ep" if args.moe else args.mesh: -1}), env)
    # the manual dp gradient path, where a flag or the environment asks
    from edl_tpu.train import comm
    comm_cfg = comm.CommConfig.from_flags(
        bucket_mb=args.comm_bucket_mb, compress=args.dcn_compress)
    if comm_cfg is not None and args.mesh != "dp":
        raise SystemExit(
            f"--dcn-compress/--comm-bucket-mb own the dp gradient "
            f"reduction; --mesh {args.mesh} keeps the XLA-partitioned "
            "step (fsdp/tp collectives are slice-local already)")
    if comm_cfg is not None and args.moe and comm_cfg.compress != "off":
        raise SystemExit("--dcn-compress compresses the dp gradient "
                         "wire; under --moe the wire knob is "
                         "--moe-compress (gradient compression over "
                         "the ep axis is not parity-gated yet)")
    make_cfg, arch_kw = TransformerConfig, {}
    if args.arch == "olmoe":
        make_cfg = olmoe_config
        arch_kw = {k: v for k, v in (("n_experts", args.n_experts),
                                    ("moe_top_k", args.moe_top_k)) if v}
    elif args.arch == "granite-hybrid":
        make_cfg = granite_hybrid_config
        if args.layer_types:
            if set(args.layer_types) - set("ma"):
                raise SystemExit(f"--layer-types {args.layer_types!r}: one "
                                 "letter a layer, m (mamba) or a "
                                 "(attention)")
            arch_kw["layer_types"] = tuple(
                {"m": "mamba", "a": "attention"}[c]
                for c in args.layer_types)
    elif args.arch == "afmoe":
        make_cfg = afmoe_config
        arch_kw = {k: v for k, v in (
            ("n_experts", args.n_experts), ("moe_top_k", args.moe_top_k),
            ("experts_held", args.experts_held), ("window", args.window))
            if v}
        if args.dense_layers is not None:
            arch_kw["n_dense_layers"] = args.dense_layers
        if args.layer_types:
            if set(args.layer_types) - set("sf"):
                raise SystemExit(f"--layer-types {args.layer_types!r}: one "
                                 "letter a layer, s (sliding) or f (full)")
            arch_kw["layer_types"] = tuple(
                {"s": "sliding", "f": "full"}[c] for c in args.layer_types)
    elif args.arch == "sdar":
        from edl_tpu.models.transformer import sdar_config as make_cfg
        arch_kw = {k: v for k, v in (
            ("n_experts", args.n_experts), ("moe_top_k", args.moe_top_k),
            ("experts_held", args.experts_held),
            ("block_length", args.block_length)) if v}
    elif args.arch == "joyai":
        from edl_tpu.models.transformer import joyai_config as make_cfg
        arch_kw = {k: v for k, v in (
            ("n_experts", args.n_experts), ("moe_top_k", args.moe_top_k),
            ("experts_held", args.experts_held)) if v}
        if args.dense_layers is not None:
            arch_kw["n_dense_layers"] = args.dense_layers
    elif args.moe:
        arch_kw = dict(moe=True,
                      n_experts=args.n_experts or 2 * jax.device_count(),
                      moe_top_k=args.moe_top_k or 2)
    if args.arch in SHARE_ARCHS and jax.device_count() > 1:
        raise SystemExit(
            f"--arch {args.arch} trains one chip's share of the experts "
            f"(--experts-held) with no exchange between chips; "
            f"{jax.device_count()} devices conflict")
    cfg = make_cfg(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_len=args.seq_len,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        # the comm/moe step's manual region is mesh-free: sharding
        # constraints / nested shard_maps would clash with the manual
        # dp/ep axis — each shard computes exactly one chip's backward
        mesh=None if (comm_cfg is not None or args.moe) else mesh,
        **arch_kw)
    if args.remat != "off":
        from edl_tpu.models.transformer import auto_remat, kept_bytes
        cfg = (auto_remat(cfg, local_bs)
               if args.remat == "auto"
               else dataclasses.replace(cfg, remat=True))
        log.info("remat=%s (mode %s)", cfg.remat, args.remat)
        if cfg.remat:
            kept = kept_bytes(cfg, local_bs, args.seq_len)
            log.info("remat keeps beside each block's input, of %d x %d "
                     "tokens a step: %s, %d B in all", local_bs,
                     args.seq_len, ", ".join(
                         f"{k} {v} B" for k, v in kept.items()),
                     sum(kept.values()))
    model = Transformer(cfg)

    source = FileSource(files)
    if cfg.block_length:
        # the noise is the batch's: a row's from (seed, epoch, its index)
        from edl_tpu.data import block_noise
        if args.seq_len % cfg.block_length:
            raise SystemExit(f"--block-length {cfg.block_length} does not "
                             f"divide --seq-len {args.seq_len}")
        source = block_noise.RowIndexed(source)
    loader = DataLoader(source, local_bs, rank=rank, world=world,
                        seed=args.seed, num_workers=args.loader_workers)
    steps_per_epoch = loader.steps_per_epoch()
    total_steps = steps_per_epoch * (args.schedule_epochs or args.epochs)
    # --batch-size is GLOBAL: LR stays batch-tied across elastic resizes
    # (scale_for_world is for per-pod batch semantics)
    tx = make_optimizer(args.lr, total_steps, args.warmup_steps,
                        args.fused_opt)
    if hasattr(tx, "quant"):
        log.info("fused optimizer path: adam, moments %s", tx.quant)

    # one row per batch shard: the flash kernel runs under a shard_map
    # over the batch axes, which a single row cannot be split over
    startup.done("mesh_model")
    toks0 = jnp.zeros((mesh_lib.dp_size(mesh), args.seq_len), jnp.int32)
    variables = shd.init_sharded(
        lambda: model.init(jax.random.PRNGKey(args.seed), toks0,
                           train=False), mesh)
    # what no gradient trains (afmoe's routing bias) rides the state
    # where a BatchNorm's statistics do
    state = TrainState.create(apply_fn=model.apply,
                              params=variables["params"], tx=tx,
                              batch_stats=variables.get("batch_stats"))
    jax.block_until_ready(state)
    startup.done("state_init")
    # a moe model's loss takes its routers' terms, weighted by its config
    loss = lm_loss_fused if args.fused_loss else lm_loss_fn
    # several chips: experts over the ep axis, through the manual region
    # and its capacity router; one chip: the jit step like a dense model
    manual_ep = args.moe and jax.device_count() > 1
    if manual_ep:
        moe_cfg = from_env(comm.MoEDispatchConfig, **given(
            mode=args.moe_dispatch, compress=args.moe_compress))

        def moe_loss_factory(wire):
            wired = Transformer(dataclasses.replace(cfg, moe_wire=wire))
            return functools.partial(loss, apply_fn=wired.apply)

        step = comm.make_moe_comm_step(
            moe_loss_factory, mesh=mesh,
            topology=distributed.slice_topology(env),
            config=comm_cfg, donate=True, moe_config=moe_cfg)
        log.info("moe path: E=%d top_k=%d dispatch=%s compress=%s",
                 cfg.n_experts, cfg.moe_top_k, moe_cfg.mode,
                 moe_cfg.compress)
    elif comm_cfg is not None:
        step = comm.make_comm_train_step(
            loss, mesh=mesh, config=comm_cfg,
            topology=distributed.slice_topology(env), donate=True)
        log.info("dcn-aware gradient path: bucket=%.1fMiB compress=%s",
                 comm_cfg.target_mb, comm_cfg.compress)
    else:
        step = make_train_step(loss, donate=True)
        if args.moe:
            log.info("moe path: E=%d top_k=%d dropless, in the jit step; "
                     "experts: %s", cfg.n_experts, cfg.moe_top_k,
                     "gate|up one grouped product in the backward"
                     if cfg.experts_one_cotangent else
                     "gate, up two grouped products" if cfg.moe_gated
                     else "gelu, two tables")
    log.info("world=%d rank=%d devices=%d params=%s steps/epoch=%d",
             world, rank, jax.device_count(),
             sum(p.size for p in jax.tree.leaves(state.params)),
             steps_per_epoch)
    dev = jax.devices()[0]
    log.info("device: platform=%s kind=%r count=%d attention=%s",
             dev.platform, dev.device_kind, jax.device_count(),
             "ring" if cfg.use_ring else
             "flash" if cfg.block_length or cfg.use_flash(args.seq_len)
             else "dense")
    log.info("state bytes per device: %s", shd.bytes_per_device(state))
    if args.arch == "sdar":
        log.info("sdar: diffusion over blocks of %d, %d + %d positions a "
                 "row (noised + clean), mask token %d, t uniform on "
                 "(%g, 1]; %d q / %d kv heads x %d, experts %d-%d of %d "
                 "held, top-%d softmax, renormalised",
                 cfg.block_length, args.seq_len, args.seq_len, cfg.mask_id,
                 block_noise.T_MIN, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
                 cfg.experts_offset,
                 cfg.experts_offset + cfg.held_experts - 1, cfg.n_experts,
                 cfg.moe_top_k)
    elif args.arch == "afmoe":
        dense = cfg.n_dense_layers
        log.info("afmoe: layers %s|%s, window %d, %d q / %d kv heads x %d, "
                 "experts %d-%d of %d held, top-%d %s x %s, %d shared",
                 "d" * dense,
                 "".join(k[0] for k in cfg.layer_types[dense:]), cfg.window,
                 cfg.n_heads, cfg.kv_heads, cfg.head_dim,
                 cfg.experts_offset,
                 cfg.experts_offset + cfg.held_experts - 1, cfg.n_experts,
                 cfg.moe_top_k, cfg.moe_score, cfg.moe_route_scale,
                 cfg.moe_shared)
    elif args.arch == "joyai":
        dense = cfg.n_dense_layers
        log.info("joyai: %d dense + %d expert layers + %d mtp, mla q %d / "
                 "kv %d, %d heads x (%d + %d | %d), experts %d-%d of %d "
                 "held, top-%d %s x %s, %d shared, mtp x %s",
                 dense, cfg.n_layers - dense, cfg.mtp_layers,
                 cfg.q_lora_rank, cfg.kv_lora_rank, cfg.n_heads,
                 cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                 cfg.experts_offset,
                 cfg.experts_offset + cfg.held_experts - 1, cfg.n_experts,
                 cfg.moe_top_k, cfg.moe_score, cfg.moe_route_scale,
                 cfg.moe_shared, cfg.mtp_weight)
    elif cfg.layer_types:
        from edl_tpu.ops import ssd, ssm_stages
        sizes = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
        log.info("hybrid: layers %s, %s, %s, kv heads %d of %d",
                 "".join(k[0] for k in cfg.layer_types),
                 ssd.describe(min(cfg.ssm_chunk, args.seq_len), *sizes),
                 ssm_stages.describe(args.seq_len, *sizes),
                 cfg.kv_heads, cfg.n_heads)
    if args.fused_loss:
        from edl_tpu.ops import fused_xent
        # the rows one chip sweeps: the manual regions (comm, ep) split
        # the batch over every chip too, without a mesh in the config
        shards = (cfg.xent_shards() if cfg.mesh is not None
                  else jax.device_count())
        log.info("fused-loss: %s", fused_xent.describe(
            args.batch_size * args.seq_len // shards, cfg.vocab_size))

    eval_toks = None
    val_path = os.path.join(args.data_dir, "val.npz")
    if os.path.exists(val_path):
        with np.load(val_path) as z:
            eval_toks = z["tokens"][: 4 * local_bs]

    def eval_batches():
        """The validation rows a batch at a time; under the diffusion
        objective each with a noise of its own, the same at every epoch
        (an epoch of -1: no training row's)."""
        batches = ({"tokens": eval_toks[lo:lo + local_bs],
                    "row": np.arange(lo, lo + local_bs)}
                   for lo in range(0, len(eval_toks) - local_bs + 1,
                                   local_bs))
        if cfg.block_length:
            return block_noise.with_noise(
                batches, seed=args.seed, epoch=-1,
                block_length=cfg.block_length)
        return ({"tokens": b["tokens"]} for b in batches)

    # eval must honor the fused path too — the dense loss would
    # materialize exactly the logits tensor --fused-loss exists to avoid
    # (MoE eval rides the dropless dispatch, whatever the training step)
    eval_step = jax.jit(lambda s, b: loss(s, s.params, b)[0])
    blog = BenchmarkLog(f"transformer_lm_{args.d_model}d{args.n_layers}L",
                        batch_size=args.batch_size, world_size=world)
    epoch_t0 = [time.perf_counter()]

    def eval_fn(state, epoch):
        elapsed = time.perf_counter() - epoch_t0[0]
        # per-rank sequences/s under the examples_per_sec key: benchlog
        # world-scales exactly that key into the global figure
        # (max_examples_per_sec_global); tokens_per_sec is pre-scaled.
        seqs_per_sec = steps_per_epoch * local_bs / max(elapsed, 1e-9)
        results = {"examples_per_sec": seqs_per_sec,
                   "tokens_per_sec": seqs_per_sec * args.seq_len * world}
        if eval_toks is not None:
            losses = [float(eval_step(state, jax.tree.map(jnp.asarray, b)))
                      for b in eval_batches()]
            results["eval_loss"] = float(np.mean(losses))
        blog.epoch(epoch, **results)
        epoch_t0[0] = time.perf_counter()
        return results

    # a restored state goes where the initial one was: sharded leaves
    # (fsdp parameters and moments, ep expert tables) stay sharded
    placement = shd.placement_of(state, mesh)
    loop = TrainLoop(
        step, state, mesh=mesh, config=loop_cfg, eval_fn=eval_fn,
        place_state=lambda t: jax.device_put(t, placement),
        batch_axes=("ep",) if args.moe else None)
    # The loop owns the state from here. A resume replaces it with the
    # restored one, and a reference kept here would hold the initial
    # parameters and moments in device memory beside it for the whole
    # run (at LM-large that is 6.5 GB, and the step no longer fits).
    del state, variables

    def data_fn(epoch):
        if cfg.block_length:
            return block_noise.with_noise(
                loader.epoch(epoch), seed=args.seed, epoch=epoch,
                block_length=cfg.block_length)
        return ({"tokens": b["tokens"]} for b in loader.epoch(epoch))

    data_fn.close = loader.close  # TrainLoop tears down the mp workers
    startup.done("loop_init")
    log.info("startup: process_start\u2192run %.3fs (%s)", *startup.emit())
    status = loop.run(data_fn)
    blog.extra(**loop.ckpt_stats())  # save-stall / restore accounting
    if comm_cfg is not None or manual_ep:
        blog.extra(**step.stats())  # bucket plan + DCN wire accounting
    if rank == 0 and args.benchmark_log:
        blog.write(args.benchmark_log, rank)
    final = blog.finalize().get("final", {})
    log.info("done: epoch=%d step=%d %s", status.epoch, status.step, final)
    if "eval_loss" in final:
        print(f"final_eval_loss={final['eval_loss']:.4f}")
    distributed.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
