"""Flagship classification trainer: ResNet50_vd over file-backed data.

Capability of the reference's 690-line flagship trainer
(example/collective/resnet50/train_with_fleet.py:347-658): full LR recipe
menu (piecewise/cosine + linear warmup, world-scaled), label smoothing,
mixup, weight decay, file-backed sharded input with per-epoch shuffle
(reader_cv2 pass_id_as_seed), per-epoch top-1/top-5 eval, rank-0
checkpoint per epoch, throughput logging, and benchmark-result JSON
(:642-658) — re-designed tpu-first:

- one process per TPU host; `init_from_env()` joins the launcher's world
  and a dp mesh spans every chip (fleet.init + NCCL's role);
- the jitted train step carries the gradient all-reduce (no allreduce
  calls to place); batches stream through host prefetch + device
  placement (`prefetch_to_device`, the DALI double-buffer role);
- bf16 compute via the model's dtype, fp32 params/optimizer;
- elastic: run under `edl_tpu.collective.launch` and resizes restart the
  process, which re-forms the mesh and resumes from the checkpoint
  (+ optional gs:// mirror for pods on fresh nodes).

Data: a directory of .npz shards (image (N,H,W,3) float32, label (N,)
int) — `--make-synthetic` generates a deterministic learnable stand-in
(no downloads in CI). Real ImageNet = convert your records to such
shards; the loader is format-, not dataset-, specific.

  python -m edl_tpu.examples.imagenet_train --make-synthetic 8 \\
      --data-dir /tmp/imgnet --model ResNetTiny --image-size 32 \\
      --epochs 2 --batch-size 256
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from edl_tpu.data.pipeline import (DataLoader, FileSource,
                                   prefetch_to_device, random_crop,
                                   random_flip_lr)
from edl_tpu.parallel import distributed, mesh as mesh_lib
from edl_tpu.utils import config
from edl_tpu.train import lr as lr_lib
from edl_tpu.train.benchlog import BenchmarkLog
from edl_tpu.train.classification import (create_state,
                                          make_classification_step,
                                          make_eval_step)
from edl_tpu.train.loop import LoopConfig, TrainLoop
from edl_tpu.utils.config import from_env, given
from edl_tpu.utils.logging import get_logger

log = get_logger("edl_tpu.examples.imagenet_train")


def make_synthetic_shards(data_dir: str, n_files: int, rows: int,
                          image_size: int, num_classes: int,
                          seed: int = 0, signal: float = 0.7,
                          label_noise: float = 0.0) -> None:
    """Learnable synthetic image shards + one val shard (deterministic).

    Each class is a fixed random template blended into noise — a
    template-matching task a conv net learns quickly (an argmax-of-linear
    task would be unlearnable through global average pooling).

    `label_noise` flips that fraction of RECORDED labels (train and val)
    to a different class while the image keeps its true template. A
    template task at 224px is separable at any SNR (the signal averages
    over ~150k pixels), so accuracy otherwise saturates at 1.0; label
    noise pins the val ceiling at ~1 - label_noise, giving convergence
    comparisons (e.g. the north-star <1%-over-resizes clause) a
    sub-ceiling operating point where a delta is measurable."""
    os.makedirs(data_dir, exist_ok=True)
    templates = np.random.default_rng(77).normal(
        size=(num_classes, image_size, image_size, 3)).astype(np.float32)
    for i in range(n_files + 1):  # last = validation shard
        rng = np.random.default_rng(seed * 131 + i)
        label = rng.integers(0, num_classes, size=rows).astype(np.int32)
        img = (rng.normal(size=(rows, image_size, image_size, 3))
               .astype(np.float32) + signal * templates[label])
        if label_noise > 0.0:
            flip = rng.random(rows) < label_noise
            shift = rng.integers(1, num_classes, size=rows)
            label = np.where(flip, (label + shift) % num_classes,
                             label).astype(np.int32)
        name = "val.npz" if i == n_files else f"train-{i:04d}.npz"
        # float16 on disk/wire: half the host->device bytes of fp32 (the
        # binding cost of 224px float shards), zero task fidelity loss
        # (unit-variance noise), and the model casts to its own dtype
        np.savez(os.path.join(data_dir, name),
                 image=img.astype(np.float16), label=label)


def build_schedule(args, steps_per_epoch: int, world: int) -> optax.Schedule:
    """The reference's LR menu (train_with_fleet.py:114-225).

    --batch-size is GLOBAL, so the LR is tied to the batch, not the
    world: an elastic resize keeps the same optimization (the linear
    scaling rule, edl_collective_design_doc.md:14-16, applies when the
    TOTAL batch grows with the trainer count — scale --lr yourself if
    you also scale --batch-size). The schedule horizon is
    --schedule-epochs (default --epochs) so a phase that stops early —
    an elastic segment resumed later — still follows the SAME decay
    curve as the full run."""
    base = args.lr
    warmup = args.warmup_epochs * steps_per_epoch
    horizon = args.schedule_epochs or args.epochs
    total = horizon * steps_per_epoch
    if args.lr_strategy == "cosine":
        return lr_lib.cosine_with_warmup(base, total, warmup)
    boundaries = [int(e) * steps_per_epoch for e in args.lr_boundaries]
    values = [base * (args.lr_decay ** i)
              for i in range(len(boundaries) + 1)]
    return lr_lib.piecewise_with_warmup(boundaries, values,
                                        max(warmup, 1))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="edl_tpu.examples.imagenet_train")
    parser.add_argument("--data-dir", required=True)
    parser.add_argument("--data-format", choices=("npz", "jpeg", "packed"),
                        default="npz",
                        help="npz: float shards; jpeg: a train.txt "
                             "'<path> <label>' file list of JPEGs with "
                             "host decode + random-resized-crop/flip "
                             "(the reference's reader_cv2 path) and "
                             "on-device normalization; packed: a "
                             "train.pack pre-decoded fixed-stride record "
                             "file (python -m edl_tpu.data.packed_records "
                             "pack) — the host only gathers raw bytes "
                             "and augmentation runs on device "
                             "(--augment-device default on)")
    parser.add_argument("--decode-threads", type=int,
                        default=max(1, (os.cpu_count() or 1) - 1),
                        help="JPEG decode/augment THREAD pool width "
                             "(ignored when --loader-workers > 0)")
    parser.add_argument("--loader-workers", type=int, default=None,
                        help="input-plane worker PROCESSES with "
                             "shared-memory batch hand-off — scales the "
                             "host loader past the GIL (default: "
                             "$EDL_TPU_LOADER_WORKERS, else 0 = "
                             "inline/threaded)")
    parser.add_argument("--make-synthetic", type=int, default=0,
                        help="generate N train shards (+1 val) first "
                             "(jpeg format: N random JPEGs + train.txt)")
    parser.add_argument("--rows-per-file", type=int, default=1024)
    parser.add_argument("--synthetic-signal", type=float, default=0.7,
                        help="template amplitude of the synthetic data: "
                             "lower = harder task (small-subset students "
                             "stay below the ceiling — the operating "
                             "point the distill-quality clause needs)")
    parser.add_argument("--synthetic-label-noise", type=float, default=0.0,
                        help="fraction of synthetic labels flipped (pins "
                             "the val accuracy ceiling at ~1-x; see "
                             "make_synthetic_shards)")
    parser.add_argument("--model", default="ResNet50_vd",
                        help="zoo factory: ResNet50[_vd], ResNet101, VGG16, "
                             "ResNetTiny, ...")
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--epochs", type=int, default=90,
                        help="train (or resume) up to this epoch")
    parser.add_argument("--schedule-epochs", type=int, default=0,
                        help="cosine-strategy LR horizon (default "
                             "--epochs); set to the job's TOTAL epochs "
                             "when an elastic segment stops early "
                             "(piecewise boundaries are absolute epochs "
                             "already, so it does not apply there)")
    parser.add_argument("--batch-size", type=int, default=256,
                        help="GLOBAL batch size")
    parser.add_argument("--lr", type=float, default=0.1,
                        help="base LR at world=1 (linear-scaled)")
    parser.add_argument("--lr-strategy", choices=("piecewise", "cosine"),
                        default="piecewise")
    parser.add_argument("--lr-boundaries", type=int, nargs="+",
                        default=[30, 60, 80], help="epochs")
    parser.add_argument("--lr-decay", type=float, default=0.1)
    parser.add_argument("--warmup-epochs", type=int, default=5)
    parser.add_argument("--momentum", type=float, default=0.9)
    parser.add_argument("--weight-decay", type=float, default=1e-4)
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
    parser.add_argument("--fused-opt",
                        choices=("off", "fp32", "int8", "fp8"),
                        default=None,
                        help="fused optimizer path (train/fused_opt.py; "
                             "default $EDL_TPU_FUSED_OPT, else off): "
                             "fp32 = momentum-SGD as one kernel pass "
                             "per bucket, bitwise vs the optax chain; "
                             "int8/fp8 also hold the momentum "
                             "quantized with error-feedback residuals "
                             "(opt state and checkpoint bytes halve, "
                             "convergence-parity gated)")
    parser.add_argument("--dgc-sparsity", type=float, default=0.0,
                        help="deep gradient compression: fraction of "
                             "gradient entries dropped (0 = off; the "
                             "reference's use_dgc flag)")
    parser.add_argument("--dgc-rampup-epochs", type=int, default=1)
    parser.add_argument("--label-smoothing", type=float, default=0.1)
    parser.add_argument("--mixup-alpha", type=float, default=0.0)
    parser.add_argument("--bf16", action="store_true",
                        help="bf16 activations (fp32 params/optimizer)")
    parser.add_argument("--no-augment", action="store_true",
                        help="disable flip/crop transforms (synthetic-label "
                             "tasks are not augmentation-invariant)")
    parser.add_argument("--augment-device", type=int, default=None,
                        choices=(0, 1),
                        help="run crop/flip/normalize as a jitted program "
                             "ON DEVICE from the loader's per-step seeds "
                             "(ops/augment.py) instead of host "
                             "transforms — the host only gathers bytes. "
                             "npz/packed formats only (jpeg decode is "
                             "inherently host-side: pack it first). "
                             "Default: $EDL_TPU_AUGMENT_DEVICE, else on "
                             "for --data-format packed, off otherwise")
    parser.add_argument("--rotate", action="store_true",
                        help="jpeg mode: +-10 degree random rotation before "
                             "the crop (reference --rotate, img_tool.py)")
    parser.add_argument("--teachers", default="",
                        help="distill mode: comma-joined teacher_server "
                             "endpoints; the loss becomes temperature-KD "
                             "against served logits (reference "
                             "train_with_fleet.py soft-label path)")
    parser.add_argument("--distill-temperature", type=float, default=2.0)
    parser.add_argument("--distill-hard-weight", type=float, default=0.0,
                        help="0 = pure soft labels (the reference's "
                             "distill recipe); >0 mixes hard-label CE")
    parser.add_argument("--distill-topk", type=int, default=0,
                        help="negotiate the compressed teacher wire and "
                             "train on sparse top-K targets")
    parser.add_argument("--distill-predict-key", default="logits",
                        help="teacher fetch name (teacher_server "
                             "--output-key)")
    parser.add_argument("--ckpt-dir", default="")
    parser.add_argument("--ckpt-steps", type=int, default=None,
                        help="also checkpoint every N optimizer steps "
                             "(cheap under async saves; shrinks the "
                             "elastic replay window; default "
                             "$EDL_TPU_CKPT_STEPS, else epoch-end only)")
    parser.add_argument("--ckpt-sync", action="store_true",
                        help="synchronous saves (escape hatch; default "
                             "is async snapshot-then-write — the step "
                             "loop blocks only for the host snapshot)")
    parser.add_argument("--benchmark-log", default="")
    parser.add_argument("--profile", default="",
                        help="jax profiler trace dir; traces steps "
                             "10-15 on rank 0 (reference --profile, "
                             "train_with_fleet.py:521-530)")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    if args.rotate and (args.data_format != "jpeg" or args.no_augment):
        raise SystemExit("--rotate is a jpeg-mode augmentation (and is "
                         "incompatible with --no-augment)")
    if args.data_format == "jpeg" and args.synthetic_label_noise > 0:
        # validate flag combinations BEFORE any rank-dependent code: a
        # rank-0-only exit would strand the other ranks in the data-gen
        # barrier below
        raise SystemExit(
            "--synthetic-label-noise is only implemented for the npz "
            "synthetic generator (jpeg synthetic data is random-labeled "
            "noise already)")
    if 0 < args.schedule_epochs < args.epochs:
        raise SystemExit(
            f"--schedule-epochs {args.schedule_epochs} < --epochs "
            f"{args.epochs}: epochs past the horizon would train at "
            "LR ~0 (the horizon is the job TOTAL; the stop point is "
            "--epochs)")
    # Device-side augmentation: CLI > env > format default (on for
    # packed — the whole point of packing is a transform-free host).
    # Resolved BEFORE any rank-dependent code so bad combinations exit
    # every rank identically.
    if args.augment_device is not None:
        augment_device = bool(args.augment_device)
    else:
        env_aug = config.env_str("EDL_TPU_AUGMENT_DEVICE")
        augment_device = (env_aug.lower() in ("1", "true", "yes", "on")
                          if env_aug is not None
                          else args.data_format == "packed")
    if args.no_augment:
        augment_device = False
    if augment_device and args.data_format == "jpeg":
        raise SystemExit(
            "--augment-device needs fixed-stride pre-decoded pixels and "
            "jpeg decode is inherently host-side — pack the list first: "
            "python -m edl_tpu.data.packed_records pack --jpeg-list "
            "train.txt --root DATA --out DATA/train.pack, then "
            "--data-format packed")
    if augment_device and args.teachers:
        raise SystemExit(
            "--augment-device is not supported with --teachers (the "
            "distill reader ships the teacher the SAME pixels the "
            "student trains on; device-augmented pixels never exist on "
            "host)")
    distributed.force_platform_from_env()
    env = distributed.init_from_env()
    world = max(1, env.world_size)
    rank = max(0, env.rank)
    if args.make_synthetic and rank == 0:
        if args.data_format == "jpeg":
            from edl_tpu.data.image import make_synthetic_jpeg_dataset
            make_synthetic_jpeg_dataset(
                args.data_dir, args.make_synthetic,
                classes=args.num_classes, seed=args.seed,
                hw=(args.image_size * 3 // 2, args.image_size * 2))
        else:
            make_synthetic_shards(args.data_dir, args.make_synthetic,
                                  args.rows_per_file, args.image_size,
                                  args.num_classes, args.seed,
                                  signal=args.synthetic_signal,
                                  label_noise=args.synthetic_label_noise)
            if args.data_format == "packed":
                # pack the freshly-written float shards (dtypes
                # preserved); val stays val.npz — eval reads it directly
                from edl_tpu.data.packed_records import pack_npz
                shards = sorted(
                    os.path.join(args.data_dir, f)
                    for f in os.listdir(args.data_dir)
                    if f.startswith("train-") and f.endswith(".npz"))
                pack_npz(shards,
                         os.path.join(args.data_dir, "train.pack"))
    if args.make_synthetic and jax.process_count() > 1:
        # non-writers must not listdir a half-written data dir
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("edl_imagenet_data_gen")

    val_path = os.path.join(args.data_dir, "val.npz")
    if args.batch_size % world:
        raise SystemExit(f"global batch {args.batch_size} not divisible by "
                         f"world {world}")
    local_bs = args.batch_size // world

    # every option below: the flag where given, else its environment
    # name (bound on the dataclass of the module that consumes it)
    loop_cfg = from_env(LoopConfig, num_epochs=args.epochs,
                        ckpt_dir=args.ckpt_dir or env.checkpoint_path
                        or None,
                        profile_dir=args.profile or None,
                        **given(ckpt_every_steps=args.ckpt_steps,
                                ckpt_async=False if args.ckpt_sync
                                else None))

    # hybrid ICI x DCN when the job is (or declares itself) multi-slice:
    # dp's major dimension crosses DCN, flat dp otherwise
    mesh = distributed.make_mesh_from_env(mesh_lib.MeshSpec({"dp": -1}),
                                          env)
    # the manual dp gradient path, where a flag or the environment asks
    from edl_tpu.train.comm import CommConfig
    comm_cfg = CommConfig.from_flags(
        bucket_mb=args.comm_bucket_mb, compress=args.dcn_compress)
    if comm_cfg is not None and args.teachers:
        raise SystemExit(
            "--dcn-compress/--comm-bucket-mb are not supported "
            "with --teachers (the distill steps carry their own "
            "jit; the dp gradient wire is the student-only path)")
    from edl_tpu.train.fused_opt import fused_mode, make_fused_tx
    fused_opt = fused_mode(args.fused_opt)
    if fused_opt != "off" and args.dgc_sparsity > 0:
        raise SystemExit(
            "--fused-opt and --dgc-sparsity are mutually exclusive: "
            "DGC's momentum correction REPLACES optimizer momentum "
            "inside an optax chain, while the fused path owns the "
            "whole momentum update in-kernel. Pick one compression "
            "story (DGC sparsifies the wire, fused-int8 shrinks "
            "resident state).")
    data_sharding = mesh_lib.data_sharding(mesh)
    normalize = None
    if args.data_format == "jpeg":
        from edl_tpu.data.image import (JpegFileListSource,
                                        eval_image_transform,
                                        train_image_transform)
        list_file = os.path.join(args.data_dir, "train.txt")
        if not os.path.exists(list_file):
            raise SystemExit(f"no train.txt under {args.data_dir}")
        source = JpegFileListSource(list_file, root=args.data_dir)
        # --no-augment keeps the deterministic eval-style decode (for
        # synthetic-label tasks that are not augmentation-invariant)
        sample_t = (eval_image_transform(
                        args.image_size, short=args.image_size * 8 // 7)
                    if args.no_augment
                    else train_image_transform(args.image_size,
                                               rotate=args.rotate))
        loader = DataLoader(source, local_bs, rank=rank, world=world,
                            seed=args.seed, sample_transforms=(sample_t,),
                            decode_threads=args.decode_threads,
                            num_workers=args.loader_workers)
        normalize = "imagenet"  # uint8 off the wire; normalize on chip
        n_files = len(source)
    else:
        if args.data_format == "packed":
            from edl_tpu.data.packed_records import PackedSource
            pack_path = os.path.join(args.data_dir, "train.pack")
            if not os.path.exists(pack_path):
                raise SystemExit(
                    f"no train.pack under {args.data_dir} (pack one: "
                    "python -m edl_tpu.data.packed_records pack)")
            source = PackedSource(pack_path)
            # pre-decoded uint8 (the jpeg-packed path) normalizes like
            # the jpeg plane; float shards were normalized at pack time
            if source.fields["image"][1] == np.uint8:
                normalize = "imagenet"
            n_files = 1
        else:
            files = sorted(os.path.join(args.data_dir, f)
                           for f in os.listdir(args.data_dir)
                           if f.startswith("train-") and f.endswith(".npz"))
            if not files:
                raise SystemExit(
                    f"no train-*.npz shards under {args.data_dir}")
            source = FileSource(files)
            n_files = len(files)
        # device augmentation replaces the host batch transforms: the
        # loader ships raw bytes + the per-step seed, and the SAME
        # crop/flip (+ normalize) runs jitted after placement
        transforms = () if (args.no_augment or augment_device) \
            else (random_flip_lr, random_crop)
        loader = DataLoader(source, local_bs, rank=rank, world=world,
                            seed=args.seed, transforms=transforms,
                            num_workers=args.loader_workers,
                            emit_batch_seed=augment_device)
    steps_per_epoch = loader.steps_per_epoch()
    log.info("world=%d rank=%d devices=%d format=%s shards=%d samples=%d "
             "steps/epoch=%d", world, rank, jax.device_count(),
             args.data_format, n_files, len(source), steps_per_epoch)

    from edl_tpu import models as zoo
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    model = zoo.get_model(args.model)(num_classes=args.num_classes,
                                      dtype=dtype)
    schedule = build_schedule(args, steps_per_epoch, world)
    if args.dgc_sparsity > 0:
        from edl_tpu.train.dgc import dgc
        # DGC's momentum correction REPLACES optimizer momentum, and
        # weight decay stays dense (applied after the compressor) so
        # regularization strength is uniform, not send-frequency-tied.
        tx = optax.chain(
            dgc(sparsity=args.dgc_sparsity, momentum=args.momentum,
                rampup_steps=args.dgc_rampup_epochs * steps_per_epoch),
            optax.add_decayed_weights(args.weight_decay),
            optax.sgd(schedule))
    elif fused_opt != "off":
        # same math as the optax chain below (fp32 mode is bitwise):
        # decayed weights fold into the momentum update in-kernel
        tx = make_fused_tx("sgdm", schedule, fused_opt,
                           momentum=args.momentum,
                           weight_decay=args.weight_decay)
        log.info("fused optimizer path: sgd-m %s", fused_opt)
    else:
        tx = optax.chain(
            optax.add_decayed_weights(args.weight_decay),
            optax.sgd(schedule, momentum=args.momentum, nesterov=False))
    state = create_state(model, jax.random.PRNGKey(args.seed),
                         (1, args.image_size, args.image_size, 3), tx)
    distill_reader = None
    if args.teachers:
        from edl_tpu.distill.reader import DistillReader
        from edl_tpu.train.classification import (make_distill_step,
                                                  make_sparse_distill_step)
        if args.mixup_alpha > 0:
            raise SystemExit("--mixup-alpha is not supported with "
                             "--teachers (mixed pixels would be sent to "
                             "a teacher that expects clean inputs)")
        if normalize is not None:
            # The student normalizes ON DEVICE; the teacher receives the
            # RAW wire feeds and must apply the SAME preprocessing.
            log.warning(
                "distill on the JPEG plane ships raw uint8 feeds: start "
                "the teacher with --input-normalize %s (a mismatched "
                "teacher emits out-of-distribution logits)", normalize)
        kd_kw = dict(temperature=args.distill_temperature,
                     hard_weight=args.distill_hard_weight,
                     smoothing=args.label_smoothing,
                     predict_key=args.distill_predict_key,
                     normalize=normalize)
        step = (make_sparse_distill_step(args.num_classes, **kd_kw)
                if args.distill_topk
                else make_distill_step(args.num_classes, **kd_kw))
        # ONE reader reused across epochs: data_fn retargets its source
        # at the current epoch (seed-per-pass order preserved)
        distill_epoch = [0]
        distill_reader = DistillReader(
            lambda: loader.epoch(distill_epoch[0]), feeds=("image",),
            predicts=(args.distill_predict_key,),
            teachers=[t for t in args.teachers.split(",") if t],
            compress_topk=args.distill_topk,
            sparse_predicts=bool(args.distill_topk))
    else:
        step = make_classification_step(
            args.num_classes, smoothing=args.label_smoothing,
            mixup_alpha=args.mixup_alpha, seed=args.seed,
            # with device augmentation the augment op normalizes (one
            # fused uint8->float pass after crop/flip); the step must
            # not normalize twice
            normalize=None if augment_device else normalize,
            comm=comm_cfg, mesh=mesh,
            topology=distributed.slice_topology(env))
        if comm_cfg is not None:
            log.info("dcn-aware gradient path: bucket=%.1fMiB "
                     "compress=%s", comm_cfg.target_mb,
                     comm_cfg.compress)
    eval_step = make_eval_step(normalize=normalize)
    augment = None
    if augment_device:
        from edl_tpu.ops.augment import make_device_augment
        augment = make_device_augment(pad=4, base_seed=args.seed,
                                      normalize=normalize)
        log.info("device-side augmentation: crop(pad=4)+flip+normalize "
                 "jitted on device from loader-emitted per-step seeds")

    # eval_batches: None, or a zero-arg callable yielding {'image',
    # 'label'} host batches of local_bs (streamed — a 50k-image val set
    # must not be decoded serially into one giant resident array)
    eval_batches = None
    val_pack = os.path.join(args.data_dir, "val.pack")
    if args.data_format == "packed" and os.path.exists(val_pack):
        from edl_tpu.data.packed_records import PackedSource
        vsrc = PackedSource(val_pack)
        if len(vsrc) >= local_bs:
            def _packed_eval_batches():
                for lo in range(0, len(vsrc) - local_bs + 1, local_bs):
                    yield vsrc.batch(np.arange(lo, lo + local_bs))

            eval_batches = _packed_eval_batches
        else:
            log.warning("val.pack has %d < batch %d rows — eval off",
                        len(vsrc), local_bs)
    elif args.data_format == "jpeg":
        val_list = os.path.join(args.data_dir, "val.txt")
        if os.path.exists(val_list):
            vsrc = JpegFileListSource(val_list, root=args.data_dir)
            if len(vsrc) >= local_bs:
                vloader = DataLoader(
                    vsrc, local_bs, shuffle=False,
                    sample_transforms=(eval_image_transform(
                        args.image_size,
                        short=args.image_size * 8 // 7),),
                    decode_threads=args.decode_threads)
                eval_batches = lambda: vloader.epoch(0)  # noqa: E731
            else:
                log.warning("val.txt has %d < batch %d images — eval off",
                            len(vsrc), local_bs)
    elif os.path.exists(val_path):
        with np.load(val_path) as z:
            eval_data = {"image": z["image"], "label": z["label"]}

        def _npz_eval_batches():
            for lo in range(0, len(eval_data["label"]) - local_bs + 1,
                            local_bs):
                yield {k: v[lo:lo + local_bs]
                       for k, v in eval_data.items()}

        eval_batches = _npz_eval_batches

    blog = BenchmarkLog(args.model, batch_size=args.batch_size,
                        world_size=world)
    epoch_t0 = [time.perf_counter()]

    def eval_fn(state, epoch):
        elapsed = time.perf_counter() - epoch_t0[0]
        # per-trainer rate (this rank consumed local_bs per step);
        # benchlog multiplies its max by world_size for the global figure
        rate = steps_per_epoch * local_bs / max(elapsed, 1e-9)
        results = {"examples_per_sec": rate}
        if eval_batches is not None:
            accs, n = {"acc1": 0.0, "acc5": 0.0}, 0
            for hb in eval_batches():
                ev = eval_step(state, {"image": jnp.asarray(hb["image"]),
                                       "label": jnp.asarray(hb["label"])})
                for k in accs:
                    accs[k] += float(ev[k])
                n += 1
            results.update({k: v / max(n, 1) for k, v in accs.items()})
        blog.epoch(epoch, **results)
        epoch_t0[0] = time.perf_counter()
        return results

    # Single-process: the augment applies inside prefetch_to_device's
    # staging thread (dispatched under the running step). Multi-process:
    # batches reach TrainLoop._place as host arrays (form_global_batch),
    # so the loop pops the seed and augments after forming the global
    # batch — exactly one of the two paths owns the seed.
    loop = TrainLoop(
        step, state, mesh=mesh, config=loop_cfg, eval_fn=eval_fn,
        place_state=lambda t: mesh_lib.replicate_host_tree(mesh, t),
        augment_fn=augment if jax.process_count() > 1 else None)

    def data_fn(epoch):
        if distill_reader is not None:
            distill_epoch[0] = epoch
            it = distill_reader()
        else:
            it = loader.epoch(epoch)
        return prefetch_to_device(it, data_sharding, augment=augment) \
            if jax.process_count() == 1 else it

    # TrainLoop closes the data plane it drives (decode pool / mp
    # workers + shm ring) when the run ends, crash paths included
    data_fn.close = loader.close

    try:
        status = loop.run(data_fn)
    finally:
        # close on the deadman/error path too (discovery client thread)
        if distill_reader is not None:
            distill_reader.close()
    blog.extra(**loop.ckpt_stats())  # save-stall / restore accounting
    if comm_cfg is not None:
        blog.extra(**step.stats())  # bucket plan + DCN wire accounting
    if rank == 0 and args.benchmark_log:
        blog.write(args.benchmark_log, rank)
    final = blog.finalize().get("final", {})
    log.info("done: epoch=%d step=%d %s", status.epoch, status.step,
             {k: round(v, 4) for k, v in final.items()})
    if final:
        print(f"final_acc1={final.get('acc1', float('nan')):.4f}")
    distributed.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
