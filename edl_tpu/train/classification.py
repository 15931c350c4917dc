"""Classification losses + step builders (label smoothing, mixup, distill).

Feature parity with the reference trainer's loss menu
(`example/collective/resnet50/train_with_fleet.py:227-276`: mixup with
Beta(alpha, alpha), label smoothing epsilon, softmax-CE; distill variant adds
a soft-label CE against teacher scores,
`example/distill/resnet/train_with_fleet.py:254-259`; NLP distill uses
temperature-T KL, `example/distill/nlp/distill.py`).

JAX-first: mixup randomness is derived inside the jitted step from
`fold_in(seed, state.step)` so a resumed elastic run replays the identical
augmentation stream — no host RNG state to checkpoint.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

# The on-device pixel ops live with the rest of the device-side
# augmentation plane (ops/augment.py, the packed-records feed path);
# re-exported here because every step builder and its callers import
# them from this module.
from edl_tpu.ops.augment import (IMAGENET_MEAN, IMAGENET_STD,  # noqa: F401
                                 mixup, normalize_image)
from edl_tpu.train.state import TrainState
from edl_tpu.train.step import make_train_step


def smoothed_labels(labels: jax.Array, num_classes: int,
                    smoothing: float = 0.0) -> jax.Array:
    """Integer labels -> (optionally smoothed) one-hot targets, fp32."""
    one_hot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    if smoothing > 0.0:
        one_hot = one_hot * (1.0 - smoothing) + smoothing / num_classes
    return one_hot


def soft_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean CE between logits and a target distribution."""
    return -jnp.mean(jnp.sum(targets * jax.nn.log_softmax(logits), axis=-1))


def distill_kl(student_logits: jax.Array, teacher_logits: jax.Array,
               temperature: float = 1.0) -> jax.Array:
    """Temperature-scaled KL(teacher || student), scaled by T^2 (Hinton)."""
    t = temperature
    teacher = jax.nn.softmax(teacher_logits / t)
    return soft_cross_entropy(student_logits / t, teacher) * t * t


def accuracy_topk(logits: jax.Array, labels: jax.Array, k: int = 1
                  ) -> jax.Array:
    topk = jax.lax.top_k(logits, k)[1]
    hit = jnp.any(topk == labels[:, None], axis=-1)
    return jnp.mean(hit.astype(jnp.float32))


def create_state(model, rng: jax.Array, input_shape: tuple,
                 tx: optax.GradientTransformation,
                 input_dtype=jnp.float32) -> TrainState:
    """Init a TrainState for a flax classification model (BN-aware).

    Init runs under jit: eager init dispatches (and compiles) each
    layer op separately.
    """
    variables = jax.jit(lambda r: model.init(
        r, jnp.zeros(input_shape, input_dtype), train=False))(rng)
    params = variables["params"]
    batch_stats = variables.get("batch_stats")
    return TrainState.create(apply_fn=model.apply, params=params, tx=tx,
                             batch_stats=batch_stats)


def make_classification_step(num_classes: int, *, smoothing: float = 0.0,
                             mixup_alpha: float = 0.0, seed: int = 0,
                             weight_decay_in_loss: float = 0.0,
                             normalize: str | None = None,
                             donate: bool = True, comm=None, mesh=None,
                             topology=None) -> Callable:
    """Jitted (state, batch)->(state, metrics) for {'image','label'} batches.

    Handles flax BN mutable batch_stats; mixup/smoothing optional. L2 can be
    added here (reference uses optimizer regularizer; prefer optax wd).
    `normalize` runs on-device pixel normalization (see `normalize_image`)
    so uint8 batches off the JPEG plane train directly.
    `comm` (a train/comm.CommConfig, with the `mesh` the step trains on
    and optionally the slice `topology`) routes the gradient reduction
    through the manual DCN-aware bucketed path: `make_comm_train_step`.
    """

    def loss_fn(state: TrainState, params: Any, batch: dict):
        targets = smoothed_labels(batch["label"], num_classes, smoothing)
        images = normalize_image(batch["image"], normalize)
        if mixup_alpha > 0.0:
            key = jax.random.fold_in(jax.random.PRNGKey(seed), state.step)
            images, targets = mixup(key, images, targets, mixup_alpha)
        variables = {"params": params}
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
            logits, mutated = state.apply_fn(
                variables, images, train=True, mutable=["batch_stats"])
            new_stats = mutated["batch_stats"]
        else:
            logits = state.apply_fn(variables, images, train=True)
            new_stats = None
        loss = soft_cross_entropy(logits, targets)
        if weight_decay_in_loss > 0.0:
            l2 = sum(jnp.sum(jnp.square(p))
                     for p in jax.tree.leaves(params))
            loss = loss + 0.5 * weight_decay_in_loss * l2
        aux = {"acc1": accuracy_topk(logits, batch["label"], 1)}
        if new_stats is not None:
            aux["batch_stats"] = new_stats
        return loss, aux

    if comm is not None:
        from edl_tpu.train.comm import make_comm_train_step
        return make_comm_train_step(loss_fn, mesh=mesh, config=comm,
                                    topology=topology, donate=donate)
    return make_train_step(loss_fn, donate=donate)


def _make_kd_step(kd_loss: Callable, num_classes: int, *,
                  hard_weight: float, smoothing: float, donate: bool,
                  input_key: str, normalize: str | None = None) -> Callable:
    """Shared KD step plumbing: `kd_loss(logits, batch) -> loss` is the
    only thing that differs between the dense and sparse variants."""

    def loss_fn(state: TrainState, params: Any, batch: dict):
        images = normalize_image(batch[input_key], normalize)
        variables = {"params": params}
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
            logits, mutated = state.apply_fn(
                variables, images, train=True,
                mutable=["batch_stats"])
            new_stats = mutated["batch_stats"]
        else:
            logits = state.apply_fn(variables, images, train=True)
            new_stats = None
        loss = kd_loss(logits, batch)
        if hard_weight > 0.0:
            targets = smoothed_labels(batch["label"], num_classes, smoothing)
            loss = ((1.0 - hard_weight) * loss
                    + hard_weight * soft_cross_entropy(logits, targets))
        aux = {"acc1": accuracy_topk(logits, batch["label"], 1)}
        if new_stats is not None:
            aux["batch_stats"] = new_stats
        return loss, aux

    return make_train_step(loss_fn, donate=donate)


def make_distill_step(num_classes: int, *, temperature: float = 1.0,
                      hard_weight: float = 0.0, smoothing: float = 0.0,
                      donate: bool = True, input_key: str = "image",
                      predict_key: str = "teacher_logits",
                      normalize: str | None = None) -> Callable:
    """Step for {input_key,'label',predict_key} batches: KD loss
    (+ optional hard-label CE mix). The student-side consumer of the
    DistillReader pipeline (reference distill/resnet train_with_fleet.py
    soft-label path)."""

    def kd_loss(logits, batch):
        return distill_kl(logits, batch[predict_key], temperature)

    return _make_kd_step(kd_loss, num_classes, hard_weight=hard_weight,
                         smoothing=smoothing, donate=donate,
                         input_key=input_key, normalize=normalize)


def sparse_distill_kl(student_logits: jax.Array, teacher_idx: jax.Array,
                      teacher_val: jax.Array,
                      temperature: float = 1.0) -> jax.Array:
    """`distill_kl` against a TOP-K teacher: (B, K) indices + values from
    the compressed teacher wire (distill/teacher_server.py
    `compress_outputs`). Teacher probs renormalize over the k classes
    (exactly what scatter-expanding with a -inf fill yields), and the
    student's log-probs are gathered at the teacher's indices — the full
    (B, C) dense teacher tensor never exists on device."""
    t = temperature
    teacher = jax.nn.softmax(teacher_val.astype(jnp.float32) / t, axis=-1)
    logp = jax.nn.log_softmax(student_logits.astype(jnp.float32) / t,
                              axis=-1)
    logp_k = jnp.take_along_axis(logp, teacher_idx.astype(jnp.int32),
                                 axis=-1)
    return -jnp.mean(jnp.sum(teacher * logp_k, axis=-1)) * t * t


def make_sparse_distill_step(num_classes: int, *, temperature: float = 1.0,
                             hard_weight: float = 0.0,
                             smoothing: float = 0.0, donate: bool = True,
                             input_key: str = "image",
                             predict_key: str = "teacher_logits",
                             normalize: str | None = None) -> Callable:
    """`make_distill_step` for sparse teacher targets: batches carry
    ``{predict_key}.idx`` / ``{predict_key}.val`` (DistillReader with
    ``compress_topk=K, sparse_predicts=True``) instead of dense logits.
    """

    def kd_loss(logits, batch):
        return sparse_distill_kl(logits, batch[predict_key + ".idx"],
                                 batch[predict_key + ".val"], temperature)

    return _make_kd_step(kd_loss, num_classes, hard_weight=hard_weight,
                         smoothing=smoothing, donate=donate,
                         input_key=input_key, normalize=normalize)


def make_eval_step(input_key: str = "image",
                   normalize: str | None = None) -> Callable:
    """Jitted eval: (state, batch) -> {'acc1','acc5'} (train=False)."""

    @jax.jit
    def eval_step(state: TrainState, batch: dict) -> dict:
        variables = {"params": state.params}
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
        logits = state.apply_fn(
            variables, normalize_image(batch[input_key], normalize),
            train=False)
        return {"acc1": accuracy_topk(logits, batch["label"], 1),
                "acc5": accuracy_topk(logits, batch["label"],
                                      min(5, logits.shape[-1]))}

    return eval_step
