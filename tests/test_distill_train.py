"""Student training through the distill pipeline: the KD loss consumer
(make_distill_step) fed by DistillReader against a real teacher server.

Done-criterion from the round-1 verdict: "a student training run consuming
it via make_distill_step"."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from edl_tpu.data.pipeline import ArraySource, DataLoader
from edl_tpu.distill.reader import DistillReader
from edl_tpu.distill.teacher_server import TeacherServer
from edl_tpu.models.mlp import MLP
from edl_tpu.train.classification import create_state, make_distill_step


def test_student_learns_from_served_teacher():
    # Teacher: fixed-weight MLP; data labeled BY the teacher so the KD
    # objective is learnable.
    teacher = MLP(num_classes=10, hidden=(32,))
    tvars = jax.jit(teacher.init)(jax.random.PRNGKey(42),
                                  jnp.zeros((1, 16)))

    @jax.jit
    def tforward(x):
        return teacher.apply(tvars, x, train=False)

    def predict(feeds):
        return {"teacher_logits":
                np.asarray(tforward(jnp.asarray(feeds["image"])), np.float32)}

    rng = np.random.default_rng(0)
    images = rng.normal(size=(512, 16)).astype(np.float32)
    labels = np.asarray(tforward(jnp.asarray(images))).argmax(1).astype(np.int32)
    loader = DataLoader(ArraySource({"image": images, "label": labels}), 64,
                        seed=0)

    student = MLP(num_classes=10, hidden=(32,))
    state = create_state(student, jax.random.PRNGKey(0), (1, 16),
                         optax.adam(1e-2))
    step = make_distill_step(10, temperature=2.0, hard_weight=0.0)

    with TeacherServer(predict, host="127.0.0.1") as srv:
        accs = []
        for epoch in range(16):
            dr = DistillReader(lambda e=epoch: loader.epoch(e),
                               feeds=["image"], predicts=["teacher_logits"],
                               teachers=[f"127.0.0.1:{srv.port}"],
                               teacher_batch_size=16)
            for batch in dr():
                state, metrics = step(state, batch)
                accs.append(float(metrics["acc1"]))
    # The KD loss has a constant floor (soft-CE includes teacher entropy),
    # so progress is measured as student->teacher agreement: labels here
    # ARE the teacher's argmax.
    first, last = np.mean(accs[:8]), np.mean(accs[-8:])
    assert last > max(0.5, first + 0.2), \
        f"no learning: agreement {first:.3f} -> {last:.3f}"


def test_soft_labels_beat_hard_labels_on_same_budget():
    """The distill QUALITY claim at unit scale (the reference's acc1
    77.1->79.0 story, /root/reference/README.md:70-72): a student given
    the teacher's soft labels must beat the SAME student trained on hard
    labels with an IDENTICAL budget — same subset, same epochs/LR/batch,
    same init seed; only the loss target differs. The teacher knows the
    full training set; the students see a 1/16 subset."""
    from edl_tpu.train.classification import (make_classification_step,
                                              make_eval_step)

    K, D, SIG = 6, 64, 0.22
    templates = np.random.default_rng(3).normal(size=(K, D)) \
        .astype(np.float32)

    def make(n, seed):
        r = np.random.default_rng(seed)
        y = r.integers(0, K, size=n).astype(np.int32)
        x = (r.normal(size=(n, D)).astype(np.float32)
             + SIG * templates[y])
        return x.reshape(n, 8, 8, 1), y

    x_full, y_full = make(3072, 10)
    x_sub, y_sub = x_full[:192], y_full[:192]
    x_val, y_val = make(512, 99)

    def train(hidden, x, y, apply_step, epochs, seed):
        model = MLP(num_classes=K, hidden=hidden)
        st = create_state(model, jax.random.PRNGKey(seed), (1, 8, 8, 1),
                          optax.adam(1e-2))
        r = np.random.default_rng(0)
        for _ in range(epochs):
            perm = r.permutation(len(y))
            for lo in range(0, len(y) - 64 + 1, 64):
                sel = perm[lo:lo + 64]
                st = apply_step(st, {"image": x[sel], "label": y[sel]})
        return st, model

    ev = make_eval_step()

    def acc(st):
        return float(ev(st, {"image": jnp.asarray(x_val),
                             "label": jnp.asarray(y_val)})["acc1"])

    cstep = make_classification_step(K, donate=False)
    teacher_state, teacher = train((128,), x_full, y_full,
                                   lambda s, b: cstep(s, b)[0],
                                   epochs=20, seed=0)
    teacher_fwd = jax.jit(lambda x: teacher.apply(
        {"params": teacher_state.params}, x, train=False))

    alone_state, _ = train((64,), x_sub, y_sub,
                           lambda s, b: cstep(s, b)[0], epochs=60, seed=1)

    dstep = make_distill_step(K, temperature=2.0, hard_weight=0.0,
                              donate=False)

    def distill_apply(st, batch):
        batch = dict(batch)
        batch["teacher_logits"] = np.asarray(
            teacher_fwd(jnp.asarray(batch["image"])))
        return dstep(st, batch)[0]

    distilled_state, _ = train((64,), x_sub, y_sub, distill_apply,
                               epochs=60, seed=1)

    teacher_acc, alone, distilled = acc(teacher_state), \
        acc(alone_state), acc(distilled_state)
    assert teacher_acc > alone, (teacher_acc, alone)  # worth distilling
    assert distilled > alone + 0.03, \
        f"soft labels did not beat hard: {distilled:.3f} vs {alone:.3f}"
