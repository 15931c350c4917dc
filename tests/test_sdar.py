"""Training by diffusion over blocks (`models.transformer.sdar_config`,
`models/blockdiff.py`, `data/block_noise.py`, the `blocks` geometry of
`ops/flash_attention.py`, the weighted sweep of `ops/fused_xent.py`)
against the plain reference `benchmark/reference/sdar_plain.py`, at a
small size on the CPU with seeded random weights: the mask's three
terms, the kernels under it (interpret mode) and the blockwise paths,
the join where a query has no clean key, the whole model's loss and
every gradient leaf, the weights of the streamed CE, the positions, the
shares of the experts adding up, the loader's noise across a restart
and a resize, and the trainer end to end through a checkpoint.

Tolerances. Program and reference both compute in float32 here and
differ in the order of their sums: losses agree to 1e-6 and the
gradient's relative error reads 5e-7 to 8e-7; `TOL` = 1e-5.
"""

import dataclasses
import importlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.core import meta

from benchmark.reference import sdar_plain as plain
from edl_tpu.data import block_noise
from edl_tpu.data.pipeline import ArraySource, DataLoader
from edl_tpu.models import blockdiff
from edl_tpu.models import transformer as tfm
from edl_tpu.ops.fused_xent import streamed_lm_xent
from edl_tpu.train.state import TrainState

fa = importlib.import_module("edl_tpu.ops.flash_attention")

TOL = 1e-5
VOCAB, D, HEADS, KV, HEAD, EFF, E, K, HELD, FIRST = 97, 32, 4, 2, 16, 24, 8, 2, 4, 2


def small(seq, block, **changed):
    return dataclasses.replace(tfm.sdar_config(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_kv_heads=KV,
        head_size=HEAD, n_layers=2, d_ff=EFF, max_len=seq, n_experts=E,
        moe_top_k=K, experts_held=HELD, experts_offset=FIRST,
        block_length=block, dtype=jnp.float32, remat=True), **changed)


def hp_of(block):
    return {"n_head": HEADS, "n_kv_head": KV, "eps": 1e-6, "theta": 1e6,
            "top_k": K, "first_expert": FIRST, "block_length": block,
            "mask_id": VOCAB - 1}


def drawn(seq, block, rows=2):
    rng = np.random.default_rng(seq + block)
    noise = [block_noise.row_noise(0, 0, r, seq, block) for r in range(rows)]
    return {"tokens": jnp.asarray(rng.integers(0, VOCAB - 1, (rows, seq)),
                                  jnp.int32),
            "masked": jnp.asarray(np.stack([m for m, _ in noise])),
            "t": jnp.asarray(np.stack([t for _, t in noise]))}


def published(length, block):
    """The mask as the objective words it, place by place."""
    seen = np.zeros((2 * length, 2 * length), bool)
    for i in range(2 * length):
        for j in range(2 * length):
            bi, bj = (i % length) // block, (j % length) // block
            if i < length:      # a noised query
                seen[i, j] = (bj == bi) if j < length else (bj < bi)
            else:               # a clean query
                seen[i, j] = j >= length and bj <= bi
    return seen


# -- the mask ------------------------------------------------------------------

@pytest.mark.parametrize("length, block", [(8, 1), (8, 2), (12, 4), (8, 8)])
def test_the_three_terms_are_the_published_predicate(length, block):
    want = published(length, block)
    np.testing.assert_array_equal(plain.visible(length, block), want)
    # the program's three pieces, each by the mask its path computes
    i = np.arange(length)
    own = i[:, None] // block == i[None, :] // block
    past = np.asarray(fa._seen(i[:, None], i[None, :], None, (block, True)))
    clean = np.asarray(fa._seen(i[:, None], i[None, :], None, (block, False)))
    mine = np.block([[own, past], [np.zeros_like(own), clean]])
    np.testing.assert_array_equal(mine, want)
    # and the kernels' mask of a piece, either way round
    for strict, piece in ((True, past), (False, clean)):
        got = fa._by_block((length, length), 0, 0, (block, strict))
        np.testing.assert_array_equal(got, piece)
        got = fa._by_block((length, length), 0, 0, (block, strict),
                           q_minor=True)
        np.testing.assert_array_equal(got, piece.T)
    half = length // 2  # a piece that starts inside the sequence
    np.testing.assert_array_equal(
        fa._by_block((half, half), half, 0, (block, True)),
        past[half:, :half])


def dense(q, k, v, seen):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


# (sequence, block of the objective, block of the kernels): one kernel
# block; a sequence that is no multiple of the kernels' wanted block
# (five of 128, masked whole); the diagonal pair as 2 x 2 sub-blocks; a
# block of the objective wider than the kernels' (whole pairs visible)
GEOMETRY = [(96, 1, 512), (96, 4, 512), (96, 32, 512), (96, 96, 512),
            (640, 4, 512), (512, 4, 256), (512, 256, 128), (384, 3, 128)]


@pytest.mark.parametrize("strict", [False, True], ids=["own", "before"])
@pytest.mark.parametrize("seq, block, want", GEOMETRY, ids=str)
@pytest.mark.parametrize("path", ["interpret", "blockwise"])
def test_attention_by_block_index_matches_the_dense_mask(path, seq, block,
                                                         want, strict):
    """Forward, `lse` and the three gradients, the kernels and the XLA
    scans alike. A strict query of the first block sees nothing: its
    rows are left out of the comparison here (the join has a test of
    its own)."""
    keys = jax.random.split(jax.random.PRNGKey(seq + block), 4)
    q, k, v, w = (jax.random.normal(kk, (1, seq, 2, 32)) for kk in keys)
    i = np.arange(seq)
    seen = np.asarray(fa._seen(i[:, None], i[None, :], None,
                               (block, strict)))
    rows = jnp.asarray(seen.any(-1))[None, :, None, None]

    def loss(fn):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            jnp.where(rows, fn(q, k, v) * w, 0.0)), argnums=(0, 1, 2))

    def mine(q, k, v):
        return fa.flash_attention(q, k, v, blocks=(block, strict),
                                  block_q=want, block_k=want)
    if path == "interpret":
        with fa.force_interpret_kernels():
            got = loss(mine)(q, k, v)
    else:
        got = loss(mine)(q, k, v)
    ref = loss(lambda q, k, v: dense(q, k, v, jnp.asarray(
        seen | ~seen.any(-1, keepdims=True))))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)


def test_a_block_of_one_is_plain_causal_attention():
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (1, 256, 2, 32)) for kk in keys)
    with fa.force_interpret_kernels():
        np.testing.assert_allclose(
            fa.flash_attention(q, k, v, blocks=(1, False), block_q=128,
                               block_k=128),
            fa.flash_attention(q, k, v, block_q=128, block_k=128),
            atol=1e-6)


@pytest.mark.parametrize("args, want", [
    ((8192, 512, 512, True, None, (4, False)),
     "blocks 512x512, keys of blocks up to the own of 4, pairs a head: 120 "
     "full, 16 on the staircase, 120 skipped; a staircase pair as 2x2 of "
     "256: 1 full, 2 masked, 1 skipped"),
    ((8192, 512, 512, True, None, (4, True)),
     "blocks 512x512, keys of earlier blocks of 4, pairs a head: 120 full, "
     "16 on the staircase, 120 skipped; a staircase pair as 2x2 of 256: 1 "
     "full, 2 masked, 1 skipped"),
    ((640, 128, 128, True, None, (4, True)),
     "blocks 128x128, keys of earlier blocks of 4, pairs a head: 10 full, 5 "
     "on the staircase, 10 skipped, masked whole"),
    ((512, 128, 128, True, None, (256, False)),
     "blocks 128x128, keys of blocks up to the own of 256, pairs a head: 12 "
     "full, 0 on the staircase, 4 skipped, masked whole"),
])
def test_block_pairs_line_counts_the_staircase(args, want):
    """136 pairs a call at 8,192 and blocks of 512, 272 a layer a
    direction, against 528 for causal attention over both copies."""
    assert fa.block_pairs(*args) == want


def test_blocks_are_refused_where_they_mean_nothing():
    x = jnp.zeros((1, 96, 1, 16))
    for kw in ({"causal": False}, {"window": 8}):
        with pytest.raises(ValueError, match="visibility by block index"):
            fa.flash_attention(x, x, x, blocks=(4, False), **kw)
    with pytest.raises(ValueError, match="divides the sequence"):
        fa.flash_attention(x, x, x, blocks=(5, False))


# -- the join ------------------------------------------------------------------

@pytest.mark.parametrize("path", ["interpret", "blockwise"])
def test_the_join_is_exact_where_a_query_has_no_clean_key(path):
    """The first block's noised queries: what comes out is their own
    block's attention, bit for bit, and nothing flows back into the
    clean copy from them."""
    seq, block = 96, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (1, 2 * seq, HEADS, HEAD))
    k, v = (jax.random.normal(kk, (1, 2 * seq, KV, HEAD)) for kk in keys[1:])

    def first_block(q, k, v):
        return blockdiff.attention(q, k, v, block=block)[:, :block]

    def run():
        out = first_block(q, k, v)
        grads = jax.grad(lambda q, k, v: jnp.sum(first_block(q, k, v)),
                         argnums=(0, 1, 2))(q, k, v)
        return out, grads
    if path == "interpret":
        with fa.force_interpret_kernels():
            out, (dq, dk, dv) = run()
    else:
        out, (dq, dk, dv) = run()
    own, _ = blockdiff.own_block(q[:, :seq], k[:, :seq], v[:, :seq], block,
                                 HEAD ** -0.5)
    np.testing.assert_array_equal(out, own[:, :block])
    for g in (dq, dk, dv):
        assert np.isfinite(g).all()
    # from the first block's queries nothing reaches a clean key or value
    assert float(jnp.abs(dk[:, seq:]).max()) == 0.0
    assert float(jnp.abs(dv[:, seq:]).max()) == 0.0
    assert float(jnp.abs(dk[:, :block]).max()) > 0.0


# -- the model -------------------------------------------------------------------

def model_of(seq, block, **changed):
    cfg = small(seq, block, **changed)
    model = tfm.Transformer(cfg)
    tree = meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32),
        train=False))["params"]
    state = TrainState.create(apply_fn=model.apply, params=tree,
                              tx=optax.sgd(0.0))
    return model, tree, state


@pytest.mark.parametrize("seq, block, path", [
    (96, 1, "interpret"), (96, 4, "interpret"), (96, 32, "interpret"),
    (96, 96, "interpret"), (96, 4, "blockwise"), (96, 32, "blockwise"),
    (640, 4, "interpret"), (1024, 4, "interpret")], ids=str)
def test_loss_and_every_gradient_leaf_match_the_reference(seq, block, path):
    """`lm_loss_fused` (the weighted streamed CE on the noised copy) and
    `lm_loss_fn` against the reference's objective, and `jax.grad` of
    the first against `jax.grad` of the reference's, leaf by leaf."""
    _, tree, state = model_of(seq, block)
    batch = drawn(seq, block)

    def run(fn):
        return jax.value_and_grad(lambda p: fn(state, p, batch)[0])(tree)
    with jax.default_matmul_precision("highest"):
        if path == "interpret":
            with fa.force_interpret_kernels():
                loss, grads = run(tfm.lm_loss_fused)
        else:
            loss, grads = run(tfm.lm_loss_fused)
        ours = plain.from_program(tree)
        want = plain.train_loss(ours, batch, hp_of(block))
        wanted = jax.grad(lambda p: plain.train_loss(p, batch,
                                                     hp_of(block)))(ours)
        if seq == 96:  # the dense loss holds (rows, L, V) logits
            unfused = tfm.lm_loss_fn(state, tree, batch)[0]
            assert float(unfused) == pytest.approx(float(want), abs=TOL)
    assert float(loss) == pytest.approx(float(want), abs=TOL)
    for (name, b), a in zip(
            jax.tree_util.tree_flatten_with_path(wanted)[0],
            jax.tree.leaves(plain.from_program(grads))):
        scale = float(jnp.abs(b).max())
        assert scale > 0, name
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, rtol=1e-4,
                                   err_msg=jax.tree_util.keystr(name))


def test_the_references_batch_functions_are_its_objective():
    """`batch_losses` + `batch_loss` and `batch_grads`, which the checker
    calls row by row, against `train_loss` and its `jax.grad`."""
    seq, block = 96, 4
    _, tree, _ = model_of(seq, block)
    batch, hp = drawn(seq, block), hp_of(block)
    ours = plain.from_program(tree)
    host = jax.tree.map(np.asarray, batch)
    losses, routed = plain.batch_losses(ours, host, hp)
    with jax.default_matmul_precision("highest"):
        want = plain.train_loss(ours, batch, hp)
        wanted = jax.grad(lambda p: plain.train_loss(p, batch, hp))(ours)
    assert plain.batch_loss(losses, host) == pytest.approx(float(want),
                                                           abs=TOL)
    assert routed[0][0].shape == (2 * seq, K)
    got = plain.batch_grads(ours, host, hp, chosen=routed)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(wanted)):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))


def test_the_metrics_carry_the_masked_share_and_the_moe_counters():
    seq, block = 96, 4
    _, tree, state = model_of(seq, block)
    batch = drawn(seq, block)
    _, metrics = tfm.lm_loss_fused(state, tree, batch)
    assert float(metrics["masked"]) == pytest.approx(
        float(np.mean(batch["masked"])))
    assert float(metrics["moe_dropped"]) == 0.0
    assert 0.0 < float(metrics["moe_held"]) < 1.0
    assert float(metrics["moe_max_load"]) >= 1.0


def test_the_clean_copy_never_meets_the_head():
    """Hidden states and logits come back for the noised copy alone, and
    the noised copy's tokens reach no clean position's state."""
    seq, block = 96, 4
    model, tree, _ = model_of(seq, block, remat=False)
    batch = drawn(seq, block)
    noised, _ = blockdiff.noised_batch(batch, VOCAB - 1)
    hidden = model.apply({"params": tree}, batch["tokens"], noised=noised,
                         return_hidden=True)
    assert hidden.shape == (2, seq, D)
    logits = model.apply({"params": tree}, batch["tokens"], noised=noised)
    assert logits.shape == (2, seq, VOCAB)


def test_the_published_sizes_count_the_configurations_parameters():
    """645,623,296 at the benchmark's cut, from shapes alone."""
    cfg = tfm.sdar_config(vocab_size=18992, n_layers=6, max_len=8192,
                          experts_held=16)
    shapes = jax.eval_shape(lambda: tfm.Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32), train=False))
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(
        meta.unbox(shapes)["params"])) == 645_623_296
    assert (cfg.head_dim, cfg.kv_heads, cfg.n_experts, cfg.moe_top_k,
            cfg.d_ff, cfg.block_length, cfg.mask_id, cfg.norm_eps,
            cfg.rope_theta, cfg.moe_renorm, cfg.moe_shared) == (
        128, 4, 128, 8, 768, 4, 18991, 1e-6, 1e6, True, 0)
    # both copies' `o` and `lse` under their names: 2 x 8192 rows a layer
    kept = tfm.kept_bytes(dataclasses.replace(cfg, remat=True), 1, 8192)
    assert kept[fa.KEPT_O] == 6 * 16384 * 32 * 128 * 2
    assert kept[fa.KEPT_LSE] == 6 * 16384 * 32 * 4


@pytest.mark.parametrize("changed", [
    {"layer_types": ("attention", "attention")}, {"pos": "learned"},
    {"attention": "dense"}, {"block_length": -4}])
def test_config_refuses_what_the_objective_cannot_run_on(changed):
    with pytest.raises(ValueError, match="block_length"):
        small(96, 4, **changed)


# -- positions -------------------------------------------------------------------

def test_rope_with_its_own_index_is_the_rope_that_was_there():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 4, 16))
    np.testing.assert_array_equal(tfm.rope(x, 1e6),
                                  tfm.rope(x, 1e6, jnp.arange(64)))
    # both copies at 0..L-1
    both = tfm.rope(jnp.concatenate([x, x], 1), 1e6, jnp.arange(128) % 64)
    np.testing.assert_array_equal(both[:, :64], both[:, 64:])
    np.testing.assert_array_equal(both[:, :64], tfm.rope(x, 1e6))


# -- the weighted sweep ------------------------------------------------------------

@pytest.mark.parametrize("block_rows", [None, 64, 100])
def test_the_weighted_sweep_is_the_dense_weighted_ce(block_rows):
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    h = jax.random.normal(keys[0], (3, 70, 32))
    kernel = jax.random.normal(keys[1], (32, 97)) / 6
    t = jax.random.randint(keys[2], (3, 70), 0, 97)
    w = jax.random.uniform(keys[3], (3, 70)) * (
        jax.random.uniform(keys[0], (3, 70)) < 0.5)

    def dense_ce(h, kernel):
        logp = jax.nn.log_softmax(h @ kernel)
        return -jnp.sum(w * jnp.take_along_axis(logp, t[..., None], -1)[..., 0])
    got = jax.value_and_grad(lambda h, k: streamed_lm_xent(
        h, k, t, block_rows, weights=w), argnums=(0, 1))(h, kernel)
    want = jax.value_and_grad(dense_ce, argnums=(0, 1))(h, kernel)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-5)
    # no gradient asked: the sweep that makes the loss alone
    assert float(streamed_lm_xent(h, kernel, t, block_rows, weights=w)) \
        == pytest.approx(float(want[0]), rel=1e-6)


def test_callers_without_weights_get_the_program_they_had():
    """The unweighted sweep bit for bit the weighted one at the weights
    it computes itself, and its jaxpr takes no weight."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    h = jax.random.normal(keys[0], (2, 50, 32))
    kernel = jax.random.normal(keys[1], (32, 97)) / 6
    t = jax.random.randint(keys[2], (2, 50), 0, 97).at[:, -1].set(-1)

    def both(**kw):
        return jax.value_and_grad(lambda h, k: streamed_lm_xent(
            h, k, t, 64, **kw), argnums=(0, 1))(h, kernel)
    n = jnp.sum(t >= 0)
    plainly = both()
    weighted = both(weights=jnp.where(t >= 0, 1.0 / n.astype(jnp.float32),
                                      0.0))
    for a, b in zip(jax.tree.leaves(plainly), jax.tree.leaves(weighted)):
        np.testing.assert_array_equal(a, b)
    text = str(jax.make_jaxpr(lambda h, k: streamed_lm_xent(h, k, t, 64))(
        h, kernel))
    assert "_xent_weighted" not in text and "_xent" in text


# -- the experts' shares -------------------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """Softmax scores, the kept gates renormalised over all the chosen
    wherever they live: the parts of 8 shares of 2 experts each are the
    layer that holds all 16, output and gradient."""
    cfg = dataclasses.replace(small(64, 4), n_experts=16, moe_top_k=4,
                              experts_held=0, experts_offset=0)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 64, D))
    v = meta.unbox(tfm.MoEMLP(cfg).init(jax.random.PRNGKey(12), x))
    whole = tfm.MoEMLP(cfg).apply(v, x)
    dy = jax.random.normal(jax.random.PRNGKey(13), whole.shape)

    def share(i, x):
        part = dataclasses.replace(cfg, experts_held=2, experts_offset=2 * i)
        held = {"params": {k: (t if k == "router" else t[2 * i:2 * i + 2])
                           for k, t in v["params"].items()}}
        return tfm.MoEMLP(part).apply(held, x)
    parts = [share(i, x) for i in range(8)]
    np.testing.assert_allclose(sum(parts), whole, atol=4e-5)
    assert min(float(jnp.abs(p).max()) for p in parts) > 1e-3
    d_whole = jax.grad(lambda x: jnp.sum(
        tfm.MoEMLP(cfg).apply(v, x) * dy))(x)
    d_parts = [jax.grad(lambda x, i=i: jnp.sum(share(i, x) * dy))(x)
               for i in range(8)]
    np.testing.assert_allclose(sum(d_parts), d_whole, atol=4e-4)
    # the uncut layer is the reference's
    p = {"router": v["params"]["router"],
         "experts": {k: v["params"][f"w_{k}"] for k in ("gate", "up",
                                                        "down")}}
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([plain.experts(
            row, *plain.route(row, p, {"top_k": 4}), p, 0) for row in x])
    np.testing.assert_allclose(whole, want, atol=4e-5)


# -- the loader's noise ----------------------------------------------------------------

def noise_by_row(world, *, start_step=0, epoch=1, seed=3, rows=24, seq=32,
                 block=4):
    source = block_noise.RowIndexed(ArraySource(
        {"tokens": np.arange(rows * seq, dtype=np.int32).reshape(rows, seq)}))
    seen = {}
    for rank in range(world):
        loader = DataLoader(source, 4 // world, rank=rank, world=world,
                            seed=seed)
        for batch in block_noise.with_noise(
                loader.epoch(epoch, start_step), seed=seed, epoch=epoch,
                block_length=block):
            assert set(batch) == {"tokens", "masked", "t"}
            for toks, masked, t in zip(batch["tokens"], batch["masked"],
                                       batch["t"]):
                seen[int(toks[0]) // seq] = (masked.copy(), t.copy())
    return seen


def test_a_rows_noise_outlives_a_restart_and_a_resize():
    one = noise_by_row(1)
    assert sorted(one) == list(range(24))
    two = noise_by_row(2)               # dp 1 -> 2: other trainers, same rows
    resumed = noise_by_row(1, start_step=3)     # from a checkpoint's cursor
    assert sorted(two) == list(range(24)) and len(resumed) == 12
    for other in (two, resumed):
        for row, (masked, t) in other.items():
            np.testing.assert_array_equal(masked, one[row][0])
            np.testing.assert_array_equal(t, one[row][1])
    # another epoch, another seed: another noise
    assert any((noise_by_row(1, epoch=2)[r][0] != one[r][0]).any()
               for r in one)
    assert any((noise_by_row(1, seed=4)[r][0] != one[r][0]).any()
               for r in one)


def test_the_noise_is_the_schedules():
    """One level a block, uniform on (1e-3, 1]; a token masked with its
    block's probability: half of all tokens in expectation."""
    masked, t = zip(*(block_noise.row_noise(0, 0, r, 4096, 4)
                      for r in range(8)))
    masked, t = np.stack(masked), np.stack(t)
    assert masked.dtype == bool and t.dtype == np.float32
    levels = t.reshape(8, -1, 4)
    assert (levels == levels[..., :1]).all()
    assert block_noise.T_MIN < t.min() and t.max() <= 1.0
    assert t.mean() == pytest.approx(0.5, abs=0.01)
    assert masked.mean() == pytest.approx(0.5, abs=0.01)
    # more noise, more masks: the tokens of the noisiest quarter of blocks
    assert masked[t > 0.75].mean() == pytest.approx(0.875, abs=0.02)
    assert masked[t < 0.25].mean() == pytest.approx(0.125, abs=0.02)


# -- the trainer -------------------------------------------------------------------------

STEP = re.compile(r"step (\d+): loss=(\S+) masked=(\S+) moe_balance=\S+ "
                  r"moe_dropped=(\S+) moe_held=(\S+) moe_max_load=(\S+) ")
SMALL_JOB = ["--vocab", "128", "--d-model", "32", "--n-heads", "4",
             "--n-layers", "2", "--d-ff", "24", "--seq-len", "64",
             "--arch", "sdar", "--block-length", "4", "--n-experts", "16",
             "--moe-top-k", "4", "--experts-held", "4", "--fused-loss",
             "--remat", "on", "--epochs", "1", "--warmup-steps", "2",
             "--lr", "1e-2"]


def lm_train(tmp_path, *flags, timeout=600):
    """`lm_train` in a process of its own: this one holds 8 devices."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
           "EDL_TPU_LOG_EVERY": "1",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "edl_tpu.examples.lm_train", "--data-dir",
         str(tmp_path / "data"), "--batch-size", "4", *flags], env=env,
        capture_output=True, text=True, timeout=timeout)


def test_lm_train_arch_sdar_resumes_its_noise_and_repeats_its_losses(
        tmp_path):
    """Four steps with a checkpoint every two, then a second run from
    the first checkpoint, as after a kill: the start line, the counters
    on every step line, and the replayed steps' losses and masked shares
    equal to the first run's (the rows met the noise they had met)."""
    ckpt = ["--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-sharded",
            "--ckpt-steps", "2", "--ckpt-sync"]
    out = lm_train(tmp_path, "--make-synthetic", "2", "--rows-per-file",
                   "8", *ckpt, *SMALL_JOB)
    assert out.returncode == 0, out.stderr[-3000:]
    assert ("sdar: diffusion over blocks of 4, 64 + 64 positions a row "
            "(noised + clean), mask token 127, t uniform on (0.001, 1]; "
            "4 q / 4 kv heads x 128, experts 0-3 of 16 held, top-4 softmax, "
            "renormalised") in out.stderr
    assert "by blocks of 4, strictly" in out.stderr
    first = {int(s): tuple(map(float, rest))
             for s, *rest in STEP.findall(out.stderr)}
    assert sorted(first) == [1, 2, 3, 4]
    for loss, masked, dropped, held, load in first.values():
        assert np.isfinite(loss) and dropped == 0.0
        assert 0.0 < held < 1.0 <= load <= 16 / 4
        assert 0.2 < masked < 0.8
    assert "final_eval_loss=" in out.stdout
    kept = sorted((tmp_path / "ckpt").glob("ckpt-*"),
                  key=lambda p: int(p.name[5:]))
    import shutil
    for p in kept[1:]:
        shutil.rmtree(p)
    out = lm_train(tmp_path, *ckpt, *SMALL_JOB)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "restored checkpoint" in out.stderr
    second = {int(s): tuple(map(float, rest))
              for s, *rest in STEP.findall(out.stderr)}
    assert sorted(second) == [3, 4]
    for step in second:
        assert second[step] == pytest.approx(first[step], abs=1e-4)


@pytest.mark.parametrize("flags, message", [
    (["--arch", "sdar", "--moe-dispatch", "flat"], "no exchange"),
    (["--arch", "gpt2", "--block-length", "4"], "only --arch sdar"),
    (["--arch", "afmoe", "--block-length", "4"], "only --arch sdar"),
    (["--arch", "sdar", "--window", "64"], "only --arch afmoe"),
    (["--arch", "olmoe", "--experts-held", "4"], "only --arch afmoe"),
])
def test_lm_train_refuses_flags_that_contradict_the_arch(tmp_path, flags,
                                                         message):
    from edl_tpu.examples.lm_train import main
    (tmp_path / "train-0000.npz").write_bytes(b"")
    with pytest.raises(SystemExit, match=re.escape(message)):
        main(["--data-dir", str(tmp_path), *flags])


def test_lm_train_arch_sdar_refuses_several_devices(tmp_path):
    from edl_tpu.examples.lm_train import main
    (tmp_path / "train-0000.npz").write_bytes(b"")
    assert jax.device_count() > 1
    with pytest.raises(SystemExit, match="no exchange between chips"):
        main(["--data-dir", str(tmp_path), "--arch", "sdar", "--batch-size",
              str(jax.device_count())])


# -- what the tolerance refuses ----------------------------------------------------------

@pytest.mark.parametrize("what", [
    "bf16", "gates_not_renormalised", "rope_by_place", "weight_left_out",
    "never_strict", "reference_causal_over_2L"])
def test_the_tolerance_refuses(what, monkeypatch):
    seq, block = 96, 4
    changed = {"bf16": {"dtype": jnp.bfloat16},
               "gates_not_renormalised": {"moe_renorm": False}}.get(what, {})
    if what == "rope_by_place":
        rope = tfm.rope
        monkeypatch.setattr(tfm, "rope",
                            lambda x, theta, positions=None: rope(x, theta))
    if what == "weight_left_out":
        draw = blockdiff.noised_batch
        monkeypatch.setattr(blockdiff, "noised_batch", lambda b, m: draw(
            {**b, "t": jnp.ones_like(b["t"])}, m))
    if what == "never_strict":
        lse = blockdiff.flash_attention_lse
        monkeypatch.setattr(
            blockdiff, "flash_attention_lse",
            lambda *a, blocks, **kw: lse(*a, blocks=(blocks[0], False), **kw))
    if what == "reference_causal_over_2L":
        monkeypatch.setattr(plain, "visible", lambda length, block: jnp.tril(
            jnp.ones((2 * length, 2 * length), bool)))
    _, tree, state = model_of(seq, block, **changed)
    batch = drawn(seq, block)
    with jax.default_matmul_precision("highest"):
        loss = tfm.lm_loss_fused(state, tree, batch)[0]
        want = plain.train_loss(plain.from_program(tree), batch,
                                hp_of(block))
    assert abs(float(loss) - float(want)) > 20 * TOL
