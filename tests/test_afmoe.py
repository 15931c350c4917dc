"""The Trinity-Mini block (`models.transformer.afmoe_config`: sliding-
window and global attention mixed, gated, grouped-query with a head
size of its own; four norms a block; a leading dense layer; sigmoid
routing over score + bias, a shared expert, a chip's share of the
experts) against the plain reference
`benchmark/reference/trinity_mini_plain.py`, at a small size on the CPU
with seeded random weights: each new piece of `Attention` by itself,
the whole model's logits, loss and every gradient leaf, what the bias
may and may not move, its rule after a step and through a checkpoint,
the shares of the experts adding up to the uncut layer, the share of
everything equal to the path that was there, and the trainer end to end.

Tolerances. Program and reference both compute in float32 here and
differ in the order of their sums (the program sorts assignments by
expert and sums a token's k outputs last; the reference loops over the
held experts): differences read 2e-6 to 7e-6 on logits of size 4.
`TOL` = 4e-5 is five times that. The controls at the end show what it
refuses: a dropped gate, rope on the global layer, a window of half the
size, the bias added to the gates, the shared expert left out and
bfloat16 activations each move the logits by 50 x TOL or more.
"""

import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import traverse_util
from flax.core import meta

from benchmark.reference import trinity_mini_plain as plain
from edl_tpu.models import transformer as tfm
from edl_tpu.train.state import TrainState, TrainStatus
from edl_tpu.train.step import make_train_step

TOL = 4e-5
VOCAB, SEQ, D, HEADS, KV, HEAD, FF = 96, 64, 32, 4, 2, 16, 48
WINDOW, E, K, HELD, FIRST, EFF = 8, 16, 4, 4, 4, 16
KINDS = ("sliding", "sliding", "sliding", "sliding", "full")
HP = {"n_head": HEADS, "n_kv_head": KV, "eps": 1e-5, "theta": 10000.0,
      "window": WINDOW, "top_k": K, "route_scale": 2.826,
      "first_expert": FIRST, "layer_types": list(KINDS)}


def small(**changed):
    return dataclasses.replace(tfm.afmoe_config(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=len(KINDS),
        d_ff=FF, max_len=SEQ, n_kv_heads=KV, head_size=HEAD, window=WINDOW,
        layer_types=KINDS, n_dense_layers=1, moe_d_ff=EFF, n_experts=E,
        moe_top_k=K, experts_held=HELD, experts_offset=FIRST,
        dtype=jnp.float32), **changed)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(11).integers(
        0, VOCAB, (2, SEQ)), jnp.int32)


@pytest.fixture(scope="module")
def variables(tokens):
    """Seeded parameters and biases; the norms' scales drawn too (all
    ones would hide a norm that forgets its scale), the routers wider
    than their init (decisive routing), the biases drawn (zeros would
    hide a bias that is forgotten, or one that weighs)."""
    v = meta.unbox(tfm.Transformer(small()).init(
        jax.random.PRNGKey(5), tokens, train=False))
    flat = traverse_util.flatten_dict(v["params"])
    rng = np.random.default_rng(17)
    for path, leaf in flat.items():
        if path[-1] == "scale":
            flat[path] = jnp.asarray(
                rng.uniform(0.5, 1.5, leaf.shape), jnp.float32)
        if path[-1] == "router":
            flat[path] = leaf * 20.0
    stats = jax.tree.map(lambda b: jnp.asarray(
        rng.normal(0, 0.05, b.shape), jnp.float32), v["batch_stats"])
    return {"params": traverse_util.unflatten_dict(flat),
            "batch_stats": stats}


def program_logits(variables, tokens, **kw):
    return tfm.Transformer(small(**kw)).apply(variables, tokens, train=True)


def plain_params(variables):
    return plain.from_program(variables["params"], variables["batch_stats"])


def plain_logits(variables, tokens, hp=HP):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([plain.forward(plain_params(variables), row, hp)[0]
                          for row in tokens])


def state_of(variables, tx=None, **kw):
    return TrainState.create(
        apply_fn=tfm.Transformer(small(**kw)).apply,
        params=variables["params"], tx=tx or optax.sgd(0.1),
        batch_stats=variables["batch_stats"])


# -- attention, piece by piece ----------------------------------------------

def attention_alone(variables, x, kind, **kw):
    cfg = small(**kw)
    return tfm.Attention(cfg, kind).apply(
        {"params": variables["params"]["block1"]["attn"]}, x)


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_an_attention_layer_matches_the_reference(variables, kind):
    """Grouped-query heads of a size of their own, RMSNorm a head, rope
    on the sliding kind and none on the full one, the window, the gate."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, D))
    p = plain_params(variables)["blocks"][1]["attn"]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([plain.attention(row, p, HP, kind == "sliding")
                          for row in x])
    got = attention_alone(variables, x, kind)
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("piece, changed, kind", [
    ("gate", {"attn_gate": False}, "sliding"),
    ("norm_a_head", {"qk_norm_heads": False}, "sliding"),
    ("window", {"window": WINDOW // 2}, "sliding"),
    ("window_on_the_full_layer", {}, "full->sliding"),
    ("rope_on_the_full_layer", {}, "full->attention"),
    ("no_rope_on_the_sliding_layer", {"pos": "none"}, "sliding"),
])
def test_each_piece_of_attention_counts(variables, piece, changed, kind):
    """Every piece moves the layer's output by far more than `TOL`: the
    comparison above would refuse a layer without it."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, D))
    kind, _, other = kind.partition("->")
    params = variables
    if piece == "norm_a_head":  # no norm, no scales to apply
        flat = traverse_util.flatten_dict(variables["params"])
        params = {"params": traverse_util.unflatten_dict(
            {k: v for k, v in flat.items()
             if k[-2] not in ("q_norm", "k_norm")})}
    moved = float(jnp.abs(
        attention_alone(variables, x, kind)
        - attention_alone(params, x, other or kind, **changed)).max())
    assert moved > 50 * TOL, (piece, moved)


def test_attention_parameters_are_the_sources(variables):
    a = variables["params"]["block1"]["attn"]
    shapes = {k: tuple(v[next(iter(v))].shape) for k, v in a.items()}
    assert shapes == {
        "query": (D, HEADS, HEAD), "gate": (D, HEADS, HEAD),
        "key": (D, KV, HEAD), "value": (D, KV, HEAD),
        "out": (HEADS, HEAD, D), "q_norm": (HEAD,), "k_norm": (HEAD,)}
    block = variables["params"]["block1"]
    assert {k for k in block if k.startswith("ln_")} == {
        "ln_attn", "ln_attn_out", "ln_mlp", "ln_mlp_out"}
    assert "moe_mlp" not in variables["params"]["block0"]
    assert variables["params"]["block0"]["mlp_gate"]["kernel"].shape \
        == (D, FF)
    m = block["moe_mlp"]
    assert m["router"].shape == (D, E)
    assert m["w_gate"].shape == (HELD, D, EFF)
    assert m["shared_down"]["kernel"].shape == (EFF, D)


def test_the_published_sizes_count_the_configurations_parameters():
    """The cell's cut at the published widths, by `jax.eval_shape`:
    705,473,792, as the configuration file's arithmetic has it."""
    cfg = tfm.afmoe_config(
        vocab_size=25024, n_layers=5, n_dense_layers=1, max_len=8192,
        layer_types=KINDS, experts_held=16)
    shapes = jax.eval_shape(lambda: tfm.Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32), train=False))
    assert sum(x.size for x in jax.tree.leaves(shapes["params"])) \
        == 705_473_792
    assert sum(x.size for x in jax.tree.leaves(shapes["batch_stats"])) \
        == 4 * 128


def test_depth_cut_keeps_the_pattern():
    full = tfm.afmoe_config()
    assert full.layer_types.count("full") == 8 and full.n_layers == 32
    assert [i for i, k in enumerate(full.layer_types) if k == "full"] \
        == list(range(3, 32, 4))
    assert tfm.afmoe_config(n_layers=8).layer_types == full.layer_types[:8]
    assert (full.head_dim, full.kv_heads, full.window, full.held_experts,
            full.n_dense_layers) == (128, 4, 2048, 128, 2)


# -- the whole model ---------------------------------------------------------

def test_logits_match_the_reference(variables, tokens):
    np.testing.assert_allclose(program_logits(variables, tokens),
                               plain_logits(variables, tokens), atol=TOL)


@pytest.mark.parametrize("loss", [tfm.lm_loss_fn, tfm.lm_loss_fused],
                         ids=["dense_logits", "streamed"])
def test_loss_and_every_gradient_leaf_match_the_reference(variables, tokens,
                                                          loss):
    state = state_of(variables)
    (mine, aux), grads = jax.value_and_grad(
        lambda p: loss(state, p, {"tokens": tokens}), has_aux=True)(
        variables["params"])
    params = plain_params(variables)
    with jax.default_matmul_precision("highest"):
        theirs, wanted = jax.value_and_grad(plain.train_loss)(
            params, tokens, HP)
    assert float(mine) == pytest.approx(float(theirs), abs=TOL)
    assert float(aux["moe_dropped"]) == 0.0
    got = plain.from_program(grads)
    for b, w in zip(got["blocks"], wanted["blocks"]):
        b.pop("bias", None)
        assert "bias" not in w or not np.asarray(w.pop("bias")).any()
    flat_w = jax.tree_util.tree_flatten_with_path(wanted)[0]
    for (path, w), g in zip(flat_w, jax.tree.leaves(got)):
        scale = max(float(jnp.abs(w).max()), 1e-3)
        assert float(jnp.abs(g - w).max()) <= 10 * TOL * scale, \
            jax.tree_util.keystr(path)


def test_the_references_batch_gradient_is_the_gradient_of_its_loss(
        variables, tokens):
    """`batch_grads` (a row at a time, the program's own experts given)
    is `jax.grad(train_loss)`: the given experts are the routed ones."""
    params = plain_params(variables)
    _, routed = plain.batch_losses(params, np.asarray(tokens), HP)
    rows = plain.batch_grads(params, np.asarray(tokens), HP, chosen=routed)
    with jax.default_matmul_precision("highest"):
        whole = jax.grad(plain.train_loss)(params, tokens, HP)
    for b in whole["blocks"]:
        b.pop("bias", None)
    for a, b in zip(jax.tree.leaves(rows), jax.tree.leaves(whole)):
        np.testing.assert_allclose(a, b, atol=TOL)


def test_remat_changes_no_number(variables, tokens):
    a = program_logits(variables, tokens)
    b = program_logits(variables, tokens, remat=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_remat_changes_no_gradient(variables, tokens):
    """Through the flash call's backward rule (its XLA path here), the
    sandwich norms and the expert layers: what a rematerialised block
    keeps (`transformer.KEPT`) is what its replay would rebuild."""
    ga, gb = (jax.grad(lambda p, r=r: tfm.lm_loss_fn(
        state_of(variables, remat=r, attention="flash"), p,
        {"tokens": tokens})[0])(variables["params"]) for r in (False, True))
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        assert float(jnp.abs(a - b).max()) < 1e-6


# -- the routing bias --------------------------------------------------------

def routed(variables, x, **kw):
    """(gates (T, k) in expert order, experts (T, k) sorted) of block 1's
    router on x, as the reference computes them from the program's
    numbers: the program's own are checked through the logits."""
    p = plain_params(variables)["blocks"][1]
    w, idx, _ = plain.route(x, {**p, **kw}, HP)
    order = jnp.argsort(idx, -1)
    return (jnp.take_along_axis(w, order, -1),
            jnp.take_along_axis(idx, order, -1))


def test_the_bias_changes_the_experts_and_never_the_gates(variables):
    x = jax.random.normal(jax.random.PRNGKey(3), (SEQ, D))
    no_bias = jnp.zeros((E,), jnp.float32)
    w0, idx0 = routed(variables, x, bias=no_bias)
    w1, idx1 = routed(variables, x)
    same = np.asarray((idx0 == idx1).all(-1))
    assert 0 < same.sum() < SEQ          # some tokens move, some stay
    # where the experts are the same, so are the gates (to the order of
    # the sum that renormalises them; the biases are of size 0.05)
    np.testing.assert_allclose(np.asarray(w0)[same], np.asarray(w1)[same],
                               atol=1e-6)
    # and the program, which computes it: without its biases the logits
    # move; with a bias so large that it fixes the experts, the gates
    # still come from the scores (the logits stay finite and of size 1)
    tokens = jnp.asarray(np.random.default_rng(4).integers(
        0, VOCAB, (1, SEQ)), jnp.int32)
    zeroed = {**variables, "batch_stats": jax.tree.map(
        jnp.zeros_like, variables["batch_stats"])}
    assert float(jnp.abs(program_logits(variables, tokens)
                         - program_logits(zeroed, tokens)).max()) > 50 * TOL
    huge = {**variables, "batch_stats": jax.tree.map(
        lambda b: b + 100.0 * (jnp.arange(E) < K), variables["batch_stats"])}
    np.testing.assert_allclose(program_logits(huge, tokens),
                               plain_logits(huge, tokens), atol=TOL)


def test_the_bias_gets_no_gradient_and_moves_by_its_rule(variables, tokens):
    """One train step: the new bias is the rule applied to the step's
    own counts of all E experts (the reference's), whatever the
    optimizer does to the parameters."""
    state = state_of(variables)
    step = make_train_step(tfm.lm_loss_fused, donate=False)
    after, metrics = step(state, {"tokens": tokens})
    assert "batch_stats" not in metrics
    _, routed = plain.batch_losses(plain_params(variables),
                                   np.asarray(tokens), HP)
    for layer in range(1, len(KINDS)):
        counts = np.zeros(E)
        for row in routed:
            counts += np.bincount(row[layer - 1].ravel(), minlength=E)
        assert counts.sum() == tokens.size * K
        before = variables["batch_stats"][f"block{layer}"]["moe_mlp"][
            "expert_bias"]
        got = after.batch_stats[f"block{layer}"]["moe_mlp"]["expert_bias"]
        want = plain.bias_after(before, jnp.asarray(counts, jnp.float32),
                                0.001)
        np.testing.assert_allclose(got, want, atol=1e-7)
        moved = np.asarray(got - before)
        assert abs(moved.sum()) < 1e-6 and np.abs(moved).max() <= 0.002
    held = sum(((row[layer] >= FIRST) & (row[layer] < FIRST + HELD)).sum()
               for row in routed for layer in range(len(KINDS) - 1))
    assert float(metrics["moe_held"]) == pytest.approx(
        held / (tokens.size * K * (len(KINDS) - 1)), abs=1e-6)
    assert float(metrics["moe_dropped"]) == 0.0
    # an evaluation moves nothing
    out = tfm.lm_loss_fused(after, after.params, {"tokens": tokens})
    assert np.isfinite(float(out[0]))


@pytest.mark.parametrize("sharded", [False, True])
def test_checkpoint_carries_the_bias(tmp_path, variables, tokens, sharded):
    """`CheckpointManager`, sharded or not: the bias after a step comes
    back with the parameters and the moments, leaf for leaf, and the
    restored state steps to the same bias as the one that never left."""
    from edl_tpu.train.checkpoint import CheckpointManager
    state = state_of(variables, tx=optax.adamw(1e-2))
    step = make_train_step(tfm.lm_loss_fused, donate=False)
    state, _ = step(state, {"tokens": tokens})
    manager = CheckpointManager(str(tmp_path), sharded=sharded)
    manager.save(state, TrainStatus(epoch=0, step=1))
    fresh = dataclasses.replace(
        state_of(jax.tree.map(jnp.zeros_like, variables),
                 tx=optax.adamw(1e-2)))
    restored, status = manager.restore(fresh)
    assert status.step == 1
    mine = jax.tree_util.tree_flatten_with_path(state)[0]
    back = jax.tree_util.tree_flatten_with_path(restored)[0]
    assert [p for p, _ in mine] == [p for p, _ in back]
    assert any("expert_bias" in jax.tree_util.keystr(p) for p, _ in mine)
    for (path, a), (_, b) in zip(mine, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
    on, _ = step(state, {"tokens": tokens})
    resumed, _ = step(restored, {"tokens": tokens})
    for a, b in zip(jax.tree.leaves(on.batch_stats),
                    jax.tree.leaves(resumed.batch_stats)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- a chip's share of the experts -------------------------------------------

def whole_layer(seed=9):
    """An expert layer that holds all E experts, and an input."""
    cfg = small(experts_held=0, experts_offset=0)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, SEQ, D))
    v = meta.unbox(tfm.MoEMLP(cfg).init(jax.random.PRNGKey(seed + 1), x))
    v["params"]["router"] = v["params"]["router"] * 20.0
    v["batch_stats"] = jax.tree.map(
        lambda b: 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 2),
                                           b.shape), v["batch_stats"])
    return cfg, v, x


def share_of(v, first, held):
    tables = {k: (t[first:first + held] if k.startswith("w_") else t)
              for k, t in v["params"].items()}
    return {"params": tables, "batch_stats": v["batch_stats"]}


def shared_alone(cfg, v, x):
    m = {k: v["params"][f"shared_{k}"]["kernel"]
         for k in ("gate", "up", "down")}
    with jax.default_matmul_precision("highest"):
        return plain.swiglu(x.reshape(-1, D), m).reshape(x.shape)


def x_gradient(f, x, dy):
    return jax.grad(lambda x: jnp.sum(f(x) * dy))(x)


@pytest.mark.parametrize("shares", [2, 4, 8, 16])
def test_the_shares_add_up(shares):
    """The routed parts of all the shares, plus the shared expert once,
    are the uncut layer: and the uncut layer is the reference's."""
    cfg, v, x = whole_layer()
    whole = tfm.MoEMLP(cfg).apply(v, x)
    shared = shared_alone(cfg, v, x)
    held = E // shares
    parts = [tfm.MoEMLP(small(experts_held=held, experts_offset=i * held))
             .apply(share_of(v, i * held, held), x) - shared
             for i in range(shares)]
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=TOL)
    # no part is empty or the whole: the test can tell the shares apart
    sizes = [float(jnp.abs(p).max()) for p in parts]
    assert min(sizes) > 100 * TOL
    assert float(jnp.abs(parts[0] - (whole - shared)).max()) > 100 * TOL
    # and so do their gradients with respect to x (through the experts
    # and through the gates), which is what the backward's mask guards:
    # a share that let the absent experts' slots through would add rows
    # of other shares' cotangents to its own
    dy = jax.random.normal(jax.random.PRNGKey(21), whole.shape)
    d_whole = x_gradient(lambda x: tfm.MoEMLP(cfg).apply(v, x), x, dy)
    d_shared = x_gradient(lambda x: shared_alone(cfg, v, x), x, dy)
    d_parts = [x_gradient(
        lambda x, i=i: tfm.MoEMLP(small(
            experts_held=held, experts_offset=i * held)).apply(
                share_of(v, i * held, held), x), x, dy) - d_shared
        for i in range(shares)]
    np.testing.assert_allclose(sum(d_parts) + d_shared, d_whole,
                               atol=10 * TOL)
    assert min(float(jnp.abs(p).max()) for p in d_parts) > 100 * TOL
    # the uncut layer against the reference, given every expert
    p = {"router": v["params"]["router"],
         "bias": v["batch_stats"]["expert_bias"],
         "shared": {k: v["params"][f"shared_{k}"]["kernel"]
                    for k in ("gate", "up", "down")},
         "experts": {k: v["params"][f"w_{k}"] for k in ("gate", "up",
                                                        "down")}}
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([plain.experts(
            row, *plain.route(row, p, HP)[:2], p, 0) for row in x])
    np.testing.assert_allclose(whole, want, atol=TOL)


def test_a_share_counts_what_it_holds_and_drops_nothing():
    cfg, v, x = whole_layer()
    layer = tfm.MoEMLP(small(experts_held=4, experts_offset=8))
    _, sown = layer.apply(share_of(v, 8, 4), x, mutable=["intermediates"])
    inter = sown["intermediates"]
    frac = np.asarray(inter["moe_frac"][0])
    assert frac.shape == (E,) and frac.sum() == pytest.approx(1.0)
    assert float(inter["moe_held"][0]) == pytest.approx(frac[8:12].sum())
    assert float(inter["moe_dropped"][0]) == 0.0
    # every token on absent experts: the routed part is exactly nothing
    away = dict(v["batch_stats"])
    away["expert_bias"] = 100.0 * (jnp.arange(E) < K)
    out, sown = layer.apply({**share_of(v, 8, 4), "batch_stats": away}, x,
                            mutable=["intermediates"])
    assert float(sown["intermediates"]["moe_held"][0]) == 0.0
    np.testing.assert_allclose(out, shared_alone(cfg, v, x), atol=TOL)
    # and its gradient is the shared expert's alone
    dy = jax.random.normal(jax.random.PRNGKey(21), out.shape)
    away = {**share_of(v, 8, 4), "batch_stats": away}
    np.testing.assert_allclose(
        x_gradient(lambda x: layer.apply(away, x), x, dy),
        x_gradient(lambda x: shared_alone(cfg, v, x), x, dy), atol=TOL)


def as_the_chip_leaves_the_tail(real):
    """`jax.lax.ragged_dot` as XLA's kernel on the chip treats the rows
    past the groups (`tools/ragged_dot_tail.py`): it reads none of them,
    whatever they hold, and writes none, so that they come back holding
    anything: NaN here, forward and in d(lhs). The CPU's zeroes them,
    which is why no other test of this file can see a missing mask."""
    def tail(rows, sizes):
        return (jnp.arange(rows.shape[0]) >= jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def dot(a, w, sizes):
        t = tail(a, sizes)
        return jnp.where(t, jnp.nan, real(jnp.where(t, 0, a), w, sizes))

    def fwd(a, w, sizes):
        return dot(a, w, sizes), (a, w, sizes)

    def bwd(res, g):
        a, w, sizes = res
        t = tail(a, sizes)
        da, dw = jax.vjp(lambda a, w: real(a, w, sizes),
                         jnp.where(t, 0, a), w)[1](jnp.where(t, 0, g))
        return jnp.where(t, jnp.nan, da), dw, None

    dot.defvjp(fwd, bwd)
    return dot


@pytest.mark.parametrize("held, first", [(4, 8), (E, 0)],
                         ids=["a_share", "every_expert"])
def test_what_the_kernel_leaves_past_the_groups_reaches_nothing(
        monkeypatch, held, first):
    """The layer's output and every gradient leaf with the grouped
    matmul's tail poisoned, forward and backward, are finite and are the
    unpoisoned run's to the bit. Holding every expert there is no tail,
    and the same holds trivially."""
    _, v, x = whole_layer()
    layer = tfm.MoEMLP(small(experts_held=held, experts_offset=first))
    v = share_of(v, first, held)
    dy = jax.random.normal(jax.random.PRNGKey(21), x.shape)

    def run():
        def loss(params, x):
            y = layer.apply({**v, "params": params}, x)
            return jnp.sum(y * dy), y
        (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                           has_aux=True)(v["params"], x)
        return jax.tree.leaves((y, grads))

    clean = run()
    poisoned = as_the_chip_leaves_the_tail(jax.lax.ragged_dot)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    dirty = run()
    assert len(clean) == 2 + len(jax.tree.leaves(v["params"]))
    for a, b in zip(clean, dirty):
        assert np.isfinite(np.asarray(b)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the poison is there: a share's buffer is mostly tail, and the
    # wrapped product's tail is NaN both ways
    sizes = jnp.full((4,), 2, jnp.int32)
    a, w = jnp.ones((16, 8)), jnp.ones((4, 8, 8))
    out, back = jax.vjp(lambda a: poisoned(a, w, sizes), a)
    for rows in (out, back(jnp.ones_like(out))[0]):
        assert np.isfinite(np.asarray(rows[:8])).all()
        assert np.isnan(np.asarray(rows[8:])).all()


# -- the rows' one cotangent: gate | up joined in the backward ---------------

def two_products(monkeypatch):
    """The backward the parent had: autodiff's, through `w_gate` and
    `w_up` as a grouped product each, what `experts_one_cotangent` still
    says where a mesh axis splits the tables' "mlp" axis."""
    monkeypatch.setattr(tfm.TransformerConfig, "experts_one_cotangent",
                        property(lambda self: False))


def layer_and_gradients(layer, v, x, dy):
    def loss(params, x):
        y = layer.apply({**v, "params": params}, x)
        return jnp.sum(y.astype(jnp.float32) * dy), y
    (_, y), (d_params, d_x) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(v["params"], x)
    return {"y": y, "x": d_x, **d_params}


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2 ** -6)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("held, first, remat", [(4, 8, True), (E, 0, False)],
                         ids=["a_share_replayed", "every_expert"])
def test_the_joined_backward_is_the_two_products(monkeypatch, held, first,
                                                 remat, dtype, tol):
    """The layer's output and every gradient leaf (the three tables, the
    router, the shared expert, the rows) with the backward against
    `[w_gate | w_up]` as one grouped product, against autodiff's through
    the two products on the same tables and rows, the kernel's tail NaN
    in both: the output to the bit (the forward is the same two
    products), the gradients the same to the order of the sums in
    float32 (the rows' cotangent is one sum over 2f columns where it was
    two over f, added), and within bfloat16's rounding, 2^-6 of a leaf's
    largest entry (0.6 % read), with bfloat16 rows and tables."""
    _, v, x = whole_layer()
    layer = tfm.MoEMLP(small(experts_held=held, experts_offset=first,
                             dtype=dtype, remat=remat))
    v, x = share_of(v, first, held), x.astype(dtype)
    dy = jax.random.normal(jax.random.PRNGKey(21), x.shape)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        as_the_chip_leaves_the_tail(jax.lax.ragged_dot))
    assert layer.cfg.experts_one_cotangent
    one = layer_and_gradients(layer, v, x, dy)
    two_products(monkeypatch)
    two = layer_and_gradients(layer, v, x, dy)
    assert set(one) == {"y", "x", "router", "w_gate", "w_up", "w_down",
                        "shared_gate", "shared_up", "shared_down"}
    np.testing.assert_array_equal(np.asarray(one["y"], np.float32),
                                  np.asarray(two["y"], np.float32))
    for name, want in two.items():
        for a, b in zip(jax.tree.leaves(one[name]), jax.tree.leaves(want)):
            a, b = (np.asarray(z, np.float32) for z in (a, b))
            assert np.isfinite(a).all() and a.shape == b.shape, name
            assert np.abs(b).max() > 0, name
            np.testing.assert_allclose(a, b, rtol=0,
                                       atol=tol * np.abs(b).max(),
                                       err_msg=name)


@pytest.mark.parametrize("replayed", [False, True])
def test_swiglu_rows_is_the_expert_ffns_first_half(replayed):
    """`_swiglu_rows` against the expression `_expert_ffn` computes,
    `silu(x @ w_gate) * (x @ w_up)` over ragged groups (one of them
    empty, rows left over past the last): the value to the bit, and the
    three cotangents autodiff's; ``replayed`` cuts the tables' halves
    out behind a barrier and changes no number."""
    e, d, f = 4, 16, 12
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(keys[0], (30, d))
    w_gate, w_up = (jax.random.normal(k, (e, d, f)) / 4 for k in keys[1:3])
    sizes = jnp.asarray([3, 9, 0, 12], jnp.int32)
    dy = jax.random.normal(keys[3], (30, f))

    def plain(x, w_gate, w_up):
        return tfm._expert_ffn(
            x, [w_gate, w_up, jnp.broadcast_to(jnp.eye(f), (e, f, f))],
            lambda a, w: jax.lax.ragged_dot(a, w, sizes), jnp.float32)

    def mine(x, w_gate, w_up):
        return tfm._swiglu_rows(x, w_gate, w_up, sizes, replayed)
    got, back = jax.vjp(mine, x, w_gate, w_up)
    want, auto = jax.vjp(plain, x, w_gate, w_up)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    for a, b in zip(back(dy), auto(dy)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_the_experts_parameters_are_the_parents():
    """What checkpoints, the resume, `trainer_draw` and the reference
    checkers read by name: `w_gate` and `w_up` stay two float32 leaves
    of (held, d, f) with their logical axes, whatever the step's
    backward makes of them (the list is the parent's, commit daa975e)."""
    x = jnp.zeros((1, SEQ, D))
    boxed = tfm.MoEMLP(small()).init(jax.random.PRNGKey(0), x)["params"]
    flat = traverse_util.flatten_dict(boxed, sep="/", is_leaf=lambda _, v:
                                      isinstance(v, meta.Partitioned))
    assert {k: (v.value.shape, str(v.value.dtype), v.names)
            for k, v in flat.items()} == {
        "router": ((D, E), "float32", ("embed", "expert_router")),
        "w_gate": ((HELD, D, EFF), "float32", ("expert", "embed", "mlp")),
        "w_up": ((HELD, D, EFF), "float32", ("expert", "embed", "mlp")),
        "w_down": ((HELD, EFF, D), "float32", ("expert", "mlp", "embed")),
        "shared_up/kernel": ((D, EFF), "float32", ("embed", "mlp")),
        "shared_gate/kernel": ((D, EFF), "float32", ("embed", "mlp")),
        "shared_down/kernel": ((EFF, D), "float32", ("mlp", "embed")),
    }
    v = meta.unbox(boxed)
    assert not np.array_equal(np.asarray(v["w_up"]), np.asarray(v["w_gate"]))


def grouped_products_and_sums(cfg, rows):
    """The gradient of one expert layer as a jaxpr: shapes of the grouped
    products' results, and of what `add_any` adds over ``rows`` rows."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, D))
    layer = tfm.MoEMLP(cfg)
    v = meta.unbox(layer.init(jax.random.PRNGKey(4), x))

    def loss(params, x):
        return jnp.sum(layer.apply({**v, "params": params}, x))
    products, sums = [], []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            shape = tuple(eqn.outvars[0].aval.shape)
            if eqn.primitive.name.startswith("ragged_dot"):
                products.append(shape)
            elif eqn.primitive.name == "add_any" and shape[0] == rows:
                sums.append(shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(v["params"], x).jaxpr)
    return sorted(products), sorted(sums)


def test_the_rows_get_one_cotangent_and_no_sum(monkeypatch):
    """The backward of a dropless layer, as a jaxpr: seven live grouped
    products where nine stood (forward gate, up and down as they were;
    d(lhs) of gate|up and of down, two where three stood; d(rhs) of
    each, two where three stood), and no `add_any` over T*k rows:
    neither the two cotangents of the dispatched rows, (T*k, d), nor two
    halves of d(h) padded to (T*k, 2f). The other form counts as the
    parent did."""
    f, rows = 24, 2 * SEQ * K                  # 2f = 48: no other size
    cfg = small(moe_d_ff=f)
    products, sums = grouped_products_and_sums(cfg, rows)
    assert products == sorted([
        (rows, f), (rows, f), (rows, D),       # forward
        (rows, D), (rows, f),                  # d(lhs)
        (HELD, D, 2 * f), (HELD, f, D),        # d(rhs)
        # where `jax.vjp` linearises the joined product: read by no one,
        # and not in the compiled layer (`tests/test_chip_compile.py`)
        (rows, 2 * f)])
    assert sums == []
    two_products(monkeypatch)
    products, sums = grouped_products_and_sums(cfg, rows)
    assert len(products) == 9
    assert products.count((rows, D)) == 3 and (rows, D) in sums


def test_one_cotangent_wherever_no_mesh_axis_splits_the_tables():
    """`experts_one_cotangent` by what the config can see: the logical
    rules on its mesh. No mesh, or one that leaves "mlp" whole: the
    joined backward; a mesh axis that splits "mlp" (tp, by the default
    rules): autodiff's two products, for the concatenation would run
    along the split axis."""
    from jax.sharding import Mesh
    devices = np.asarray(jax.devices()[:4])
    assert small().experts_one_cotangent
    assert small(mesh=Mesh(devices.reshape(2, 2),
                           ("dp", "fsdp"))).experts_one_cotangent
    assert small(mesh=Mesh(devices.reshape(4, 1),
                           ("fsdp", "tp"))).experts_one_cotangent
    assert not small(mesh=Mesh(devices.reshape(2, 2),
                               ("fsdp", "tp"))).experts_one_cotangent
    assert small(mesh=Mesh(devices.reshape(2, 2), ("fsdp", "tp")),
                 rules=(("mlp", None),)).experts_one_cotangent
    # gelu's two tables have nothing to put side by side
    assert not dataclasses.replace(small(), moe_gated=False
                                   ).experts_one_cotangent


def test_a_checkpoint_of_the_two_products_restores_in_the_one(
        monkeypatch, tmp_path, variables, tokens):
    """A checkpoint written by the program with autodiff's backward (the
    parent's step) restores leaf for leaf into the program with the
    joined one, and both step on from it to the same parameters, within
    float32's order of sums: nothing of the form is in the state."""
    from edl_tpu.train.checkpoint import CheckpointManager
    batch = {"tokens": tokens}
    with monkeypatch.context() as parent:
        two_products(parent)
        step = make_train_step(tfm.lm_loss_fused, donate=False)
        state, _ = step(state_of(variables, tx=optax.adamw(1e-2)), batch)
        CheckpointManager(str(tmp_path)).save(
            state, TrainStatus(epoch=0, step=1))
        on, _ = step(state, batch)
    assert small().experts_one_cotangent
    fresh = state_of(jax.tree.map(jnp.zeros_like, variables),
                     tx=optax.adamw(1e-2))
    restored, status = CheckpointManager(str(tmp_path)).restore(fresh)
    assert status.step == 1
    mine = jax.tree_util.tree_flatten_with_path(state)[0]
    back = jax.tree_util.tree_flatten_with_path(restored)[0]
    assert [p for p, _ in mine] == [p for p, _ in back]
    for (path, a), (_, b) in zip(mine, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
    resumed, _ = make_train_step(tfm.lm_loss_fused, donate=False)(
        restored, batch)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(on.params)[0],
            jax.tree_util.tree_flatten_with_path(resumed.params)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL,
                                   err_msg=jax.tree_util.keystr(path))


def test_remat_replays_no_gather_of_a_shares_buffer(variables, tokens):
    """`tests/test_olmoe.py`'s count with a share of the experts and the
    sandwich norms, whose `block_mlp_out` is kept: five gathers with a
    (T*k, d) result an expert layer, two of them from the buffer, and
    the replay holds the dispatch's alone."""
    from tests.test_olmoe import expert_buffer_gathers
    cfg = small(remat=True)
    gathers, scattered = expert_buffer_gathers(cfg, variables, tokens)
    layers = len(KINDS) - cfg.n_dense_layers
    t, rows = tokens.size, tokens.size * K
    assert sorted(gathers) == sorted(
        layers * [(False, t), (False, rows), (True, t), (True, t),
                  (True, rows)])
    assert scattered == {(t, E), (VOCAB, D)}


def test_holding_every_expert_is_the_path_that_was_there():
    """`experts_held == n_experts`, said or not, is OLMoE's dispatch to
    the bit: the same jaxpr, the same logits."""
    kw = dict(vocab_size=97, d_model=32, n_heads=4, n_layers=2, d_ff=16,
              max_len=24, n_experts=8, moe_top_k=2, dtype=jnp.float32)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 97, (2, 24)),
                       jnp.int32)
    models = [tfm.Transformer(tfm.olmoe_config(**kw, **held))
              for held in ({}, {"experts_held": 8})]
    params = meta.unbox(models[0].init(jax.random.PRNGKey(7), toks,
                                       train=False))
    assert set(params) == {"params"}      # no state beside the parameters
    outs = [m.apply(params, toks, train=True) for m in models]
    assert np.array_equal(np.asarray(outs[0]), np.asarray(outs[1]))
    text = [str(jax.make_jaxpr(lambda p, m=m: m.apply(p, toks, train=True))(
        params)) for m in models]
    assert text[0] == text[1]


def test_config_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="layer_types"):
        small(layer_types=("sliding", "window", "full", "full", "full"))
    with pytest.raises(ValueError, match="window"):
        small(window=0)
    with pytest.raises(ValueError, match="not among"):
        small(experts_held=8, experts_offset=12)
    with pytest.raises(ValueError, match="moe_score"):
        small(moe_score="tanh")


# -- the trainer -------------------------------------------------------------

STEP = re.compile(r"step (\d+): loss=(\S+) moe_dropped=(\S+) "
                  r"moe_held=(\S+) moe_max_load=(\S+) ")
SMALL_JOB = ["--vocab", "128", "--d-model", "32", "--n-heads", "4",
             "--n-layers", "5", "--d-ff", "48", "--seq-len", "64",
             "--arch", "afmoe", "--n-experts", "16", "--moe-top-k", "4",
             "--experts-held", "4", "--dense-layers", "1", "--window", "16",
             "--layer-types", "ssssf",
             "--fused-loss", "--remat", "on", "--epochs", "1",
             "--warmup-steps", "2", "--lr", "1e-2"]


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


def test_lm_train_arch_afmoe_logs_its_counters_and_resumes_its_bias(
        tmp_path):
    """Two steps and a checkpoint, then a second run from it: the start
    line, the counters on every step line, and the resumed run's first
    step equal to the first run's third (the bias came back: without it
    the routing, and so the loss, differ)."""
    ckpt = ["--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-sharded",
            "--ckpt-steps", "2", "--ckpt-sync"]
    out = lm_train(tmp_path, "--make-synthetic", "2", "--rows-per-file",
                   "8", *ckpt, *SMALL_JOB)
    assert out.returncode == 0, out.stderr[-3000:]
    assert ("afmoe: layers d|sssf, window 16, 4 q / 4 kv heads x 128, "
            "experts 0-3 of 16 held, top-4 sigmoid x 2.826, 1 shared") \
        in out.stderr
    assert "params=2392480" in out.stderr
    # float32 and attention without the flash call here: the two halves'
    # results of five layers, 4 x 64 x 32 x 4 B each
    assert ("remat keeps beside each block's input, of 4 x 64 tokens a "
            "step: flash_o 0 B, flash_lse 0 B, block_mixer_out 163840 B, "
            "block_mlp_out 163840 B, 327680 B in all") in out.stderr
    first = {int(s): tuple(map(float, rest))
             for s, *rest in STEP.findall(out.stderr)}
    assert sorted(first) == [1, 2, 3, 4]
    for loss, dropped, held, load in first.values():
        assert np.isfinite(loss) and dropped == 0.0
        assert 0.0 < held < 1.0 <= load <= 16 / 4
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
    (["--arch", "afmoe", "--moe-dispatch", "flat"], "no exchange"),
    (["--arch", "afmoe", "--moe-compress", "int8"], "no exchange"),
    (["--arch", "afmoe", "--layer-types", "sma"], "s (sliding) or f (full)"),
    (["--arch", "olmoe", "--experts-held", "4"], "only --arch afmoe"),
    (["--arch", "gpt2", "--window", "64"], "only --arch afmoe"),
    (["--arch", "granite-hybrid", "--dense-layers", "1"],
     "only --arch afmoe"),
])
def test_lm_train_refuses_flags_that_contradict_the_arch(tmp_path, flags,
                                                         message):
    """In this process: every refusal comes before the model is built."""
    from edl_tpu.examples.lm_train import main
    (tmp_path / "train-0000.npz").write_bytes(b"")
    with pytest.raises(SystemExit, match=re.escape(message)):
        main(["--data-dir", str(tmp_path), *flags])


def test_lm_train_arch_afmoe_refuses_several_devices(tmp_path):
    from edl_tpu.examples.lm_train import main
    (tmp_path / "train-0000.npz").write_bytes(b"")
    assert jax.device_count() > 1
    with pytest.raises(SystemExit, match="no exchange between chips"):
        main(["--data-dir", str(tmp_path), "--arch", "afmoe", "--batch-size",
              str(jax.device_count())])


# -- what the tolerance refuses ----------------------------------------------

@pytest.mark.parametrize("what, changed", [
    ("bf16", {"dtype": jnp.bfloat16}),
    ("no_gate", {"attn_gate": False}),
    ("window_halved", {"window": WINDOW // 2}),
    ("rope_on_the_global_layer", {"layer_types": KINDS[:-1] + ("attention",)}),
    ("no_shared_expert", {"moe_shared": 0}),
    ("route_scale_1", {"moe_route_scale": 1.0}),
    ("two_norms_a_block", {"sandwich_norm": False}),
    ("no_embedding_multiplier", {"embed_scale": 1.0}),
])
def test_the_tolerance_refuses(variables, tokens, what, changed):
    """What `TOL` must not let through moves the logits by far more."""
    mine = program_logits(variables, tokens)
    other = program_logits(variables, tokens, **changed)
    moved = float(jnp.abs(mine - other.astype(jnp.float32)).max())
    assert moved > 50 * TOL, (what, moved)


def test_the_bias_added_to_the_gates_is_refused(variables, tokens,
                                                monkeypatch):
    """The reference with the bias in its gates, which the published
    rule forbids, is far from the program."""
    def biased(x, p, hp):
        scores = jax.nn.sigmoid(x @ p["router"]) + p["bias"]
        _, idx = jax.lax.top_k(scores, hp["top_k"])
        return plain.gates(scores, idx, hp), idx, scores
    monkeypatch.setattr(plain, "route", biased)
    moved = float(jnp.abs(program_logits(variables, tokens)
                          - plain_logits(variables, tokens)).max())
    assert moved > 50 * TOL
