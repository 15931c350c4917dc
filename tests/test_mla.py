"""The JoyAI-LLM-Flash block (`models.transformer.joyai_config`: latent
attention with keys of nope + rope and values of their own size, rotary
positions by interleaved pairs on a part of the head with one key for
all heads, sigmoid routing over score + bias with a shared expert, a
chip's share of the experts, one multi-token-prediction module trained
through a second loss on the same head) against the plain reference
`benchmark/reference/joyai_plain.py`, at a small size on the CPU with
seeded random weights: rope by pairs, the mixer alone and piece by
piece, the whole model's loss, `mtp_loss` and every gradient leaf
through both losses, what the module's loss reaches, the bias's rule and
a checkpoint's round trip, the shares of an expert layer adding up to
the uncut one, and the trainer end to end.

Tolerances. Program and reference both compute in float32 here and
differ in the order of their sums: losses differ by 1e-6, gradient
leaves by 2e-6 of their size. `TOL` = 4e-5 on logits and 2e-4 relative
on a gradient leaf are tens of times that; the controls show what they
refuse.
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

from benchmark.reference import joyai_plain as plain
from benchmark.reference.check_granite_hybrid import leaf_errors
from edl_tpu.models import mla
from edl_tpu.models import transformer as tfm
from edl_tpu.ops.flash_attention import force_interpret_kernels
from edl_tpu.train.state import TrainState, TrainStatus
from edl_tpu.train.step import make_train_step

TOL = 4e-5
VOCAB, SEQ, D, HEADS, FF = 96, 128, 32, 4, 48
Q_RANK, KV_RANK, NOPE, ROPE, VDIM = 24, 16, 16, 8, 12
E, K, HELD, FIRST, EFF, LAYERS = 16, 4, 4, 4, 16, 3
HP = {"n_head": HEADS, "eps": 1e-6, "theta": 32e6, "nope": NOPE,
      "rope": ROPE, "kv_rank": KV_RANK, "top_k": K, "route_scale": 2.5,
      "first_expert": FIRST, "mtp_weight": 0.3}


def small(**changed):
    return dataclasses.replace(tfm.joyai_config(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
        d_ff=FF, max_len=SEQ, n_dense_layers=1, moe_d_ff=EFF, n_experts=E,
        moe_top_k=K, experts_held=HELD, experts_offset=FIRST,
        q_lora_rank=Q_RANK, kv_lora_rank=KV_RANK, qk_nope_head_dim=NOPE,
        qk_rope_head_dim=ROPE, v_head_dim=VDIM, dtype=jnp.float32),
        **changed)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(11).integers(
        0, VOCAB, (2, SEQ)), jnp.int32)


@pytest.fixture(scope="module")
def variables(tokens):
    """Seeded parameters and biases; the norms' scales drawn too (all
    ones would hide a norm that forgets its scale), the routers wider
    than their init (decisive routing), the biases drawn."""
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


def plain_params(variables):
    return plain.from_program(variables["params"], variables["batch_stats"])


def state_of(variables, tx=None, **kw):
    return TrainState.create(
        apply_fn=tfm.Transformer(small(**kw)).apply,
        params=variables["params"], tx=tx or optax.sgd(0.1),
        batch_stats=variables["batch_stats"])


# -- rotary positions by interleaved pairs -----------------------------------

@pytest.mark.parametrize("shape", [(2, 64, 4, 8), (1, 32, 1, 64),
                                   (1, 16, 3, 2)], ids=str)
def test_rope_by_pairs_is_the_complex_product(shape):
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    s, d = shape[1], shape[-1]
    z = np.asarray(x, np.float64)
    z = z[..., 0::2] + 1j * z[..., 1::2]
    angle = np.arange(s)[:, None] * 32e6 ** (-np.arange(0, d, 2) / d)
    z = z * np.exp(1j * angle)[None, :, None, :]
    want = np.stack([z.real, z.imag], -1).reshape(shape)
    np.testing.assert_allclose(mla.rope_pairs(x, 32e6), want, atol=1e-5)
    # the reference's own, a sequence at a time
    np.testing.assert_allclose(plain.rope(x[0], 32e6), want[0], atol=1e-5)


def test_rope_by_pairs_is_rotate_half_on_the_deinterleaved_head():
    """De-interleave, rotate halves (`transformer.rope`), interleave
    again: the same rotation; and applied to q and k alike the two
    conventions give the same scores."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 4, 8))
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 4, 8))

    def halves(x):
        return jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    np.testing.assert_allclose(halves(mla.rope_pairs(x, 1e4)),
                               tfm.rope(halves(x), 1e4), atol=1e-5)
    by_pairs = jnp.einsum("bqhd,bkhd->bhqk", mla.rope_pairs(x, 1e4),
                          mla.rope_pairs(y, 1e4))
    by_halves = jnp.einsum("bqhd,bkhd->bhqk", tfm.rope(halves(x), 1e4),
                           tfm.rope(halves(y), 1e4))
    np.testing.assert_allclose(by_pairs, by_halves, atol=2e-5)
    # and pairs on q against halves on k is another function
    mixed = jnp.einsum("bqhd,bkhd->bhqk", mla.rope_pairs(x, 1e4),
                       tfm.rope(y, 1e4))
    assert float(jnp.abs(mixed - by_pairs).max()) > 0.1


def test_rope_by_pairs_takes_positions_and_keeps_the_type():
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 2, 8), jnp.bfloat16)
    at = jnp.arange(8) + 100
    assert mla.rope_pairs(x, 1e4, at).dtype == jnp.bfloat16
    far = mla.rope_pairs(jnp.tile(x.astype(jnp.float32), (1, 16, 1, 1)),
                         1e4)
    wide = jnp.tile(x.astype(jnp.float32), (1, 16, 1, 1))
    np.testing.assert_allclose(
        mla.rope_pairs(wide[:, 100:108], 1e4, at), far[:, 100:108],
        atol=1e-5)


# -- the mixer alone ---------------------------------------------------------

def mixer_alone(variables, x, **kw):
    return mla.LatentAttention(small(**kw)).apply(
        {"params": variables["params"]["block1"]["attn"]}, x)


def plain_mixer(variables, x, hp=HP):
    p = plain_params(variables)["blocks"][1]["attn"]
    with jax.default_matmul_precision("highest"):
        return jnp.stack([plain.mla(row, p, hp) for row in x])


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, D))


def test_the_mixer_matches_the_reference(variables, x):
    np.testing.assert_allclose(mixer_alone(variables, x),
                               plain_mixer(variables, x), atol=TOL)


def test_the_mixer_through_the_flash_kernels(variables, x):
    """attention="flash" under the interpret hook: the three Mosaic
    kernels at keys of 24 and values of 12, forward and gradient."""
    want = plain_mixer(variables, x)
    with force_interpret_kernels():
        got = mixer_alone(variables, x, attention="flash")
        g = jax.grad(lambda x: jnp.sum(jnp.sin(mixer_alone(
            variables, x, attention="flash"))))(x)
    np.testing.assert_allclose(got, want, atol=TOL)
    w = jax.grad(lambda x: jnp.sum(jnp.sin(mixer_alone(variables, x))))(x)
    np.testing.assert_allclose(g, w, atol=10 * TOL)


def _rope_on_the_whole_head(q_nope, q_pe, k_nope, k_pe, hp):
    turn = lambda t: plain.rope(t, hp["theta"])  # noqa: E731
    return turn(q_nope), turn(q_pe), turn(k_nope), turn(k_pe)


def _halves_on_k(q_nope, q_pe, k_nope, k_pe, hp):
    """Pairs on q; rotate-half, as the key lies, on k."""
    return (q_nope, plain.rope(q_pe, hp["theta"]), k_nope,
            tfm.rope(k_pe[None, :, None, :], hp["theta"])[0, :, 0])


def _k_pe_normed(q_nope, q_pe, k_nope, k_pe, hp):
    return (q_nope, plain.rope(q_pe, hp["theta"]), k_nope,
            plain.rope(plain.rms(k_pe, 1.0, hp["eps"]), hp["theta"]))


@pytest.mark.parametrize("piece, seam, fault", [
    ("rope_on_the_whole_head", "turned", _rope_on_the_whole_head),
    ("pairs_on_q_halves_on_k", "turned", _halves_on_k),
    ("k_pe_normed", "turned", _k_pe_normed),
    ("the_scale_from_the_value_size", "softmax_scale",
     lambda hp: VDIM ** -0.5),
    ("the_latent_norms_left_out", "rms", lambda x, w, eps: x),
])
def test_each_piece_of_the_mixer_counts(variables, x, piece, seam, fault,
                                        monkeypatch):
    """The reference with one piece wrong is far from the program."""
    monkeypatch.setattr(plain, seam, fault)
    p = plain_params(variables)["blocks"][1]["attn"]
    with jax.default_matmul_precision("highest"):
        other = jnp.stack([plain.mla(row, p, HP) for row in x])
    moved = float(jnp.abs(mixer_alone(variables, x) - other).max())
    assert moved > 50 * TOL, (piece, moved)


def test_the_mixers_parameters_are_the_sources(variables):
    a = variables["params"]["block1"]["attn"]
    assert {k: tuple(np.shape(v.get("kernel", v.get("scale"))))
            for k, v in a.items()} == {
        "q_a": (D, Q_RANK), "q_a_norm": (Q_RANK,),
        "q_b": (Q_RANK, HEADS, NOPE + ROPE),
        "kv_a": (D, KV_RANK + ROPE), "kv_a_norm": (KV_RANK,),
        "kv_b": (KV_RANK, HEADS, NOPE + VDIM),
        "out": (HEADS, VDIM, D)}


def test_the_published_sizes_count_the_configurations_parameters():
    """The cell's configuration by the program's own builder: what
    benchmark/configs/joyai-llm-flash-d5-e16v8.json says it holds."""
    cfg = tfm.joyai_config(vocab_size=16160, n_layers=5, experts_held=16,
                           max_len=8192)
    shapes = jax.eval_shape(lambda: tfm.Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32),
        train=False))
    sizes = {k: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(v))
             for k, v in meta.unbox(shapes["params"]).items()}
    assert sizes["block0"] == 70_391_808
    assert sizes["block1"] == sizes["block4"] == 107_091_968
    assert sizes["mtp"] == 115_486_720
    assert sizes["tok_embed"] == sizes["lm_head"] == 33_095_680
    assert sum(sizes.values()) == 680_439_808
    attn = meta.unbox(shapes["params"])["block1"]["attn"]
    assert sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(attn)) == 26_347_520
    assert cfg.head_dim == 192 and cfg.value_head_dim == 128
    assert (cfg.rope_theta, cfg.norm_eps, cfg.moe_route_scale) == (
        32e6, 1e-6, 2.5)


# -- the whole model ---------------------------------------------------------

def plain_losses(variables, tokens, hp=HP):
    main, ahead, routed = plain.batch_losses(plain_params(variables),
                                             np.asarray(tokens), hp)
    return (float(np.mean(np.concatenate(main))),
            float(np.mean(np.concatenate(ahead))), routed)


@pytest.mark.parametrize("loss", [tfm.lm_loss_fn, tfm.lm_loss_fused],
                         ids=["dense", "fused"])
def test_loss_mtp_loss_and_every_gradient_leaf_match_the_reference(
        variables, tokens, loss):
    state = state_of(variables)
    (value, metrics), grads = jax.value_and_grad(
        lambda p: loss(state, p, {"tokens": tokens}), has_aux=True)(
        state.params)
    main, ahead, _ = plain_losses(variables, tokens)
    assert float(metrics["mtp_loss"]) == pytest.approx(ahead, abs=TOL)
    assert float(value) == pytest.approx(main + 0.3 * ahead, abs=TOL)
    assert float(metrics["ppl"]) == pytest.approx(np.exp(main), rel=1e-4)
    assert float(metrics["moe_dropped"]) == 0.0
    want = plain.batch_grads(plain_params(variables), np.asarray(tokens), HP)
    got = plain.from_program(jax.tree.map(np.asarray, grads))
    for b in (*got["blocks"], got["mtp"]["block"]):
        b.pop("bias", None)
    for name, err, size, _ in leaf_errors(got, want):
        assert size > 0, name
        assert err / size < 2e-4, (name, err / size)


def test_the_two_losses_agree(variables, tokens):
    state = state_of(variables)
    a, ma = tfm.lm_loss_fn(state, state.params, {"tokens": tokens})
    b, mb = tfm.lm_loss_fused(state, state.params, {"tokens": tokens})
    assert float(a) == pytest.approx(float(b), abs=1e-5)
    assert float(ma["mtp_loss"]) == pytest.approx(float(mb["mtp_loss"]),
                                                  abs=1e-5)


def test_the_default_call_leaves_the_module_out(variables, tokens):
    model = tfm.Transformer(small())
    logits = model.apply(variables, tokens, train=False)
    assert logits.shape == (2, SEQ, VOCAB)
    both = model.apply(variables, tokens, train=False, mtp=True)
    np.testing.assert_array_equal(both[0], logits)
    assert both[1].shape == logits.shape
    hidden = model.apply(variables, tokens, train=False, mtp=True,
                         return_hidden=True)
    assert [h.shape for h in hidden] == [(2, SEQ, D)] * 2
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([plain.forward(plain_params(variables), row, HP)[1]
                          for row in tokens])
    # the last place of a row is fed the row's first token: both sides
    np.testing.assert_allclose(both[1], want, atol=TOL)


def test_the_modules_loss_trains_the_main_blocks(variables, tokens):
    """Nothing is detached: with the main loss's weight out of the
    picture (the gradient of `mtp_loss` alone) the main blocks, the
    embedding and the head all get a gradient; cut at h, they get none
    but through the embedding."""
    state = state_of(variables)
    g = jax.grad(lambda p: tfm.lm_loss_fused(
        state, p, {"tokens": tokens})[1]["mtp_loss"])(state.params)
    for name in ("block0", "block2", "tok_embed", "lm_head", "mtp",
                 "ln_final"):
        assert float(optax.global_norm(g[name])) > 1e-4, name


@pytest.mark.parametrize("fault, changed", [
    ("lambda_0", {"mtp_weight": 0.0}),
    ("no_shared_expert", {"moe_shared": 0}),
    ("route_scale_1", {"moe_route_scale": 1.0}),
])
def test_the_tolerance_refuses(variables, tokens, fault, changed):
    main, ahead, _ = plain_losses(variables, tokens)
    state = state_of(variables, **changed)
    value, _ = tfm.lm_loss_fused(state, state.params, {"tokens": tokens})
    assert abs(float(value) - (main + 0.3 * ahead)) > 50 * TOL, fault


@pytest.mark.parametrize("fault", ["fed_t_i", "target_off_by_one",
                                   "embedding_second"])
def test_the_modules_wiring_is_refused(variables, tokens, fault,
                                       monkeypatch):
    """The reference with the module wired wrongly is far from the
    program's `mtp_loss`."""
    state = state_of(variables)
    mine = float(tfm.lm_loss_fused(state, state.params,
                                   {"tokens": tokens})[1]["mtp_loss"])
    if fault == "fed_t_i":
        monkeypatch.setattr(plain, "next_tokens", lambda t: t)
    elif fault == "target_off_by_one":
        monkeypatch.setattr(plain, "ahead_pairs",
                            lambda ahead, t: (ahead[:-2], t[1:-1]))
    else:
        cat = plain.jnp.concatenate
        monkeypatch.setattr(
            plain.jnp, "concatenate", lambda parts, axis=0: cat(
                parts[::-1] if axis == -1 and len(parts) == 2
                and parts[0].shape == parts[1].shape == (SEQ, D)
                else parts, axis))
    plain._losses_of.cache_clear()
    try:
        _, ahead, _ = plain.batch_losses(plain_params(variables),
                                         np.asarray(tokens), HP)
    finally:
        plain._losses_of.cache_clear()
    assert abs(float(np.mean(np.concatenate(ahead))) - mine) > 50 * TOL


def test_remat_changes_no_gradient(variables, tokens):
    def grads(**kw):
        state = state_of(variables, **kw)
        return jax.grad(lambda p: tfm.lm_loss_fused(
            state, p, {"tokens": tokens})[0])(state.params)
    for a, b in zip(jax.tree.leaves(grads()),
                    jax.tree.leaves(grads(remat=True))):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)


def test_kept_bytes_counts_o_at_the_value_size():
    cfg = dataclasses.replace(small(dtype=jnp.bfloat16), attention="flash")
    kept = tfm.kept_bytes(cfg, 2, SEQ)
    layers = LAYERS + 1  # the module's block among them
    assert kept[tfm.KEPT_O] == layers * 2 * SEQ * HEADS * VDIM * 2
    assert kept[tfm.KEPT_LSE] == layers * 2 * SEQ * HEADS * 4
    assert kept[tfm.KEPT_MIXER_OUT] == layers * 2 * SEQ * D * 2
    assert kept[tfm.KEPT_MLP_OUT] == 0
    # and a model with one head size still counts it at that size
    plain_cfg = tfm.TransformerConfig(d_model=64, n_heads=4, n_layers=2,
                                      attention="flash")
    assert tfm.kept_bytes(plain_cfg, 1, 128)[tfm.KEPT_O] == 2 * 128 * 64 * 2


# -- the bias ----------------------------------------------------------------

def test_the_bias_moves_by_the_papers_rule_up_to_a_constant(variables,
                                                            tokens):
    """One train step: every expert layer's bias, the module's too,
    moves by DeepSeek-V3's rule on the step's own counts of all E
    experts (the reference's), less the mean of the move, which changes
    no top-k."""
    state = state_of(variables)
    step = make_train_step(tfm.lm_loss_fused, donate=False)
    after, metrics = step(state, {"tokens": tokens})
    _, _, routed = plain_losses(variables, tokens)
    where = [("block1",), ("block2",), ("mtp", "block")]
    for at, path in enumerate(where):
        counts = sum(np.bincount(row[at].ravel(), minlength=E)
                     for row in routed)
        assert counts.sum() == tokens.size * K

        def bias(tree):
            for name in path:
                tree = tree[name]
            return np.asarray(tree["moe_mlp"]["expert_bias"])
        before, got = bias(variables["batch_stats"]), bias(after.batch_stats)
        move = np.asarray(plain.bias_after(
            jnp.asarray(before), jnp.asarray(counts, jnp.float32),
            0.001)) - before
        np.testing.assert_allclose(got - before, move - move.mean(),
                                   atol=1e-7)
        assert np.abs(move).max() == pytest.approx(0.001, rel=1e-4)
    assert float(metrics["moe_dropped"]) == 0.0
    assert 0.0 < float(metrics["moe_held"]) < 1.0


@pytest.mark.parametrize("sharded", [False, True])
def test_checkpoint_carries_the_tiny_model_and_its_bias(
        tmp_path, variables, tokens, sharded):
    from edl_tpu.train.checkpoint import CheckpointManager
    state = state_of(variables, tx=optax.adamw(1e-2))
    step = make_train_step(tfm.lm_loss_fused, donate=False)
    state, _ = step(state, {"tokens": tokens})
    manager = CheckpointManager(str(tmp_path), sharded=sharded)
    manager.save(state, TrainStatus(epoch=0, step=1))
    fresh = state_of(jax.tree.map(jnp.zeros_like, variables),
                     tx=optax.adamw(1e-2))
    restored, status = manager.restore(fresh)
    assert status.step == 1
    mine = jax.tree_util.tree_flatten_with_path(state)[0]
    back = jax.tree_util.tree_flatten_with_path(restored)[0]
    assert [p for p, _ in mine] == [p for p, _ in back]
    names = [jax.tree_util.keystr(p) for p, _ in mine]
    assert any("mtp" in n and "expert_bias" in n for n in names)
    assert any("kv_a_norm" in n for n in names)
    for (path, a), (_, b) in zip(mine, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
    on, m1 = step(state, {"tokens": tokens})
    resumed, m2 = step(restored, {"tokens": tokens})
    assert float(m1["loss"]) == float(m2["loss"])
    for a, b in zip(jax.tree.leaves(on.batch_stats),
                    jax.tree.leaves(resumed.batch_stats)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the share, tied to the model --------------------------------------------

def whole_block(seed=9):
    """An expert block that holds all E experts, and an input."""
    cfg = small(experts_held=0, experts_offset=0)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, SEQ, D))
    v = meta.unbox(tfm.Block(cfg).init(jax.random.PRNGKey(seed + 1), x))
    v["params"]["moe_mlp"]["router"] = v["params"]["moe_mlp"]["router"] * 20
    v["batch_stats"] = jax.tree.map(
        lambda b: 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 2),
                                           b.shape), v["batch_stats"])
    return cfg, v, x


def share_of(v, first, held):
    moe = {k: (t[first:first + held] if k.startswith("w_") else t)
           for k, t in v["params"]["moe_mlp"].items()}
    return {"params": {**v["params"], "moe_mlp": moe},
            "batch_stats": v["batch_stats"]}


@pytest.mark.parametrize("shares", [4, 16])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_reference(
        shares):
    """The guide's tie of the share to the model: a block's output is
    a + F(a); the routed parts of all the shares' F, the shared expert
    counted once, are the uncut reference's F."""
    cfg, v, x = whole_block()
    held = E // shares

    def mlp_of(cfg, v):
        """F(rms(a)) of the block: its output less the residual stream
        a = x + mixer, which every share computes alike."""
        y = tfm.Block(cfg).apply(v, x)
        no_mlp = jax.tree.map(jnp.zeros_like, v["params"]["moe_mlp"])
        a = tfm.Block(cfg).apply(
            {"params": {**v["params"], "moe_mlp": no_mlp},
             "batch_stats": v["batch_stats"]}, x)
        return y - a, a
    whole, a = mlp_of(cfg, v)
    m = v["params"]["moe_mlp"]
    shared = {k: m[f"shared_{k}"]["kernel"] for k in ("gate", "up", "down")}
    p = {"norm_2": v["params"]["ln_mlp"]["scale"], "router": m["router"],
         "bias": v["batch_stats"]["moe_mlp"]["expert_bias"],
         "shared": shared,
         "experts": {k: m[f"w_{k}"] for k in ("gate", "up", "down")}}
    hp = {**HP, "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([plain.feed_forward(row, p, hp)[0] for row in a])
        once = jnp.stack([plain.swiglu(plain.rms(row, p["norm_2"], 1e-6),
                                       shared) for row in a])
    np.testing.assert_allclose(whole, want, atol=TOL)
    parts = [mlp_of(small(experts_held=held, experts_offset=i * held),
                    share_of(v, i * held, held))[0] - once
             for i in range(shares)]
    np.testing.assert_allclose(sum(parts) + once, want, atol=TOL)
    sizes = [float(jnp.abs(part).max()) for part in parts]
    assert min(sizes) > 100 * TOL
    assert float(jnp.abs(parts[0] - (want - once)).max()) > 100 * TOL


def test_config_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="latent attention"):
        small(q_lora_rank=0)
    with pytest.raises(ValueError, match="latent attention"):
        small(qk_rope_head_dim=7)
    with pytest.raises(ValueError, match="latent attention"):
        small(n_kv_heads=2)
    with pytest.raises(ValueError, match="mtp_layers"):
        small(mtp_layers=2)
    with pytest.raises(ValueError, match="mtp_layers"):
        small(tie_embeddings=True)


# -- the trainer -------------------------------------------------------------

STEP = re.compile(r"step (\d+): loss=(\S+) moe_dropped=(\S+) "
                  r"moe_held=(\S+) moe_max_load=(\S+) mtp_loss=(\S+) ")
SMALL_JOB = ["--vocab", "128", "--d-model", "32", "--n-heads", "2",
             "--n-layers", "2", "--d-ff", "48", "--seq-len", "64",
             "--arch", "joyai", "--n-experts", "16", "--moe-top-k", "4",
             "--experts-held", "4", "--dense-layers", "1",
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


def test_lm_train_arch_joyai_logs_both_losses_and_resumes(tmp_path):
    """Two steps and a checkpoint, then a second run from it: the start
    line, both losses and the counters on every step line, and the
    resumed run's steps equal to the first run's (parameters, moments
    and every bias, the module's too, came back)."""
    ckpt = ["--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-sharded",
            "--ckpt-steps", "2", "--ckpt-sync"]
    out = lm_train(tmp_path, "--make-synthetic", "2", "--rows-per-file",
                   "8", *ckpt, *SMALL_JOB)
    assert out.returncode == 0, out.stderr[-3000:]
    assert ("joyai: 1 dense + 1 expert layers + 1 mtp, mla q 1536 / kv "
            "512, 2 heads x (128 + 64 | 128), experts 0-3 of 16 held, "
            "top-4 sigmoid x 2.5, 1 shared, mtp x 0.3") in out.stderr
    first = {int(s): tuple(map(float, rest))
             for s, *rest in STEP.findall(out.stderr)}
    assert sorted(first) == [1, 2, 3, 4]
    for loss, dropped, held, load, ahead in first.values():
        assert np.isfinite(loss) and np.isfinite(ahead) and dropped == 0.0
        assert 0.0 < held < 1.0 <= load <= 16 / 4
        assert loss > ahead * 0.3
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
    (["--arch", "joyai", "--moe-dispatch", "flat"], "no exchange"),
    (["--arch", "joyai", "--window", "64"], "only --arch afmoe has"),
    (["--arch", "joyai", "--block-length", "4"], "only --arch sdar"),
    (["--arch", "olmoe", "--experts-held", "4"],
     "only --arch afmoe, sdar, joyai has such a size"),
    (["--arch", "sdar", "--dense-layers", "1"],
     "only --arch afmoe, joyai has such a size"),
    (["--arch", "gpt2", "--experts-held", "2"], "--arch gpt2 conflicts"),
])
def test_lm_train_refusals_name_the_archs_that_take_a_size(tmp_path, flags,
                                                           message):
    from edl_tpu.examples.lm_train import main
    (tmp_path / "train-0000.npz").write_bytes(b"")
    with pytest.raises(SystemExit, match=re.escape(message)):
        main(["--data-dir", str(tmp_path), *flags])


def test_lm_train_arch_joyai_refuses_several_devices(tmp_path):
    from edl_tpu.examples.lm_train import main
    (tmp_path / "train-0000.npz").write_bytes(b"")
    assert jax.device_count() > 1
    with pytest.raises(SystemExit, match="no exchange between chips"):
        main(["--data-dir", str(tmp_path), "--arch", "joyai", "--batch-size",
              str(jax.device_count())])
