"""The OLMoE block (`models.transformer.olmoe_config`) against the plain
reference `benchmark/reference/olmoe_plain.py`, at a small size on the
CPU with seeded random weights: logits, the training loss and the
gradient of every leaf, routing, RoPE and qk-norm by themselves, the
streamed CE with the routers' terms against the dense-logits loss, the
trainer end to end with a sharded save and a restore, and the default
configuration pinned as it was before the block learned new kinds.

Tolerances. Program and reference both compute in float32 here and
differ only in the order of their sums (the program sorts tokens by
expert and sums a token's k expert outputs last; the reference loops
over the experts): differences read 1e-6 to 4e-6 on logits of size 1.
`TOL` = 2e-5 is five times that. The controls at the end show what it
refuses: bfloat16 activations move the logits by 1e-2, renormalised
gates by 2e-1, one dropped assignment by 1e-2, the interleaved rotary
convention by 1e-1.
"""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import traverse_util
from flax.core import meta

from benchmark.reference import olmoe_plain as plain
from edl_tpu.models import transformer as tfm
from edl_tpu.train.state import TrainState

TOL = 2e-5
VOCAB, SEQ, D, HEADS, FF, E, K, LAYERS = 96, 24, 32, 2, 16, 8, 2, 2
HP = {"n_head": HEADS, "eps": 1e-5, "theta": 10000.0, "top_k": K,
      "aux_coef": 0.01, "z_coef": 0.001}


def small(**changed):
    return dataclasses.replace(tfm.olmoe_config(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=LAYERS,
        d_ff=FF, max_len=SEQ, n_experts=E, moe_top_k=K,
        dtype=jnp.float32), **changed)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(11).integers(
        0, VOCAB, (3, SEQ)), jnp.int32)


@pytest.fixture(scope="module")
def tree(tokens):
    """Seeded parameters; the norms' scales drawn too (all ones would
    hide a norm that forgets its scale)."""
    params = meta.unbox(tfm.Transformer(small()).init(
        jax.random.PRNGKey(5), tokens, train=False))["params"]
    flat = traverse_util.flatten_dict(params)
    rng = np.random.default_rng(17)
    for path, leaf in flat.items():
        if path[-1] == "scale":
            flat[path] = jnp.asarray(
                rng.uniform(0.5, 1.5, leaf.shape), jnp.float32)
        if path[-1] == "router":  # wider than init: decisive routing
            flat[path] = leaf * 20.0
    return traverse_util.unflatten_dict(flat)


def program_logits(tree, tokens, **kw):
    return tfm.Transformer(small(**kw)).apply({"params": tree}, tokens,
                                              train=True)


def plain_logits(tree, tokens):
    params = plain.from_program(tree)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([plain.forward(params, row, HP)[0]
                          for row in tokens])


def state_of(tree, **kw):
    return TrainState.create(apply_fn=tfm.Transformer(small(**kw)).apply,
                             params=tree, tx=optax.sgd(0.1))


def test_parameter_tree_is_the_sources():
    shapes = jax.eval_shape(lambda: meta.unbox(tfm.Transformer(
        tfm.olmoe_config(n_layers=1)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
            train=False))["params"])
    flat = traverse_util.flatten_dict(shapes, sep="/")
    assert sum(int(np.prod(v.shape)) for v in flat.values()) == 625_616_896
    layer = {k: v.shape for k, v in flat.items() if k.startswith("block0/")}
    assert sum(int(np.prod(s)) for s in layer.values()) == 419_569_664
    assert {k: s for k, s in layer.items() if "moe_mlp" in k} == {
        "block0/moe_mlp/router": (2048, 64),
        "block0/moe_mlp/w_gate": (64, 2048, 1024),
        "block0/moe_mlp/w_up": (64, 2048, 1024),
        "block0/moe_mlp/w_down": (64, 1024, 2048)}
    assert "pos_embed" not in flat and "block0/attn/q_norm/scale" in flat


def test_expert_tables_carry_the_expert_axis():
    from edl_tpu.parallel.sharding import logical_to_spec
    boxed = jax.eval_shape(lambda: tfm.Transformer(small()).init(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32),
        train=False))["params"]["block0"]["moe_mlp"]
    assert boxed["w_gate"].names == boxed["w_up"].names \
        == ("expert", "embed", "mlp")
    assert boxed["w_down"].names == ("expert", "mlp", "embed")
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("ep",))
    assert logical_to_spec(boxed["w_down"].names, mesh=mesh) == P("ep")


def test_logits_match_the_reference(tree, tokens):
    diff = jnp.abs(program_logits(tree, tokens) - plain_logits(tree, tokens))
    assert float(diff.max()) < TOL


def test_loss_and_every_gradient_leaf_match_the_reference(tree, tokens):
    cfg = small()

    def program_loss(p):
        return tfm.lm_loss_fn(state_of(tree), p, {"tokens": tokens},
                               aux_weight=cfg.moe_aux_weight,
                               z_weight=cfg.moe_z_weight)[0]
    loss, grads = jax.value_and_grad(program_loss)(tree)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(plain.train_loss)(
            plain.from_program(tree), tokens, HP)
    assert abs(float(loss) - float(ref_loss)) < TOL
    # `from_program` is a renaming and reshaping: it maps gradients too
    def named(t):
        return {jax.tree_util.keystr(path): leaf for path, leaf in
                jax.tree_util.tree_flatten_with_path(t)[0]}
    mine, theirs = named(plain.from_program(grads)), named(ref_grads)
    assert set(mine) == set(theirs) and len(mine) == len(
        jax.tree.leaves(tree))
    for name in mine:
        scale = max(1e-3, float(jnp.abs(theirs[name]).max()))
        err = float(jnp.abs(mine[name] - theirs[name]).max())
        assert err < 5e-4 * scale, (name, err, scale)


def test_the_loss_is_the_sources_three_terms(tree, tokens):
    """CE + 0.01 x the source's pooled load-balance (top_k at perfect
    balance) + 0.001 x z, by the step line's own counters."""
    cfg = small()
    loss, m = tfm.lm_loss_fn(state_of(tree), tree, {"tokens": tokens},
                              aux_weight=cfg.moe_aux_weight,
                              z_weight=cfg.moe_z_weight)
    with jax.default_matmul_precision("highest"):
        ref = plain.pool([plain.sequence_stats(plain.from_program(tree),
                                               row, HP) for row in tokens],
                         HP)
    assert abs(float(jnp.log(m["ppl"])) - float(ref["ce"])) < TOL
    assert abs(K * float(m["moe_balance"]) - float(ref["balance"])) < TOL
    assert abs(float(m["moe_z"]) - float(ref["z"])) < 1e-4
    assert abs(float(loss) - float(ref["loss"])) < TOL
    assert float(m["moe_dropped"]) == 0.0
    assert 1.0 <= float(m["moe_max_load"]) <= E / K


def moe_layer(tree, x, **kw):
    out, sown = tfm.MoEMLP(small(**kw)).apply(
        {"params": tree["block0"]["moe_mlp"]}, x, mutable=["intermediates"])
    return out, sown["intermediates"]


def test_routing_matches_token_by_token(tree):
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, D))
    p = plain.from_program(tree)["blocks"][0]
    w, idx, _, _ = plain.route(x.reshape(-1, D), p["router"], K)
    # not renormalised: a token's weights sum to less than 1
    assert float(w.sum(-1).max()) < 1.0
    with jax.default_matmul_precision("highest"):
        ref = plain.experts(x.reshape(-1, D), w, idx, p["experts"])
    out, sown = moe_layer(tree, x)
    assert float(jnp.abs(out.reshape(-1, D) - ref).max()) < TOL
    counts = np.bincount(np.asarray(idx).ravel(), minlength=E)
    np.testing.assert_allclose(np.asarray(sown["moe_frac"][0]),
                               counts / counts.sum(), atol=1e-7)


def test_nothing_is_dropped_when_one_expert_gets_every_token(tree):
    """Every token's first choice is expert 3: it holds T of the T*k
    assignments, E/k times the mean, and every one is computed."""
    params = jax.tree.map(lambda a: a, tree)
    router = np.asarray(params["block0"]["moe_mlp"]["router"]).copy()
    router[:, 3] = 50.0
    params["block0"]["moe_mlp"]["router"] = jnp.asarray(router)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, D)))
    p = plain.from_program(params)["blocks"][0]
    w, idx, _, _ = plain.route(x.reshape(-1, D), p["router"], K)
    assert bool((idx[:, 0] == 3).all())
    with jax.default_matmul_precision("highest"):
        ref = plain.experts(x.reshape(-1, D), w, idx, p["experts"])
    out, sown = moe_layer(params, x)
    assert float(jnp.abs(out.reshape(-1, D) - ref).max()) < TOL
    assert float(sown["moe_dropped"][0]) == 0.0
    assert float(sown["moe_frac"][0][3]) == pytest.approx(1.0 / K)


def test_dispatch_backward_is_gathers_only(tree):
    """No scatter-add over repeated rows on the dropless path, forward
    or backward: the permutations are inverted by gathers."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, D))
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, x: moe_layer({"block0": {"moe_mlp": p}}, x)[0].sum(),
        argnums=(0, 1)))(tree["block0"]["moe_mlp"], x))
    # top_k's own backward puts a token's k gate gradients among its E
    # probabilities (unique places, (T, E)); no other scatter-add
    scattered = set(re.findall(r"(\w+\[[\d,]+\]) = scatter-add", text))
    assert scattered <= {f"f32[{2 * SEQ},{E}]"}, scattered
    assert "ragged_dot" in text


# -- the combine, whose backward stays in expert order ----------------------

def buffer_and_routing(dtype, share, seed=31, t=2 * SEQ):
    """An expert buffer (t*K, D) in expert order with the routing that
    sorted it, as `MoEMLP` makes them: with a share, three of the E
    experts are held and their rows come first."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    idx = jax.random.randint(keys[0], (t, K), 0, E)
    key, here = idx.reshape(-1), None
    if share:
        here = (key >= 2) & (key < 5)
        key = jnp.where(here, key - 2, 3)
        here = here.reshape(t, K)
    order = jnp.argsort(key, stable=True)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(t * K))
    out = jax.random.normal(keys[1], (t * K, D)).astype(dtype)
    gate = jax.nn.softmax(jax.random.normal(keys[2], (t, K)))
    # as the step hands it over: the cotangent of a result rounded to
    # the buffer's dtype
    dy = jax.random.normal(keys[3], (t, D)).astype(dtype).astype(jnp.float32)
    return out, gate, order, inv, here, dy


def plain_combine(out, gate, order, inv, here):
    """What `_combine_rows` replaces: un-permute, select, weigh, sum."""
    rows = out[inv].reshape(*gate.shape, -1)
    if here is not None:
        rows = jnp.where(here[..., None], rows, 0)
    return jnp.einsum("tk,tkd->td", gate.astype(out.dtype), rows,
                      preferred_element_type=jnp.float32)


def value_and_gradients(combine, out, gate, order, inv, here, dy):
    y, back = jax.vjp(lambda o, g: combine(o, g, order, inv, here),
                      out, gate)
    return (y, *back(dy))


def rel_err(a, b):
    a, b = (np.asarray(v, np.float64) for v in (a, b))
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("share", [False, True], ids=["all_held", "a_share"])
@pytest.mark.parametrize("dtype, tol", [
    (jnp.float32, 1e-6),
    # the plain form rounds the gates and d(gate) to bfloat16, half a
    # unit of 2**-8 each: read 2.7e-3 on d(out), 1.8e-3 on d(gate)
    (jnp.bfloat16, 6e-3)], ids=["float32", "bfloat16"])
def test_combine_backward_in_expert_order_is_the_plain_gradient(
        dtype, tol, share):
    args = buffer_and_routing(dtype, share)
    mine = value_and_gradients(tfm._combine_rows, *args)
    want = value_and_gradients(plain_combine, *args)
    np.testing.assert_array_equal(mine[0], want[0])   # one forward
    for a, b in zip(mine[1:], want[1:]):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert rel_err(a, b) < tol
    if share:
        out, gate, order, inv, here, dy = args
        # the slots that are not held: nothing to their rows, nothing
        # from their gates
        grouped = int(here.sum())
        assert 0 < grouped < here.size // 2
        assert not np.asarray(mine[1][grouped:], np.float32).any()
        assert not np.asarray(mine[2])[~np.asarray(here)].any()
        assert np.asarray(mine[2])[np.asarray(here)].all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_combine_takes_nothing_from_the_rows_past_the_groups(dtype):
    """The chip's grouped matmul leaves the buffer's tail unwritten
    (`tools/ragged_dot_tail.py`): with NaN there the value, d(gate) and
    d(out) are finite and what they were."""
    out, gate, order, inv, here, dy = buffer_and_routing(dtype, True)
    grouped = int(here.sum())
    poisoned = out.at[grouped:].set(jnp.nan)
    clean = value_and_gradients(tfm._combine_rows, out, gate, order, inv,
                                here, dy)
    dirty = value_and_gradients(tfm._combine_rows, poisoned, gate, order,
                                inv, here, dy)
    for a, b in zip(clean, dirty):
        assert np.isfinite(np.asarray(b, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a zero gate in the select's place would not do
    leaky = value_and_gradients(tfm._combine_rows, poisoned,
                                jnp.where(here, gate, 0), order, inv,
                                None, dy)
    assert np.isnan(np.asarray(leaky[0])).any()
    assert np.isnan(np.asarray(leaky[2])).any()


def expert_buffer_gathers(cfg, variables, tokens):
    """The gathers with a (T*k, d) result in the gradient of a model's
    loss, as (inside a block's replay-and-backward?, rows of the
    operand), and the results of its scatter-adds."""
    from tests.test_transformer import _equations
    model = tfm.Transformer(cfg)
    state = {c: v for c, v in variables.items() if c != "params"}

    def loss(p):
        out = model.apply({"params": p, **state}, tokens, train=True,
                          mutable=list(state))[0]
        return jnp.mean(out ** 2)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(variables["params"])
    rows = tokens.size * cfg.moe_top_k
    gathers, scattered = [], set()
    for inside, e in _equations(jaxpr.jaxpr):
        shape = e.outvars[0].aval.shape if e.outvars else None
        if e.primitive.name == "gather" and shape == (rows, cfg.d_model):
            gathers.append((inside, e.invars[0].aval.shape[0]))
        if e.primitive.name == "scatter-add":
            scattered.add(shape)
    return gathers, scattered


def test_remat_replays_no_gather_of_the_expert_buffer(tree, tokens):
    """Five gathers with a (T*k, d) result a layer under `--remat on`:
    the dispatch's and the combine's forward; the dispatch's again in
    the replay, dy's rows and the dispatch's backward. Two of the five
    read the buffer (the combine's forward and the dispatch's backward);
    the replay's copy of the combine's went with its only reader."""
    cfg = small(remat=True)
    gathers, scattered = expert_buffer_gathers(cfg, {"params": tree}, tokens)
    t, rows = tokens.size, tokens.size * K
    assert sorted(gathers) == sorted(
        LAYERS * [(False, t), (False, rows), (True, t), (True, t),
                  (True, rows)])
    # top_k's backward and the embedding's: no row of the buffer is added
    assert scattered == {(t, E), (VOCAB, D)}


def test_combine_keeps_the_buffer_in_expert_order_and_no_copy(tree):
    """What the layer's backward holds of the buffer's size: arrays of
    (T*k, .) rows, in expert order; nothing of the shape (T, k, d)."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, D))
    _, back = jax.vjp(
        lambda p, x: moe_layer({"block0": {"moe_mlp": p}}, x)[0],
        tree["block0"]["moe_mlp"], x)
    shapes = [leaf.shape for leaf in jax.tree.leaves(back)]
    assert (2 * SEQ * K, D) in shapes
    assert (2 * SEQ, K, D) not in shapes


def test_rope_alone_matches_the_reference():
    x = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, HEADS, 16))
    ref = jnp.stack([plain.rope(row, 10000.0) for row in x])
    assert float(jnp.abs(tfm.rope(x, 10000.0) - ref).max()) < 1e-6
    # position 0 is not turned; the turn is a rotation
    np.testing.assert_allclose(tfm.rope(x, 10000.0)[:, 0], x[:, 0],
                               atol=1e-7)
    np.testing.assert_allclose(jnp.linalg.norm(tfm.rope(x, 1e4), axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_qk_norm_alone_matches_the_reference(tree):
    x = jax.random.normal(jax.random.PRNGKey(9), (SEQ, D))
    attn = plain.from_program(tree)["blocks"][0]["attn"]
    scale = tree["block0"]["attn"]["q_norm"]["scale"]
    mine = tfm.RMSNorm(1e-5, jnp.float32).apply(
        {"params": {"scale": scale}}, x @ attn["q"])
    ref = plain.rms(x @ attn["q"], attn["q_norm"], 1e-5)
    assert float(jnp.abs(mine - ref).max()) < 1e-6
    # over all heads' features at once, not head by head
    per_head = plain.rms((x @ attn["q"]).reshape(SEQ, HEADS, -1),
                         attn["q_norm"].reshape(HEADS, -1), 1e-5)
    assert float(jnp.abs(mine - per_head.reshape(SEQ, -1)).max()) > 1e-2


def test_fused_loss_with_moe_is_the_dense_logits_loss(tree, tokens):
    cfg = small()
    kw = dict(aux_weight=cfg.moe_aux_weight, z_weight=cfg.moe_z_weight)
    state = state_of(tree)
    dense, dm = tfm.lm_loss_fn(state, tree, {"tokens": tokens}, **kw)
    fused, fm = tfm.lm_loss_fused(state, tree, {"tokens": tokens},
                                  block_rows=32, **kw)
    assert abs(float(dense) - float(fused)) < 1e-5
    assert set(dm) == set(fm) == {"ppl", "moe_balance", "moe_dropped",
                                  "moe_z", "moe_max_load"}
    gd = jax.grad(lambda p: tfm.lm_loss_fn(
        state, p, {"tokens": tokens}, **kw)[0])(tree)
    gf = jax.grad(lambda p: tfm.lm_loss_fused(
        state, p, {"tokens": tokens}, block_rows=32, **kw)[0])(tree)
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gf)):
        assert float(jnp.abs(a - b).max()) < 1e-5


@pytest.mark.parametrize("loss", [tfm.lm_loss_fn, tfm.lm_loss_fused])
def test_loss_takes_the_routers_weights_from_the_models_config(
        loss, tree, tokens):
    """No keyword: a moe model's own `moe_aux_weight` / `moe_z_weight`;
    a keyword given wins, and a state bound to another model says
    nothing once ``apply_fn`` is."""
    cfg = small()
    batch = {"tokens": tokens}
    own, metrics = loss(state_of(tree), tree, batch)
    told, _ = loss(state_of(tree), tree, batch,
                   aux_weight=cfg.moe_aux_weight, z_weight=cfg.moe_z_weight)
    assert float(own) == float(told)
    assert {"moe_balance", "moe_z", "moe_max_load", "moe_dropped"} \
        <= set(metrics)
    ce_only, _ = loss(state_of(tree), tree, batch, aux_weight=0.0)
    assert float(ce_only) == pytest.approx(
        float(own) - cfg.moe_aux_weight * float(metrics["moe_balance"])
        - cfg.moe_z_weight * float(metrics["moe_z"]), abs=1e-5)
    heavier = tfm.Transformer(small(moe_aux_weight=1.0, moe_z_weight=0.0))
    rebound, _ = loss(state_of(tree), tree, batch, apply_fn=heavier.apply)
    assert float(rebound) == pytest.approx(
        float(ce_only) + float(metrics["moe_balance"]), abs=1e-5)


@pytest.mark.parametrize("loss", [tfm.lm_loss_fn, tfm.lm_loss_fused])
def test_a_dense_model_has_no_router_terms(loss, tokens):
    cfg = tfm.TransformerConfig(
        vocab_size=VOCAB, d_model=D, n_heads=HEADS, n_layers=1, d_ff=FF,
        max_len=SEQ, dtype=jnp.float32)
    model = tfm.Transformer(cfg)
    params = meta.unbox(model.init(jax.random.PRNGKey(2), tokens,
                                   train=False))["params"]
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.sgd(0.1))
    value, metrics = loss(state, params, {"tokens": tokens})
    assert set(metrics) == {"ppl"}
    assert float(value) == pytest.approx(float(jnp.log(metrics["ppl"])),
                                         abs=1e-5)


@pytest.mark.parametrize("what, least", [
    ("bf16", 3e-3), ("renormalised", 5e-2), ("dropped", 1e-2),
    ("interleaved_rope", 1e-2)])
def test_the_tolerance_refuses(tree, tokens, monkeypatch, what, least):
    """What `TOL` must not let through moves the logits by far more."""
    mine = program_logits(tree, tokens)
    if what == "bf16":
        other = program_logits(tree, tokens, dtype=jnp.bfloat16)
    elif what == "renormalised":
        other = program_logits(tree, tokens, moe_renorm=True)
    elif what == "dropped":
        # the reference without token 5's second expert, in every layer
        def lossy(x, w, idx, p):
            return plain_experts(x, w.at[5, 1].set(0.0), idx, p)
        plain_experts = plain.experts
        monkeypatch.setattr(plain, "experts", lossy)
        other = plain_logits(tree, tokens)
    else:
        # pairs (2i, 2i+1) turned together, GPT-J's convention
        def interleaved(x, theta):
            d = x.shape[-1]
            split = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
            out = rotate_half(split, theta)
            return jnp.stack([out[..., : d // 2], out[..., d // 2:]],
                             -1).reshape(x.shape)
        rotate_half = plain.rope
        monkeypatch.setattr(plain, "rope", interleaved)
        other = plain_logits(tree, tokens)
    moved = float(jnp.abs(mine - other.astype(jnp.float32)).max())
    assert moved > least > 50 * TOL, (what, moved)


def test_default_configuration_is_pinned_bit_for_bit():
    """The three dense cells run the default block: its parameter paths
    and its logits as they were before this file knew another block
    (checksum taken from the commit before, on this CPU backend)."""
    cfg = tfm.TransformerConfig(vocab_size=97, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_len=24,
                                dtype=jnp.float32)
    model = tfm.Transformer(cfg)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 97, (2, 24)),
                       jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(7), toks,
                                   train=False))["params"]
    block = ["attn/key/kernel", "attn/out/kernel", "attn/query/kernel",
             "attn/value/kernel", "ln_attn/bias", "ln_attn/scale",
             "ln_mlp/bias", "ln_mlp/scale", "mlp_in/kernel",
             "mlp_out/kernel"]
    assert sorted(traverse_util.flatten_dict(params, sep="/")) == sorted(
        [f"block{i}/{p}" for i in (0, 1) for p in block]
        + ["lm_head/kernel", "ln_final/bias", "ln_final/scale",
           "pos_embed", "tok_embed/embedding"])
    out = np.asarray(model.apply({"params": params}, toks, train=True))
    assert hashlib.sha256(out.tobytes()).hexdigest() == (
        "f896cf87150daac09fce9f0393d045cf33646be7e5a66281e279934fbd4c6fc4")


def test_default_moe_keeps_its_tables_and_gates():
    """moe=True without the preset: gelu experts `w_in`/`w_out`,
    renormalised gates, the same dropless dispatch."""
    cfg = tfm.TransformerConfig(vocab_size=VOCAB, d_model=D, n_heads=HEADS,
                                n_layers=1, d_ff=FF, max_len=SEQ,
                                dtype=jnp.float32, moe=True, n_experts=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, D))
    layer = tfm.MoEMLP(cfg)
    params = meta.unbox(layer.init(jax.random.PRNGKey(0), x))["params"]
    assert set(params) == {"router", "w_in", "w_out"}
    probs = jax.nn.softmax(x.reshape(-1, D) @ params["router"], -1)
    gate, idx = jax.lax.top_k(probs, 2)
    gate = gate / gate.sum(-1, keepdims=True)
    ref = sum(jnp.where((idx == e)[..., None], gate[..., None], 0.0).sum(1)
              * (jax.nn.gelu(x.reshape(-1, D) @ params["w_in"][e])
                 @ params["w_out"][e]) for e in range(4))
    out = layer.apply({"params": params}, x)
    assert float(jnp.abs(out.reshape(-1, D) - ref).max()) < TOL


@pytest.mark.parametrize("arch", ["gpt2", "olmoe"])
def test_choose_remat_counts_the_configurations_parameters(arch):
    """One layer of the published OLMoE holds 7.5 GB of state on a
    16 GB chip: 4 x 4096 tokens fit beside it without remat, 16 do
    not. A dense block of the same d_ff is 1/40 of that state and
    keeps 16 rows."""
    if arch == "olmoe":
        cfg = tfm.olmoe_config(n_layers=1, attention="flash")
    else:
        cfg = tfm.TransformerConfig(vocab_size=50304, d_model=2048,
                                    n_heads=16, n_layers=1, d_ff=1024,
                                    max_len=4096, attention="flash")
    hbm = 16 * 10 ** 9
    assert not tfm.choose_remat(cfg, 4, hbm_bytes=hbm)
    assert tfm.choose_remat(cfg, 16, hbm_bytes=hbm) == (arch == "olmoe")


def test_config_refuses_unknown_kinds():
    with pytest.raises(ValueError, match="norm"):
        tfm.TransformerConfig(norm="batchnorm")
    with pytest.raises(ValueError, match="pos"):
        tfm.TransformerConfig(pos="alibi")


STEP = re.compile(r"step (\d+): loss=(\S+) .*moe_dropped=(\S+) ")
SMALL_JOB = ["--vocab", "128", "--d-model", "32", "--n-heads", "2",
             "--n-layers", "2", "--d-ff", "16", "--seq-len", "32",
             "--arch", "olmoe", "--n-experts", "8", "--moe-top-k", "2",
             "--fused-loss", "--moe", "--epochs", "1", "--warmup-steps", "2",
             "--lr", "1e-2"]


def test_lm_train_arch_olmoe_saves_restores_and_replays(tmp_path):
    """`lm_train --arch olmoe --fused-loss` on one device, through
    `make_train_step`, `TrainLoop` and the sharded `CheckpointManager`:
    a run saved every 4 steps and ended at 8 is resumed from its step-4
    checkpoint by a second run, whose replayed steps log the first
    run's losses. A process of its own: this one holds 8 devices."""
    import os
    import shutil
    import subprocess
    import sys
    ckpt = tmp_path / "ckpt"
    common = [sys.executable, "-m", "edl_tpu.examples.lm_train",
              "--data-dir", str(tmp_path / "data"), "--batch-size", "4",
              "--ckpt-dir", str(ckpt), "--ckpt-sharded", "--ckpt-steps", "4",
              "--ckpt-sync", *SMALL_JOB]
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
           "EDL_TPU_LOG_EVERY": "1",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)

    def run(argv):
        out = subprocess.run(argv, env=env, capture_output=True, text=True,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        return out.stderr, {int(s): (float(v), float(d))
                            for s, v, d in STEP.findall(out.stderr)}
    text, first = run([*common, "--make-synthetic", "2",
                       "--rows-per-file", "16"])
    assert "dropless, in the jit step" in text and "count=1" in text
    # the start line says which form of the experts the run measured
    assert "experts: gate|up one grouped product in the backward" in text
    assert sorted(first) == list(range(1, 9))
    assert all(d == 0.0 for _, d in first.values())
    # throw the later checkpoints away: resume from step 4
    kept = sorted(ckpt.glob("ckpt-*"), key=lambda p: int(p.name[5:]))
    assert len(kept) >= 2
    for p in kept[1:]:
        shutil.rmtree(p)
    text, second = run(common)
    assert "restored checkpoint" in text
    assert sorted(second) == [5, 6, 7, 8]
    for step in second:
        assert second[step][0] == pytest.approx(first[step][0], abs=1e-4)


def test_lm_train_arch_olmoe_over_an_ep_mesh(tmp_path, monkeypatch):
    """Several devices: the same block through the manual ep region,
    its capacity router and the wire, with the three gated tables and
    the streamed CE."""
    import logging

    from edl_tpu.examples.lm_train import main
    monkeypatch.setenv("EDL_TPU_LOG_EVERY", "1")
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    loggers = [logging.getLogger(n) for n in ("edl_tpu.examples.lm_train",
                                              "edl_tpu.train.loop")]
    for lg in loggers:
        lg.addHandler(handler)
    try:
        assert main(["--data-dir", str(tmp_path), "--make-synthetic", "2",
                     "--rows-per-file", "16", "--batch-size",
                     str(jax.device_count()), *SMALL_JOB]) == 0
    finally:
        for lg in loggers:
            lg.removeHandler(handler)
    text = "\n".join(seen)
    assert "moe path: E=8 top_k=2 dispatch=" in text
    assert "moe_dropped=" in text
