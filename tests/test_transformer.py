"""Transformer LM: forward, sharded init, full dp*fsdp*tp*sp train step."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.models import transformer as tfm
from edl_tpu.models.transformer import (Transformer, TransformerConfig,
                                        lm_loss_fn)
from edl_tpu.ops.flash_attention import force_interpret_kernels
from edl_tpu.parallel import mesh as mesh_lib, sharding as shd
from edl_tpu.train.state import TrainState
from edl_tpu.train.step import make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P

VOCAB = 64


def tiny_cfg(**kw):
    defaults = dict(vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=2,
                    d_ff=64, max_len=64, dtype=jnp.float32)
    defaults.update(kw)
    return TransformerConfig(**defaults)


def tokens(b=4, s=16, key=0):
    return jax.random.randint(jax.random.PRNGKey(key), (b, s), 0, VOCAB)


def test_forward_shape_single_device():
    cfg = tiny_cfg()
    model = Transformer(cfg)
    toks = tokens()
    variables = model.init(jax.random.PRNGKey(0), toks, train=False)
    logits = model.apply(variables, toks, train=False)
    assert logits.shape == (4, 16, VOCAB)
    assert logits.dtype == jnp.float32


def test_logical_to_spec_rules():
    mesh = mesh_lib.make_mesh(
        mesh_lib.MeshSpec({"dp": 2, "fsdp": 2, "tp": 2}))
    assert shd.logical_to_spec(("vocab", "embed"), mesh=mesh) == \
        P("tp", "fsdp")
    assert shd.logical_to_spec(("batch", "seq", "embed"), mesh=mesh) == \
        P(("dp", "fsdp"))
    # Axes absent from the mesh drop out.
    small = mesh_lib.make_mesh(mesh_lib.MeshSpec({"dp": 8}))
    assert shd.logical_to_spec(("vocab", "embed"), mesh=small) == P()


def test_sharded_init_places_params():
    mesh = mesh_lib.make_mesh(
        mesh_lib.MeshSpec({"dp": 2, "fsdp": 2, "tp": 2}))
    cfg = tiny_cfg(mesh=mesh)
    model = Transformer(cfg)
    toks = tokens()
    variables = shd.init_sharded(
        lambda: model.init(jax.random.PRNGKey(0), toks, train=False), mesh)
    emb = variables["params"]["tok_embed"]["embedding"]
    # vocab dim deliberately unsharded (gather can't partition over it —
    # would force involuntary table remat); embed dim splits over tp.
    assert emb.sharding.spec == P(None, "tp")
    mlp = variables["params"]["block0"]["mlp_in"]["kernel"]
    assert mlp.sharding.spec == P("fsdp", "tp")


def test_full_train_step_dp_fsdp_tp_sp():
    # The dryrun_multichip shape: all four axes live at once.
    mesh = mesh_lib.make_mesh(
        mesh_lib.MeshSpec({"dp": 2, "fsdp": 1, "tp": 2, "sp": 2}))
    cfg = tiny_cfg(mesh=mesh)
    model = Transformer(cfg)
    toks = tokens(b=4, s=16)
    variables = shd.init_sharded(
        lambda: model.init(jax.random.PRNGKey(0), toks, train=False), mesh)
    state = TrainState.create(apply_fn=model.apply,
                              params=variables["params"],
                              tx=optax.adamw(1e-3))
    step = make_train_step(lm_loss_fn, donate=False)
    batch = {"tokens": jax.device_put(
        toks, NamedSharding(mesh, P("dp", "sp")))}
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    # Params stayed sharded through the update.
    emb = state.params["tok_embed"]["embedding"]
    assert emb.sharding.spec == P(None, "tp")


def test_remat_matches_no_remat():
    cfg = tiny_cfg()
    model = Transformer(cfg)
    toks = tokens()
    variables = model.init(jax.random.PRNGKey(0), toks, train=False)
    cfg_r = tiny_cfg(remat=True)
    out = model.apply(variables, toks, train=False)
    out_r = Transformer(cfg_r).apply(variables, toks, train=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


def test_remat_gradients_match_no_remat():
    """Through the flash call's own backward rule (its XLA path here):
    the kept `o` and `lse` are the values the backward would rebuild."""
    toks = tokens()
    variables = Transformer(tiny_cfg()).init(jax.random.PRNGKey(0), toks,
                                             train=False)
    ga, gb = (jax.grad(lambda p, r=r: jnp.mean(Transformer(
        tiny_cfg(remat=r, attention="flash")).apply(
            {"params": p}, toks, train=False) ** 2))(variables["params"])
        for r in (False, True))
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


# -- what a rematerialised block keeps (`transformer.KEPT`) ----------------
# Read off the jaxpr of the loss's gradient: a block's replay and its
# backward are one `remat2` equation with `differentiated` set; what
# stands outside is the forward.

B, S, LAYERS = 2, 128, 2
# every kernel a shape of its own, so a product is known by its operand
GQA = dict(d_model=32, n_heads=4, n_kv_heads=2, head_size=16, d_ff=48,
           n_layers=LAYERS, max_len=S, norm="rmsnorm", pos="rope",
           mlp_gated=True, attention="flash", remat=True)
OUT_KERNEL, MLP_OUT_KERNEL = (4, 16, 32), (48, 32)


def _equations(jaxpr, replayed=False):
    """(inside a block's replay-and-backward?, equation), nested
    jaxprs walked."""
    for e in jaxpr.eqns:
        inside = replayed or (e.primitive.name == "remat2"
                              and e.params["differentiated"])
        yield inside, e
        for value in e.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, inside)


def _gradient_equations(**kw):
    cfg = tiny_cfg(**{**GQA, **kw})
    toks = tokens(b=B, s=S)
    model = Transformer(cfg)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), toks, train=False))["params"]
    with force_interpret_kernels():
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.mean(model.apply(
            {"params": p}, toks, train=False) ** 2)))(params)
    return list(_equations(jaxpr.jaxpr))


def _forward_kernels(equations):
    """Runs of the `flash_fwd` kernel: (in the forward, in a replay)."""
    where = [inside for inside, e in equations
             if e.primitive.name == "pallas_call"
             and e.params["name"] == "flash_fwd"]
    return where.count(False), where.count(True)


def _replayed_products(equations, kernel):
    """Forward products with a kernel of this shape inside the replays:
    the backward's own product with it (dx) has another result."""
    return sum(
        inside and e.primitive.name == "dot_general"
        and kernel in [v.aval.shape for v in e.invars]
        and e.outvars[0].aval.shape == (B, S, GQA["d_model"])
        for inside, e in equations)


@pytest.mark.parametrize("kept, replays", [(None, 0), ((), LAYERS)],
                         ids=["kept", "nothing_kept"])
def test_remat_runs_each_flash_forward_once(monkeypatch, kept, replays):
    """One forward kernel a layer with `o` and `lse` kept, and a second
    one in the replay with the policy's names taken out: the count is
    the policy's doing."""
    if kept is not None:
        monkeypatch.setattr(tfm, "KEPT", kept)
    assert _forward_kernels(_gradient_equations()) == (LAYERS, replays)


@pytest.mark.parametrize("sandwich", [False, True],
                         ids=["plain", "sandwich_norm"])
@pytest.mark.parametrize("kept, replays", [(None, 0), ((), LAYERS)],
                         ids=["kept", "nothing_kept"])
def test_remat_replays_no_output_projection(monkeypatch, sandwich, kept,
                                            replays):
    """The first half's kept result leaves `attn/out` out of the replay,
    with a norm behind it or none; under a sandwich norm the second
    half's leaves `mlp_out` out too."""
    if kept is not None:
        monkeypatch.setattr(tfm, "KEPT", kept)
    equations = _gradient_equations(sandwich_norm=sandwich)
    assert _replayed_products(equations, OUT_KERNEL) == replays
    if sandwich:
        assert _replayed_products(equations, MLP_OUT_KERNEL) == replays


@pytest.mark.parametrize("build, kw, wanted", [
    # trinity-mini-p1-e16v8 and granite-4.0-h-micro-p1v4 at 2 x 8192
    (tfm.afmoe_config, dict(n_layers=5, layer_types=("sliding",) * 4
                            + ("full",), n_dense_layers=1),
     {"flash_o": 5 * 134217728, "flash_lse": 5 * 2097152,
      "block_mixer_out": 5 * 67108864, "block_mlp_out": 5 * 67108864}),
    (tfm.granite_hybrid_config, dict(n_layers=10),
     {"flash_o": 67108864, "flash_lse": 2097152,
      "block_mixer_out": 10 * 67108864, "block_mlp_out": 0}),
], ids=["afmoe", "hybrid"])
def test_kept_bytes_at_the_two_remat_cells_shapes(build, kw, wanted):
    cfg = build(max_len=8192, attention="flash", remat=True, **kw)
    assert tfm.kept_bytes(cfg, 2) == wanted
    assert set(wanted) == set(tfm.KEPT)
    # attention that does not go through the flash call names no `o`
    dense = tfm.kept_bytes(build(max_len=8192, attention="dense", **kw), 2)
    assert dense["flash_o"] == dense["flash_lse"] == 0
